"""Tile autotuner for the katana_bank kernels.

``lane_tile`` (filters per program) and ``time_chunk`` (frames per
dispatch of the scan kernels) are the two knobs that decide how much of
the bank is resident per program and how big each dispatch's VMEM
blocks are. The right values depend on (kernel, shape, backend, mode)
— compiled TPU programs want the 256-lane tile the BlockSpecs were
shaped for, while the interpreter (and small banks) often prefer
smaller tiles — so the measured best per configuration is persisted to
a checked-in table, ``tuned.json`` next to this module, and the ops
wrappers consult it whenever a caller leaves ``lane_tile``/``time_chunk``
at their 0 ("tuned") defaults.

Table format (see docs/benchmarks.md):

    {"format": 1,
     "entries": {
       "<kernel>": {
         "<backend>/<mode>": [
            {"N": 64, "lane_tile": 128, "time_chunk": 32,
             "us_per_frame": 103.2}, ...]}}}

Lookup is by exact ``backend/mode`` key (a CPU/interpret entry never
drives a TPU/compiled run) and nearest ``N`` in log-space within the
matching list; misses fall back to the static defaults, so the table
is purely advisory — deleting it changes no semantics, only speed.
``python -m benchmarks.autotune`` regenerates it.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib
from typing import Dict, Optional

from repro.execmode import ExecMode, active_mode

TUNED_PATH = pathlib.Path(__file__).with_name("tuned.json")
TABLE_FORMAT = 1

# static fallbacks when the table has no matching entry
STATIC_DEFAULTS = {
    "katana_bank": dict(lane_tile=256),
    # time_chunk 0: the chunk whose whole-T blocks fit the scoped VMEM
    # (kernel.scan_time_chunk — 384 frames for cv-6/ctra-8 at 256 lanes)
    "katana_bank_sequence": dict(lane_tile=256, time_chunk=0),
    "katana_bank_imm": dict(lane_tile=256),
    "imm_bank_sequence": dict(lane_tile=256),
    # lane_tile 0 keeps ops' LANE_TILE//K track tile, at least 128
    "katana_imm_sequence": dict(lane_tile=0, time_chunk=64),
}


@functools.lru_cache(maxsize=1)
def _load_table(path_str: str) -> Dict:
    path = pathlib.Path(path_str)
    if not path.exists():
        return {}
    try:
        table = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    if table.get("format") != TABLE_FORMAT:
        return {}
    return table.get("entries", {})


def clear_cache() -> None:
    """Drop the cached table (tests rewrite it)."""
    _load_table.cache_clear()


def best_config(kernel: str, N: Optional[int] = None,
                mode: Optional[ExecMode] = None,
                path: Optional[pathlib.Path] = None) -> Dict:
    """The tuned {lane_tile, time_chunk, ...} entry for ``kernel`` at
    bank size ``N`` under ``mode`` (default: the active execution
    mode), or {} when the table has nothing for this configuration."""
    mode = mode or active_mode()
    entries = _load_table(str(path or TUNED_PATH))
    rows = entries.get(kernel, {}).get(f"{mode.backend}/{mode.mode}", [])
    if not rows:
        return {}
    if N is None or N <= 0:
        return dict(rows[0])
    # nearest bank size in log-space: tile choice scales multiplicatively
    best = min(rows, key=lambda r: abs(math.log(max(r.get("N", 1), 1))
                                       - math.log(max(N, 1))))
    return dict(best)


def tuned_lane_tile(kernel: str, N: Optional[int], default: int,
                    mode: Optional[ExecMode] = None) -> int:
    cfg = best_config(kernel, N, mode)
    tile = int(cfg.get("lane_tile", 0)) or default
    return tile


def tuned_time_chunk(kernel: str, N: Optional[int], default: int,
                     mode: Optional[ExecMode] = None) -> int:
    cfg = best_config(kernel, N, mode)
    return int(cfg.get("time_chunk", 0)) or default


def write_table(entries: Dict, path: Optional[pathlib.Path] = None) -> None:
    """Persist an autotuned entries dict (``benchmarks/autotune.py``
    builds it); clears the lookup cache so new defaults apply."""
    path = path or TUNED_PATH
    path.write_text(json.dumps(
        dict(format=TABLE_FORMAT,
             note=("measured best lane_tile/time_chunk per (kernel, "
                   "bank size, backend, execution mode); regenerate "
                   "with `python -m benchmarks.autotune`"),
             entries=entries), indent=2, sort_keys=True) + "\n")
    clear_cache()
