"""Tile autotuner CLI: measure lane_tile/time_chunk candidates per
(kernel, bank size) under the ACTIVE execution mode and persist the
winners to the checked-in table the ops wrappers consult
(src/repro/kernels/katana_bank/tuned.json — see autotune.py there for
the format and lookup rules).

    PYTHONPATH=src python -m benchmarks.autotune [--Ns 64,256] [--T 16]
        [--out PATH] [--dry-run]

Entries are keyed ``backend/mode`` with the RESOLVED mode, so a table
tuned on this CPU container only ever drives cpu/interpret runs; a TPU
machine re-running the CLI adds tpu/compiled rows next to them instead
of overwriting. Candidates that fail to build (tile constraints) are
skipped, not fatal — the table is advisory and the static defaults in
autotune.STATIC_DEFAULTS always remain the fallback.
"""
from __future__ import annotations

import argparse
import json
import pathlib
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn
from repro.core.filters import get_filter, make_imm
from repro.execmode import active_mode
from repro.kernels.katana_bank import autotune as table_lib
from repro.kernels.katana_bank.ops import (katana_bank,
                                           katana_bank_sequence,
                                           katana_imm_sequence)

LANE_TILES = (64, 128, 256, 512)
TIME_CHUNKS = (256, 1024, 4096)
# IMM lane tiles are per-model-slot (K models resident per program);
# 0 keeps ops' LANE_TILE//K power-of-two heuristic in the race
IMM_LANE_TILES = (0, 32, 64, 128)
IMM_TIME_CHUNKS = (16, 64, 256)


def _best(candidates, measure) -> Optional[Dict]:
    """Race the candidate configs; None when every one failed."""
    best = None
    for cfg in candidates:
        try:
            us = measure(**cfg)
        except Exception as e:  # noqa: BLE001 - tile-constraint rejects
            print(f"    skip {cfg}: {type(e).__name__}: {e}")
            continue
        print(f"    {cfg} -> {us:.1f} us/frame")
        if best is None or us < best["us_per_frame"]:
            best = dict(cfg, us_per_frame=round(us, 2))
    return best


def tune(Ns=(64, 256), T: int = 16, rounds: int = 2,
         iters: int = 2) -> Dict:
    """Measure all kernels at all bank sizes; return the entries dict
    for ``write_table`` (only the active backend/mode key)."""
    mode = active_mode()
    key = f"{mode.backend}/{mode.mode}"
    print(f"autotuning for {key}")
    lkf = get_filter("lkf")
    imm = make_imm()
    rng = np.random.default_rng(3)
    entries: Dict[str, Dict[str, List[Dict]]] = {}

    def record(kernel: str, N: int, best: Optional[Dict]) -> None:
        if best is not None:
            entries.setdefault(kernel, {}).setdefault(key, []).append(
                dict(N=N, **best))

    for N in Ns:
        print(f"  N={N}")
        zs = jnp.asarray(rng.normal(size=(T, N, lkf.m)) * 0.5, jnp.float32)
        x0 = jnp.asarray(np.tile(lkf.x0, (N, 1)), jnp.float32)
        P0 = jnp.asarray(np.tile(lkf.P0, (N, 1, 1)), jnp.float32)

        def m_bank(lane_tile):
            fn = lambda: katana_bank(lkf, x0, P0, zs[0], lane_tile=lane_tile)
            return min(time_fn(fn, iters=iters, warmup=1)
                       for _ in range(rounds)) * 1e6

        record("katana_bank", N,
               _best([dict(lane_tile=t) for t in LANE_TILES], m_bank))

        def m_seq(lane_tile, time_chunk):
            fn = lambda: katana_bank_sequence(
                lkf, zs, x0, P0, lane_tile=lane_tile, time_chunk=time_chunk)
            return min(time_fn(fn, iters=iters, warmup=1)
                       for _ in range(rounds)) / T * 1e6

        record("katana_bank_sequence", N, _best(
            [dict(lane_tile=t, time_chunk=c)
             for t in LANE_TILES for c in TIME_CHUNKS if c >= T], m_seq))

        zs9 = jnp.asarray(rng.normal(size=(T, N, imm.m)) * 0.5, jnp.float32)
        x9 = jnp.asarray(np.tile(imm.models[0].x0, (N, 1)), jnp.float32)
        P9 = jnp.asarray(np.tile(imm.models[0].P0, (N, 1, 1)), jnp.float32)

        def m_imm(lane_tile, time_chunk):
            fn = lambda: katana_imm_sequence(
                imm, zs9, x9, P9, lane_tile=lane_tile, time_chunk=time_chunk)
            return min(time_fn(fn, iters=iters, warmup=1)
                       for _ in range(rounds)) / T * 1e6

        record("katana_imm_sequence", N, _best(
            [dict(lane_tile=t, time_chunk=c)
             for t in IMM_LANE_TILES for c in IMM_TIME_CHUNKS], m_imm))

    return entries


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--Ns", default="64,256")
    ap.add_argument("--T", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--out", default=None,
                    help="table path (default: the checked-in tuned.json)")
    ap.add_argument("--dry-run", action="store_true",
                    help="measure + print, don't write the table")
    args = ap.parse_args(argv)
    Ns = tuple(int(n) for n in args.Ns.split(","))

    new = tune(Ns=Ns, T=args.T, rounds=args.rounds)
    # merge over the existing table: other kernels and other
    # backend/mode keys (e.g. a TPU's rows) survive a CPU re-tune
    path = table_lib.TUNED_PATH if args.out is None else \
        pathlib.Path(args.out)
    merged = {k: dict(v) for k, v in
              table_lib._load_table(str(path)).items()}
    for kernel, by_key in new.items():
        merged.setdefault(kernel, {}).update(by_key)
    print(json.dumps(merged, indent=2, sort_keys=True))
    if args.dry_run:
        return
    table_lib.write_table(merged, path)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
