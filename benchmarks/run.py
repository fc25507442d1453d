"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (0 in the us column for
pure-analysis rows).

  PYTHONPATH=src python -m benchmarks.run [--only table1,fig4,...] [--smoke]

``--smoke`` runs the drivers that accept shape parameters at tiny
shapes (T<=8, a handful of tracks) — the CI benchmark-smoke job uses it
to prove every driver still imports, runs and writes its BENCH json
without paying full benchmark time. Smoke numbers are NOT meaningful
perf data; don't commit the resulting json.
"""
from __future__ import annotations

import argparse
import sys
import traceback
from typing import List

ALL = ("accuracy", "fig4", "batching", "table1", "roofline", "scan_fusion",
       "imm", "frame", "serving")

SMOKE_KWARGS = {
    # roofline: the census/cost_analysis wiring is the point; tiny
    # shapes keep the compiles cheap while still emitting every row
    "roofline": dict(Ns=(8,), T=8, C=16, M=8),
    "scan_fusion": dict(Ns=(8,), T=8),
    "imm": dict(N=4, T=8),
    # keeps the HLO-census rows small AND drives the sharded-IMM serving
    # rows at a 4-sensor fleet over however many host devices exist
    "batching": dict(N=8, imm_sensors=4, imm_frames=4),
    # tiny shapes: the fused-vs-einsum frame equivalence assert is the
    # point in CI; the timings at these shapes are not perf data.
    # sensors=8 so the 8-device sharded row actually runs under the
    # bench-smoke job's forced 8-device host platform
    "frame": dict(Cs=(16,), M=8, sensors=8, sensor_frames=4),
    # deterministic behavior (fake clock + seeded scenes), so the
    # served/recovered fractions the regression gate pins are exact
    # at these shapes; the fps column is machine noise in CI
    "serving": dict(tenants=3, cycles=24),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(ALL))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes: exercise the drivers, not the perf")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    wanted = [w for w in args.only.split(",") if w]
    csv: List[str] = []
    failed = []
    for name in wanted:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run(csv, **(SMOKE_KWARGS.get(name, {}) if args.smoke else {}))
        except Exception as e:  # noqa: BLE001
            failed.append((name, repr(e)))
            traceback.print_exc()
    print("name,us_per_call,derived")
    for line in csv:
        print(line)
    if failed:
        print(f"\n{len(failed)} bench module(s) failed:", file=sys.stderr)
        for n, e in failed:
            print(f"  {n}: {e}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
