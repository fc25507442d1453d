"""Readings for the correctness limits: the compared numbers of the
program on many seeds and of the lower-precision control on a few, at a
cell's own size, in one process.

    python benchmarks/chip/readings.py --workload <cell> \
        --program-seeds 1,2,3 --control-seeds 4,5,6 --seconds 5

The control is the program's own bfloat16 path. Prints one JSON line
per run: which side, the seed, and the compared numbers.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import jax

    from bench import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, traffic = harness.load_cell(bench, args.workload)
    for side, seeds in (("program", args.program_seeds),
                        ("control", args.control_seeds)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            t0 = time.monotonic()
            out = harness.run_loaded(
                bench, cell, cfg, traffic, seed, args.seconds, False, t0,
                devices, control=side == "control")
            print(json.dumps(dict(side=side, seed=seed,
                                  correct=out["correct"],
                                  compared={k: v["value"] for k, v in
                                            out["compared"].items()},
                                  metrics={k: v["value"] for k, v in
                                           out["metrics"].items()},
                                  attempted=out["attempted"],
                                  failed=out["failed"],
                                  wall_s=time.monotonic() - t0)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    sys.exit(main())
