"""Run one benchmark cell once on the chip(s) of this machine.

    python benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints one JSON result line last on standard output, and the numbers
the correctness check compared, each beside its limit, as the last
lines on standard error. Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for. JAX's persistent
compilation cache lives in ``.jax_cache/`` at the checkout's root unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # cache every program, however quick to compile or small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if cell is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); the "
              f"benchmark runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}",
          file=sys.stderr)
    enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import harness

    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, devices)
    for line in harness.compared_lines(out):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
