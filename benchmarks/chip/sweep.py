"""Find a live configuration's knee: the most tenants whose windows keep
a median ``on_time_pct`` >= 99 over the seeds, with no growing backlog.
Runs the live loop once per tenant count and seed, in one process, and
prints one JSON line per run and one summary line per count.

    python benchmarks/chip/sweep.py --config <configs/x.json> \
        --traffic <traffic/y.json> --tenants 1,2,3 --seconds 51 \
        --seeds 11,12,13

The backlog grows when the last third of the window's frames wait
longer than the first third (median latency ratio > 1.5). Used once to
size a cell; a cell's traffic file then fixes its tenant count.
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--tenants", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)
    import jax

    from bench import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    import live

    cfg = json.loads((HERE / args.config).read_text())
    base = json.loads((HERE / args.traffic).read_text())
    seeds = [int(x) for x in args.seeds.split(",")]
    for n in [int(x) for x in args.tenants.split(",")]:
        traffic = dict(base, tenants=n)
        runs = []
        for seed in seeds:
            t0 = time.monotonic()
            rec, _, facts = live.run(cfg, traffic, seed, args.seconds,
                                     harness.Profile(None), devices)
            e2e = live.end_to_end(rec, cfg["fps"])
            w0, w1 = rec.window
            lat = rec.done - rec.due
            thirds = np.linspace(w0, w1, 4)
            med = [float(np.nanmedian(np.where(
                (rec.due >= a) & (rec.due < b), lat, np.nan)))
                for a, b in zip(thirds, thirds[1:])]
            runs.append(dict(
                tenants=n, seed=seed, on_time_pct=e2e["on_time_pct"],
                frame_p50_ms=e2e["frame_p50_ms"],
                frame_p95_ms=e2e["frame_p95_ms"],
                median_by_third_ms=[1e3 * m for m in med],
                backlog_grows=bool(med[2] > 1.5 * med[0]),
                pumps=len(rec.pump_t), frames_due=e2e["frames_due"],
                frames_served=e2e["frames_served"],
                compiles_in_window=rec.compiles, stats=facts["stats"],
                host=live.host_report(rec), wall_s=time.monotonic() - t0))
            print(json.dumps(runs[-1]), flush=True)
        on_time = float(np.median([r["on_time_pct"] for r in runs]))
        print(json.dumps(dict(
            tenants=n, seeds=seeds, median_on_time_pct=on_time,
            median_frame_p95_ms=float(np.median(
                [r["frame_p95_ms"] for r in runs])),
            backlog_grows=any(r["backlog_grows"] for r in runs),
            sustained=bool(on_time >= 99.0 and not any(
                r["backlog_grows"] for r in runs)))), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    sys.exit(main())
