"""The four-chip cell ``mot17-lkf-x4.cams30`` on the CPU: the whole
harness at a tiny size over four virtual devices, one front-end shard
each, and the ``fanout_stagger_ms`` reader on hand-made traces.

JAX fixes its device count when it starts, so the harness runs in one
child process started with ``--xla_force_host_platform_device_count=4``:
it runs this file as a script and prints an untraced and a traced result
line as JSON.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import CHIP, ROOT

import tracing

CELL = "mot17-lkf-x4.cams30"
SHARDS = 4


def _child() -> dict:
    import time

    import jax

    import harness
    import peaks
    from conftest import tiny

    # the CPU has no published peaks; the roofline readers need some
    harness.peaks_for = lambda kind: peaks.PEAKS["TPU v5 lite"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, traffic = tiny(bench, CELL)
    traffic["tenants"] = SHARDS  # one camera per shard, as the cell runs
    assert len(jax.devices()) == cfg["layout"]["shards"] == SHARDS

    def slow(step):
        # a step slower than the cameras' spacing (a quarter period): a
        # backlog builds, and pumps find several shards with frames due
        def run(*a):
            time.sleep(0.012)
            return step(*a)
        return run

    return {str(trace): harness.run_loaded(
        bench, cell, cfg, traffic, 3000000001, 1.0, trace,
        time.monotonic(), jax.devices(), wrap=slow if trace else None)
        for trace in (False, True)}


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_cell_runs_on_four_devices_and_is_correct(runs, bench):
    out, stderr = runs[0]["False"], runs[1]
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == SHARDS
    want = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert set(out["metrics"]) == want
    # the stats= line counts the pumps that fanned out over shards
    assert len(re.findall(r"'fanout_pumps': \d+", stderr)) == 2


def test_traced_cell_reads_the_fanout_stagger(runs):
    out, stderr = runs[0]["True"], runs[1]
    assert out["correct"], out["compared"]
    assert int(re.findall(r"'fanout_pumps': (\d+)", stderr)[-1]) > 0
    # the CPU trace has no TPU plane, but the host spans are there; the
    # slowed step makes every stagger at least one step long
    assert out["metrics"]["fanout_stagger_ms"]["value"] >= 12.0


def test_config_lists_its_camera_cut(bench):
    # the source's site has more cameras than one host sustains; the
    # configuration states the count the cell serves and lists the cut
    import harness

    cell, cfg, traffic = harness.load_cell(bench, CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cfg["cameras"] == traffic["tenants"] < SHARDS
    assert entry["reduced"] == cfg["reduced"] == ["cameras"]
    assert entry["source"] == cfg["source"]


def _reader():
    spec = importlib.util.spec_from_file_location(
        "fanout_stagger_ms", CHIP / "metrics" / "fanout_stagger_ms.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Ctx:
    def __init__(self, spans, window):
        self.trace = tracing.Trace({}, sorted(spans, key=lambda s: s[1]),
                                   window)


def test_fanout_stagger_reads_the_pumps_that_fanned_out():
    read = _reader()
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms),
             # one dispatch: no fan-out
             ("bench.pump", 10 * ms, 20 * ms),
             ("bench.dispatch", 11 * ms, 13 * ms),
             # three dispatches, started 2 and 7 ms after the first
             ("bench.pump", 30 * ms, 50 * ms),
             ("bench.dispatch", 31 * ms, 33 * ms),
             ("bench.dispatch", 33 * ms, 35 * ms),
             ("bench.dispatch", 38 * ms, 40 * ms),
             # a fan-out before the window is not read
             ("bench.pump", -20 * ms, -5 * ms),
             ("bench.dispatch", -19 * ms, -18 * ms),
             ("bench.dispatch", -10 * ms, -9 * ms)]
    assert read(_Ctx(spans, (0, 100 * ms))) == pytest.approx(7.0)


def test_fanout_stagger_is_silent_without_a_fanout():
    read = _reader()
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms),
             ("bench.pump", 10 * ms, 20 * ms),
             ("bench.dispatch", 11 * ms, 13 * ms),
             ("bench.pump", 30 * ms, 50 * ms),
             ("bench.dispatch", 31 * ms, 33 * ms)]
    assert read(_Ctx(spans, (0, 100 * ms))) is None
    assert read(_Ctx([("bench.window", 0, 100 * ms)], (0, 100 * ms))) is None


if __name__ == "__main__":
    print(json.dumps(_child()))
