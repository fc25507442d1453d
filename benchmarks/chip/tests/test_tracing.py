"""The trace reducer: its interval arithmetic on hand-made traces, and
its readings of a small trace recorded on a TPU v5e."""
import pathlib

import pytest

import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _trace(ops, spans, window):
    return tracing.Trace({0: sorted(ops, key=lambda o: o[1])},
                         sorted(spans, key=lambda s: s[1]), window)


def test_op_name():
    assert tracing.op_name("%katana_frame_step.1 = (f32[6,256]) "
                           "custom-call(...)") == "katana_frame_step"
    assert tracing.op_name("%fusion = f32[8] fusion(...)") == "fusion"


def test_union_and_overlap():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    merged = [(0, 3), (5, 9)]
    assert tracing.overlap(merged, [(2, 6)]) == 2.0
    assert tracing.overlap(merged, [(2, 6), (4, 8)]) == 4.0


def test_busy_idle_and_attribution():
    ops = [("k", 10, 20), ("f", 15, 30), ("k", 60, 70)]
    spans = [("bench.window", 0, 100), ("bench.pump", 5, 50),
             ("bench.dispatch", 8, 32), ("bench.snapshot", 40, 48)]
    tr = _trace(ops, spans, (0, 100))
    assert tracing.busy_s(tr) == pytest.approx(30e-9)
    assert tracing.op_time_ns(tr, {"k"}) == 20.0
    assert tracing.op_count(tr, {"k"}) == 2
    assert tracing.device_ns_in(tr, "pump") == 20.0
    assert dict(map(tuple, tracing.top_ops(tr))) == {"k": 20e-9,
                                                     "f": 15e-9}
    gaps = dict(map(tuple, tracing.idle_gaps(tr)))
    # idle: 0-10 (none 0-5, pump 5-8, dispatch 8-10), 30-60, 70-100
    assert gaps["dispatch"] == pytest.approx((2 + 0) * 1e-9 + 2e-9)
    assert gaps["snapshot"] == pytest.approx(8e-9)
    assert gaps["pump"] == pytest.approx((3 + 8 + 2) * 1e-9)
    assert gaps["none"] == pytest.approx((5 + 10 + 30) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(70e-9)


def test_recorded_trace():
    """A trace recorded on a TPU v5e: 5 pumps of a 4-lane LKF front end
    (C=256, M=64), each pump and dispatch in a benchmark span."""
    ops, spans = tracing.read(str(DATA / "lkf_live.xplane.pb"))
    pumps = [(a, b) for n, a, b in spans if n == "bench.pump"]
    tr = tracing.Trace(ops, spans, (pumps[0][0], pumps[-1][1]))
    assert list(tr.ops) == [0]
    n = tracing.op_count(tr, {"katana_frame_step"})
    disp = tr.spans_named("dispatch")
    # one frame kernel per dispatch, each inside its dispatch span
    assert n == len(disp) == len(pumps) == 5
    for _, s, e in (o for o in tr.ops[0] if o[0] == "katana_frame_step"):
        assert any(a <= s and e <= b for a, b in disp)
    busy = tracing.busy_s(tr)
    assert 0 < busy < tr.window_s
    assert tracing.device_ns_in(tr, "pump") == pytest.approx(busy * 1e9)
    idle = sum(t for _, t in tracing.idle_gaps(tr, k=100))
    assert idle + busy == pytest.approx(tr.window_s, rel=1e-6)


def test_lifecycle_reads_the_step_spans_only():
    """The tracker step's device time is the ops inside the ``dispatch``
    and ``select`` spans less the frame kernel; the checkpoint's and the
    snapshots' device ops in the same pump are the front end's."""
    import harness

    ops = [("katana_frame_step", 10, 14), ("fusion", 14, 20),
           ("select_fusion", 32, 35), ("slice", 40, 45), ("copy", 50, 52)]
    spans = [("bench.window", 0, 100), ("bench.pump", 5, 60),
             ("bench.dispatch", 8, 30), ("bench.select", 31, 36),
             ("bench.snapshot", 48, 54), ("bench.checkpoint", 38, 46)]
    ctx = harness.Context("live", {}, {}, _trace(ops, spans, (0, 100)), {})
    got = harness.read_metric("lifecycle_device_us", ctx)
    assert got == pytest.approx((6 + 3) / 1e3)
