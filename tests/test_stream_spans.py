"""The streaming front end's profiler spans and counters
(serving/stream.py).

A two-tenant LKF front end pumps under ``jax.profiler`` past one
checkpoint; the recorded ``katana.*`` host spans are read back from the
``.xplane.pb`` with ``jax.profiler.ProfileData`` and counted against
what the front end did: one ``katana.pump`` per ``pump()``, one
``katana.form`` per live shard and pump, one ``katana.dispatch`` and one
``katana.select`` per shard and pump that had a plan, one
``katana.snapshot`` per returned update, one ``katana.checkpoint`` per
checkpoint written, and every child span inside a ``katana.pump``.

A process holds one profiler session at a time: every profiled test of
the suite lives in this file, so ``--dist loadfile`` runs them on one
worker, one after another.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.filters import make_cv_lkf
from repro.core.tracker import TrackerConfig
from repro.serving import stream
from repro.serving.stream import ServiceTier, StreamConfig, StreamFrontEnd

TRACKER = TrackerConfig(capacity=8, max_meas=4)
CHILDREN = (stream.FORM_SPAN, stream.DISPATCH_SPAN, stream.SELECT_SPAN,
            stream.SNAPSHOT_SPAN, stream.CHECKPOINT_SPAN)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def targets(frame: int) -> np.ndarray:
    """Two targets moving steadily: tracks confirm after a few frames."""
    return np.float32([[1.0 + 0.1 * frame, 2.0, 0.0],
                       [-3.0, 0.5 - 0.1 * frame, 1.0]])


def program_spans(trace_dir) -> dict:
    """{span name: [(start_ns, end_ns)]} of the ``katana.`` host spans
    in the one trace written under ``trace_dir``."""
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("katana."):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Pump a two-tenant LKF front end (two shards of two lanes, one
    tenant on each) under the profiler, with pumps where both, one or
    neither tenant has a frame. Returns (front end, what each pump did,
    checkpoints before the first pump, spans)."""
    root = tmp_path_factory.mktemp("spans")
    front = StreamFrontEnd(make_cv_lkf(),
                           StreamConfig(n_shards=2, lanes_per_shard=2,
                                        checkpoint_every=3),
                           TRACKER, ckpt_dir=str(root / "ckpt"),
                           clock=FakeClock())
    front.attach("a")
    front.attach("b")
    assert front.tenants["a"].shard != front.tenants["b"].shard
    ckpt0 = front.stats.checkpoints
    # which tenants submit a frame before each pump
    schedule = [("a", "b")] * 4 + [("a",), ()] + [("a", "b")] * 3 + [("b",)]
    frame = {"a": 0, "b": 0}
    pumps = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(root / "trace"), profiler_options=opts)
    try:
        for who in schedule:
            for name in who:
                front.submit(name, targets(frame[name]))
                frame[name] += 1
            before = front.stats.checkpoints
            ups = front.pump()
            pumps.append(dict(shards=len({u.shard for u in ups.values()}),
                              updates=len(ups),
                              checkpoints=front.stats.checkpoints - before))
    finally:
        jax.profiler.stop_trace()
    return front, pumps, ckpt0, program_spans(root / "trace")


def test_span_counts_follow_the_pumps(traced):
    front, pumps, ckpt0, spans = traced
    st = front.stats
    assert len(spans[stream.PUMP_SPAN]) == len(pumps)
    # one batch formed per live shard and pump, planned or not
    assert len(spans[stream.FORM_SPAN]) == 2 * len(pumps)
    dispatched = sum(p["shards"] for p in pumps)
    assert dispatched == st.dispatches < 2 * len(pumps)
    assert len(spans[stream.DISPATCH_SPAN]) == dispatched
    assert len(spans[stream.SELECT_SPAN]) == dispatched
    assert len(spans[stream.SNAPSHOT_SPAN]) == st.applied == \
        sum(p["updates"] for p in pumps)
    assert st.checkpoints - ckpt0 == sum(p["checkpoints"] for p in pumps) > 0
    assert len(spans[stream.CHECKPOINT_SPAN]) == st.checkpoints - ckpt0


def test_child_spans_nest_in_their_pump(traced):
    front, pumps, _, spans = traced
    pump_spans = spans[stream.PUMP_SPAN]
    for (a, b), p in zip(pump_spans, pumps):
        inside = {n: [s for s in spans[n] if a <= s[0] and s[1] <= b]
                  for n in CHILDREN}
        assert len(inside[stream.FORM_SPAN]) == 2
        assert len(inside[stream.DISPATCH_SPAN]) == p["shards"]
        assert len(inside[stream.SELECT_SPAN]) == p["shards"]
        assert len(inside[stream.SNAPSHOT_SPAN]) == p["updates"]
        assert len(inside[stream.CHECKPOINT_SPAN]) == p["checkpoints"]
        # a shard's batch is formed before its step runs, and its lanes
        # are selected after it
        for d0, d1 in inside[stream.DISPATCH_SPAN]:
            assert any(f1 <= d0 for _, f1 in inside[stream.FORM_SPAN])
            assert any(d1 <= s0 for s0, _ in inside[stream.SELECT_SPAN])
    # no child span outside a pump
    for n in CHILDREN:
        for s0, s1 in spans[n]:
            assert any(a <= s0 and s1 <= b for a, b in pump_spans), n


def test_lanes_dispatched_counts_lanes_per_dispatch(traced, tmp_path):
    front, *_ = traced
    # one tenant per shard: each dispatch carries one lane
    assert front.stats.lanes_dispatched == front.stats.dispatches
    # two tenants on one shard: a dispatch carries the lanes with a frame
    fe = StreamFrontEnd(make_cv_lkf(), StreamConfig(n_shards=1,
                                                    lanes_per_shard=3),
                        TRACKER, ckpt_dir=str(tmp_path), clock=FakeClock())
    fe.attach("a")
    fe.attach("b")
    for who in [("a", "b"), ("a",), ("a", "b"), ("b",)]:
        for name in who:
            fe.submit(name, targets(0))
        fe.pump()
    assert fe.stats.dispatches == 4
    assert fe.stats.lanes_dispatched == 6 == fe.stats.applied


def test_step_traces_once_per_tier(tmp_path):
    # a model of its own, so no earlier test has traced its steps
    fe = StreamFrontEnd(make_cv_lkf(),
                        StreamConfig(n_shards=1, lanes_per_shard=2,
                                     queue_depth=4, degrade_at=0.3,
                                     coast_at=0.9, reject_at=0.95),
                        TRACKER, ckpt_dir=str(tmp_path), clock=FakeClock())
    fe.attach("a")
    assert fe.stats.step_traces == 0
    for f in range(4):
        fe.submit("a", targets(f))  # load 0.25 -> FULL
        assert fe.effective_tier() == ServiceTier.FULL
        fe.pump()
        # the FULL step traces on its first dispatch only
        assert fe.stats.step_traces == 1
    fe.submit("a", targets(4))
    for f in range(5, 8):
        fe.submit("a", targets(f))  # load 0.5 -> WIDE_GATE
        assert fe.effective_tier() == ServiceTier.WIDE_GATE
        fe.pump()
        assert fe.stats.step_traces == 2
    assert fe.stats.dispatches == 7
