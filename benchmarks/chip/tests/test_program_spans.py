"""The benchmark's spans around its calls into the front end still wrap
the program's own ``katana.*`` spans: a tiny traced LKF cell on the CPU,
read back from its ``.xplane.pb``."""
import jax
import pytest
from jax.profiler import ProfileData

import harness
import live
import tracing
from conftest import tiny


def _host_spans(trace_dir, prefix):
    """{name: [(start_ns, end_ns)]} of the host spans named ``prefix*``."""
    out = {}
    for plane in ProfileData.from_file(tracing.find_xplane(trace_dir)).planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


def _inside(inner, outer):
    return [s for s in inner if any(a <= s[0] and s[1] <= b
                                    for a, b in outer)]


@pytest.fixture(scope="module")
def spans(bench, tmp_path_factory):
    _, cfg, traffic = tiny(bench, "mot17-lkf.cams30")
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    live.run(cfg, traffic, 20250101, 1.0, harness.Profile(trace_dir),
             jax.devices())
    return _host_spans(trace_dir, "bench."), _host_spans(trace_dir, "katana.")


def test_every_hook_still_spans_a_program_call(spans):
    bench, program = spans
    for name in ("pump", "dispatch", "select", "snapshot", "checkpoint"):
        assert bench.get("bench." + name), name
        assert len(bench["bench." + name]) == \
            len(program["katana." + name]), name


@pytest.mark.parametrize("outer,inner", [
    ("bench.pump", "katana.pump"),
    ("bench.select", "katana.select"),
    ("bench.snapshot", "katana.snapshot"),
    ("bench.checkpoint", "katana.checkpoint"),
    # the program's dispatch span also holds the step's lookup, which
    # the benchmark wraps
    ("katana.dispatch", "bench.dispatch"),
])
def test_spans_nest(spans, outer, inner):
    both = {**spans[0], **spans[1]}
    assert _inside(both[inner], both[outer]) == both[inner]
