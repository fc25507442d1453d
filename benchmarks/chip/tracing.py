"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A trace (``.xplane.pb``) holds one plane per TPU (``/device:TPU:<i>``)
whose ``XLA Ops`` line has one event per device operation, and a host
plane whose threads carry the benchmark's own spans (``bench.*``
``TraceAnnotation``s around its calls into the program). Both are on
the profiler's clock. The window is the ``bench.window`` span.

Device busy time is the union of the ``XLA Ops`` intervals: async
copies show there as their start and done ops, and whole programs
(``XLA Modules``) are containers of those ops, so neither is counted
twice. An operation is named by its HLO instruction name, without the
``%`` and the ``.N`` suffix: the frame kernel's op is
``katana_frame_step`` (the jitted function around its ``pallas_call``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%katana_frame_step.1 = (f32[...]) custom-call(...)`` ->
    ``katana_frame_step``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


@dataclass
class Trace:
    ops: dict      # device index -> [(op name, start_ns, end_ns)], sorted
    spans: list    # [(span name, start_ns, end_ns)], sorted by start
    window: tuple  # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def spans_named(self, name: str) -> list:
        return [(a, b) for n, a, b in self.spans if n == SPAN_PREFIX + name]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    return files[0]


def load(path: str) -> Trace:
    """Read a trace file (or the directory a trace was written to)."""
    ops, spans = read(path)
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"trace has {len(win)} {WINDOW_SPAN} spans, not 1")
    return Trace(ops, spans, win[0])


def read(path: str):
    """(device ops by chip, benchmark spans) of a trace file or
    directory, each sorted by start."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops, spans = defaultdict(list), []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                ops[int(dev.group(1))].extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not dev:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for v in ops.values():
        v.sort(key=lambda o: o[1])
    spans.sort(key=lambda s: s[1])
    return dict(ops), spans


# ------------------------------------------------------------ arithmetic

def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def overlap(merged, spans) -> float:
    """Nanoseconds of the disjoint ``merged`` intervals inside ``spans``
    (themselves merged first)."""
    total, j = 0, 0
    spans = union(spans)
    for a, b in spans:
        while j < len(merged) and merged[j][1] <= a:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < b:
            total += min(b, merged[k][1]) - max(a, merged[k][0])
            k += 1
    return float(total)


def busy(trace: Trace, device: int) -> list:
    """Disjoint device-busy intervals of one chip, clipped to the
    window."""
    lo, hi = trace.window
    return [(max(a, lo), min(b, hi))
            for a, b in union((s, e) for _, s, e in trace.ops.get(device, []))
            if b > lo and a < hi]


def busy_s(trace: Trace) -> float:
    """Seconds some operation ran, averaged over the chips traced."""
    devs = sorted(trace.ops)
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in busy(trace, d))
               for d in devs) / len(devs) / 1e9


def op_time_ns(trace: Trace, names) -> float:
    """Total device time of the ops named in ``names``, all chips, inside
    the window."""
    lo, hi = trace.window
    return float(sum(min(e, hi) - max(s, lo)
                     for v in trace.ops.values() for n, s, e in v
                     if n in names and e > lo and s < hi))


def op_count(trace: Trace, names) -> int:
    lo, hi = trace.window
    return sum(1 for v in trace.ops.values() for n, s, e in v
               if n in names and s >= lo and s < hi)


def device_ns_in(trace: Trace, span: str) -> float:
    """Device-busy nanoseconds, summed over chips, inside the host spans
    named ``span``."""
    spans = trace.spans_named(span)
    return sum(overlap(busy(trace, d), spans) for d in trace.ops)


def top_ops(trace: Trace, k: int = 10) -> list:
    """[[op name, seconds]] of the k ops that took the most device time
    in the window, summed over chips."""
    lo, hi = trace.window
    tot = defaultdict(int)
    for v in trace.ops.values():
        for n, s, e in v:
            if e > lo and s < hi:
                tot[n] += min(e, hi) - max(s, lo)
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[[label, seconds]]: device idle time in the window, averaged over
    chips and split by what the host was doing, the innermost benchmark
    span over each stretch of idle time (``none`` where it was in no
    span), largest first."""
    lo, hi = trace.window
    spans = sorted((a, b, n[len(SPAN_PREFIX):]) for n, a, b in trace.spans
                   if n != WINDOW_SPAN and b > lo and a < hi)
    # cut the window at every span edge; each piece takes the innermost
    # (latest-starting) span that covers it
    cuts = sorted({lo, hi} | {min(max(x, lo), hi)
                              for a, b, _ in spans for x in (a, b)})
    pieces, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= a:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] >= b]
        pieces.append((a, b, active[-1][2] if active else "none"))
    tot = defaultdict(float)
    devs = sorted(trace.ops) or [0]
    for d in devs:
        idle, t = [], lo
        for a, b in busy(trace, d):
            if a > t:
                idle.append((t, a))
            t = max(t, b)
        if t < hi:
            idle.append((t, hi))
        i = 0
        for a, b, name in pieces:
            while i < len(idle) and idle[i][1] <= a:
                i += 1
            q = i
            while q < len(idle) and idle[q][0] < b:
                tot[name] += min(b, idle[q][1]) - max(a, idle[q][0])
                q += 1
    return [[n, t / len(devs) / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k] if t > 0]
