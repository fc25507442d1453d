"""One run of one cell: set-up, the measured window, the metrics, the
check against the reference, and the result line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``traffic/<traffic>.json``, each per-layer metric in
``metrics/<metric>.py`` (a ``read(ctx)`` that returns a number, or None
where the run has nothing to read). A traffic mix's ``kind`` picks the
generator; ``live`` (open-loop cameras through the stream front end) is
the one there is.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import tempfile
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

import kernel_bytes  # noqa: E402
import live  # noqa: E402
import reference  # noqa: E402
import tracing  # noqa: E402
from peaks import peaks_for  # noqa: E402

# A --trace 1 run measures and traces a window of at most TRACE_SECONDS:
# the per-layer readings are means per dispatch or per call, and a trace
# of a longer window is large and slow to read.
TRACE_SECONDS = 5.0


def load_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return cell, cfg, traffic


def metrics_for(bench: dict, cell: dict, section: str) -> list:
    """The entries of ``section`` that this cell reports."""
    return [m for m in bench[section]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def read_metric(name: str, ctx) -> float | None:
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    kind: str
    cfg: dict
    traffic: dict
    trace: tracing.Trace
    peaks: dict
    run: dict = field(default_factory=dict)


class Profile:
    """The profiler around a run's window. ``start`` runs during set-up
    (starting a trace stalls the host for about a second), ``open`` marks
    the window's start with the ``bench.window`` span, ``close`` ends the
    span and writes the trace. Without a directory every step is a
    no-op."""

    def __init__(self, trace_dir: str | None):
        self.dir, self.span, self.started = trace_dir, None, False

    def start(self) -> None:
        import jax

        if self.dir is None:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # keeps the benchmark's spans
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True

    def open(self) -> None:
        from jax.profiler import TraceAnnotation

        if self.started and self.span is None:
            self.span = TraceAnnotation(tracing.WINDOW_SPAN)
            self.span.__enter__()

    def close(self) -> None:
        import jax

        if self.started:
            if self.span is not None:
                self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.started = False


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices, **kw) -> dict:
    """Run one cell once and return its result line (a dict)."""
    cell, cfg, traffic = load_cell(bench, name)
    return run_loaded(bench, cell, cfg, traffic, seed, seconds, trace,
                      t_start, devices, **kw)


def run_loaded(bench: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
               seconds: float, trace: bool, t_start: float, devices,
               wrap=None, control: bool = False) -> dict:
    """``run_cell`` with the cell's files already read. ``wrap`` breaks
    the program's timed path (tests); ``control`` puts the cell's
    lower-precision control, the program's own bfloat16 path, in the
    program's place."""
    kind = traffic["kind"]
    if kind != "live":
        raise KeyError(f"no generator for traffic kind {kind!r}")
    d0 = devices[0]
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    with tempfile.TemporaryDirectory(prefix="katana_trace_") as tmp:
        tracer = Profile(tmp if trace else None)
        rec, inputs, facts = live.run(cfg, traffic, seed, seconds, tracer,
                                      devices, wrap_step=wrap,
                                      control=control)
        e2e = live.end_to_end(rec, cfg["fps"])
        attempted = e2e["frames_due"]
        failed = attempted - e2e["frames_served"]
        setup_s = facts["setup_end"] - t_start
        device = dict(platform=d0.platform, kind=d0.device_kind,
                      count=len(devices),
                      memory_peak_bytes=int(facts["memory_peak_bytes"]))
        out = dict(correct=False, attempted=int(attempted),
                   failed=int(failed), metrics={}, device=device)
        if trace:
            tr = tracing.load(tmp)
            device.update(busy_s=tracing.busy_s(tr), window_s=tr.window_s)
            ctx = Context(kind, cfg, traffic, tr, peaks_for(d0.device_kind),
                          run=_run_facts(cfg, e2e, facts))
            for m in metrics_for(bench, cell, "per_layer"):
                v = read_metric(m["name"], ctx)
                if v is not None:
                    out["metrics"][m["name"]] = dict(value=float(v),
                                                     unit=m["unit"])
            out["breakdown"] = dict(device_ops=tracing.top_ops(tr),
                                    idle_gaps=tracing.idle_gaps(tr))
        else:
            vals = dict(e2e, setup_s=setup_s)
            for m in metrics_for(bench, cell, "end_to_end"):
                out["metrics"][m["name"]] = dict(value=float(vals[m["name"]]),
                                                 unit=m["unit"])
    print(f"compiles_in_window={rec.compiles} "
          f"traces_in_window={rec.traces} stats={facts['stats']}",
          file=sys.stderr)
    for line in live.host_report(rec):
        print(line, file=sys.stderr)
    got = live.check(cfg, traffic, rec, inputs, facts)
    lim = cfg["limits"][kind]
    compared = {k: dict(value=float(got[k]), limit=float(v))
                for k, v in lim.items()}
    out["correct"] = bool(all(c["value"] <= c["limit"]
                              for c in compared.values()))
    out["compared"] = compared
    return out


def _run_facts(cfg, e2e, facts) -> dict:
    t = cfg["tracker"]
    md = reference.model_from_config(cfg)
    return dict(gen_lag_ms=e2e["gen_lag_ms"],
                frame_bytes=kernel_bytes.frame_kernel_bytes(
                    facts["lanes"], t["capacity"], t["max_meas"], md.n, md.m,
                    md.K))


def compared_lines(out: dict) -> list:
    return [f"{k} {c['value']!r} limit {c['limit']!r}"
            for k, c in out["compared"].items()]
