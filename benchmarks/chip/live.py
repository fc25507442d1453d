"""Live cells: open-loop camera tenants through ``StreamFrontEnd``.

Each tenant is a camera at the configuration's frame rate. Tenant phases
are staggered uniformly over one period, and every frame gets a seeded
jitter below a fraction of the period. One thread runs the loop: it
submits every frame whose due time has passed (deadline: due plus the
mix's ``deadline_periods`` periods), calls ``pump()`` if anything is
pending, and otherwise sleeps to the next due time. Latency runs from a
frame's due time to the pump that returned its ``TenantUpdate``.

The stream runs ``preroll_s`` seconds before the measured window, so
every bank holds confirmed tracks at steady occupancy when it opens. The
same applied frames, pre-roll included, are checked against the float64
reference afterwards (``check``).
"""
from __future__ import annotations

import gc
import tempfile
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

import reference
from scenes import SceneSpec, simulate

SPAN_PREFIX = "bench."


def program_model(cfg: dict):
    """The configuration's filter, built by the program under test."""
    from repro.core.filters import make_cv_lkf, make_imm

    f, dt = cfg["filter"], 1.0 / cfg["fps"]
    if f["kind"] == "lkf-cv6":
        return make_cv_lkf(dt=dt, q=f["q"], r=f["r"], p0=f["p0"])
    if f["kind"] == "imm-cv-ca-ct9":
        return make_imm(dt=dt, omega=f["omega"], p_stay=f["p_stay"],
                        q_cv=f["q"], q_ca=f["q_ca"], r=f["r"], p0=f["p0"])
    raise KeyError(f"unknown filter kind {f['kind']!r}")


def schedule(fps: float, tenants: int, frames: int, jitter: float,
             seed: int) -> np.ndarray:
    """(tenants, frames) due times in seconds from the stream's start:
    phase i/tenants of a period, plus a seeded jitter in
    [0, jitter · period)."""
    period = 1.0 / fps
    rng = np.random.default_rng([int(seed), 1])
    phase = np.arange(tenants)[:, None] * period / tenants
    return (phase + np.arange(frames)[None, :] * period
            + rng.uniform(0.0, jitter * period, (tenants, frames)))


class Update(NamedTuple):
    """One ``TenantUpdate`` as the check reads it, its snapshots in
    arrays. A run keeps thousands of updates; kept as Python objects,
    they would grow the heap that every full garbage collection of the
    program scans, where a deployment hands its updates on."""

    frame: int
    seq: int
    kind: str
    tier: int
    shard: str
    ids: np.ndarray       # (k,) namespaced track ids of confirmed tracks
    hits: np.ndarray      # (k,)
    age: np.ndarray       # (k,)
    states: np.ndarray    # (k, n) combined states
    modes: np.ndarray | None   # (k, K) mode probabilities (IMM)


def keep(u) -> Update:
    s = u.snapshots

    def rows(xs):
        return np.array(xs, np.float64) if s else np.zeros((0, 0))

    return Update(
        u.frame, u.seq, u.kind, int(u.tier), u.shard,
        np.array([x.track_id for x in s], np.int64),
        np.array([x.hits for x in s], np.int64),
        np.array([x.age for x in s], np.int64),
        rows([x.state for x in s]),
        rows([x.mode_probs for x in s])
        if s and s[0].mode_probs is not None else None)


@dataclass
class Records:
    """What the window produced, kept for the metrics and the check."""

    due: np.ndarray = None        # (tenants, frames) absolute
    submit: np.ndarray = None     # (tenants, frames) absolute, nan if never
    done: np.ndarray = None       # (tenants, frames) absolute, nan if never
    kind: np.ndarray = None       # (tenants, frames) object: update kind
    updates: list = field(default_factory=list)   # per pump: {name: Update}
    assoc: list = field(default_factory=list)     # (pump no, device assoc)
    pump_t: list = field(default_factory=list)    # (start, end) per pump
    phases: list = field(default_factory=list)    # (span, start, end)
    gc: list = field(default_factory=list)        # (start, end, generation)
    sleeps: list = field(default_factory=list)    # (start, meant end, end)
    window: tuple = (0.0, 0.0)
    horizon: float = 0.0          # when the run stops listening
    period: float = 0.0
    compiles: int = 0
    traces: int = 0


class CompileCounter:
    """Counts backend compiles and jaxpr traces from JAX's monitoring
    events while ``on`` is set."""

    def __init__(self):
        import jax

        self.on, self.compiles, self.traces = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **_) -> None:
        if not self.on:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


def instrument(front, rec: Records, wrap_step=None):
    """Put the benchmark's spans around the front end's calls into the
    tracker step, the lane select, the snapshot copies and the checkpoint
    writes, note each call's host interval, and keep each dispatch's
    association for the check. ``wrap_step`` lets a test break the step
    underneath. Returns the function that takes the lane select's span
    off the program's module again."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.serving import stream

    def spanned(name, fn):
        fn = getattr(fn, "__wrapped__", fn)  # never a span in a span

        def run(*a, **k):
            t0 = time.monotonic()
            with TraceAnnotation(SPAN_PREFIX + name):
                out = fn(*a, **k)
            rec.phases.append((name, t0, time.monotonic()))
            return out
        run.__wrapped__ = fn
        return run

    step_for = front._step_for

    def traced_step_for(tier):
        step = step_for(tier)
        if wrap_step is not None:
            step = wrap_step(step)

        def run(banks, z, valid):
            res = step(banks, z, valid)
            jax.block_until_ready(res.bank.x)
            rec.assoc.append((len(rec.pump_t), res.assoc))
            return res

        return spanned("dispatch", run)

    front._step_for = traced_step_for
    front._lane_snapshots = spanned("snapshot", front._lane_snapshots)
    front._checkpoint = spanned("checkpoint", front._checkpoint)
    select = getattr(stream._select_lanes, "__wrapped__",
                     stream._select_lanes)
    stream._select_lanes = spanned("select", select)

    def restore():
        stream._select_lanes = select

    return restore


class GcPauses:
    """Host intervals of Python's garbage collections while installed."""

    def __init__(self):
        self.pauses, self._t = [], None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic(),
                                info["generation"]))
            self._t = None


def tracker_config(cfg: dict, control: bool = False):
    from repro.core.tracker import TrackerConfig

    t = cfg["tracker"]
    # the control: the program's own lower-precision path (the fused
    # frame kernel is float32-only, so the einsum route serves it)
    return TrackerConfig(capacity=t["capacity"], max_meas=t["max_meas"],
                         max_misses=t["max_misses"], min_hits=t["min_hits"],
                         dtype="bfloat16" if control else t["dtype"],
                         fused_frame=not control)


def run(cfg: dict, traffic: dict, seed: int, seconds: float, tracer,
        devices, wrap_step=None, control: bool = False):
    """Set up, warm, pre-roll and measure one live window. ``tracer``
    (a ``harness.Profile``) starts before the stream, opens its window
    span when the window opens and closes once the run stops listening
    (stopping a trace stalls the host for seconds). Returns (Records,
    inputs, front-end facts)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from repro.serving.stream import (ServiceTier, StreamConfig,
                                      StreamFrontEnd, _select_lanes)

    fps, tenants = cfg["fps"], traffic["tenants"]
    period = 1.0 / fps
    shards = cfg["layout"]["shards"]
    M = cfg["tracker"]["max_meas"]
    preroll = traffic["preroll_s"]
    wait = traffic["deadline_periods"] * period
    frames = int(np.ceil((preroll + seconds) * fps)) + 2 * int(fps) + 2
    dets, counts = simulate(SceneSpec.from_config(cfg), tenants, frames,
                            seed, M)
    due_rel = schedule(fps, tenants, frames, traffic["jitter"], seed)
    model = program_model(cfg)
    st = cfg["stream"]
    scfg = StreamConfig(n_shards=shards,
                        lanes_per_shard=-(-tenants // shards),
                        queue_depth=st["queue_depth"],
                        checkpoint_every=st["checkpoint_every"],
                        degrade_at=st["degrade_at"], coast_at=st["coast_at"],
                        reject_at=st["reject_at"],
                        wide_gate_scale=st["wide_gate_scale"],
                        drop_oldest=st["drop_oldest"],
                        starve_limit=st["starve_limit"],
                        heartbeat_timeout_s=st["heartbeat_timeout_s"])
    rec = Records()
    counter = CompileCounter()
    names = [f"cam{i:03d}" for i in range(tenants)]
    with tempfile.TemporaryDirectory(prefix="katana_ckpt_") as ckpt:
        front = StreamFrontEnd(model, scfg, tracker_config(cfg, control),
                               ckpt_dir=ckpt, devices=devices[:shards])
        restore = instrument(front, rec, wrap_step)
        for name in names:
            front.attach(name)
        # warm both ladder tiers' steps and the lane select on every
        # shard; results are dropped, the banks stay empty
        for sh in front.shards:
            L = scfg.lanes_per_shard
            z0 = jnp.zeros((L, M, model.m), jnp.float32)
            v0 = jnp.zeros((L, M), bool)
            for tier in (ServiceTier.FULL, ServiceTier.WIDE_GATE):
                res = front._step_for(tier)(sh.banks, z0, v0)
                jax.block_until_ready(_select_lanes(
                    np.ones(L, bool), res.bank, sh.banks, front._axes))
        rec.assoc.clear()
        rec.phases.clear()
        pauses = GcPauses()
        tracer.start()
        t0 = time.monotonic() + 0.01
        rec.due = t0 + due_rel
        rec.submit = np.full(due_rel.shape, np.nan)
        rec.done = np.full(due_rel.shape, np.nan)
        rec.kind = np.full(due_rel.shape, "", object)
        setup_end = t0
        w0 = t0 + preroll
        w1 = w0 + seconds
        rec.window = (w0, w1)
        # every frame due in the window is served, or expires, before
        # the run stops listening
        rec.horizon = w1 + wait
        rec.period = period
        order = np.argsort(rec.due, axis=None, kind="stable")
        flat_due = rec.due.reshape(-1)[order]
        nxt = 0
        opened = False
        while True:
            now = time.monotonic()
            if not opened and now >= w0:
                tracer.open()
                opened = counter.on = True
                gc.callbacks.append(pauses)
            if now >= rec.horizon:
                break
            with TraceAnnotation(SPAN_PREFIX + "generator"):
                while nxt < len(order) and flat_due[nxt] <= now:
                    i, f = divmod(int(order[nxt]), frames)
                    front.submit(names[i], dets[i, f, :counts[i, f]], seq=f,
                                 deadline=rec.due[i, f] + wait)
                    rec.submit[i, f] = time.monotonic()
                    nxt += 1
            if front.pending():
                p0 = time.monotonic()
                with TraceAnnotation(SPAN_PREFIX + "pump"):
                    ups = front.pump()
                p1 = time.monotonic()
                rec.pump_t.append((p0, p1))
                rec.updates.append({n: keep(u) for n, u in ups.items()})
                for name, u in ups.items():
                    i = int(name[3:])
                    rec.done[i, u.seq] = p1
                    rec.kind[i, u.seq] = u.kind
            elif nxt < len(order):
                s0 = time.monotonic()
                until = min(flat_due[nxt], rec.horizon)
                time.sleep(max(0.0, until - s0))
                rec.sleeps.append((s0, until, time.monotonic()))
            else:
                break
        counter.on = False
        if pauses in gc.callbacks:
            gc.callbacks.remove(pauses)
        restore()
        tracer.close()
        rec.gc = pauses.pauses
        rec.compiles, rec.traces = counter.compiles, counter.traces
        facts = dict(stats=dict(front.stats.__dict__),
                     memory_peak_bytes=max(
                         (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devices[:shards]),
                     lanes=scfg.lanes_per_shard, shards=shards,
                     ns_base={n: front.tenants[n].ns_base for n in names},
                     lane={n: front.tenants[n].lane for n in names},
                     setup_end=setup_end,
                     gate_scale_wide=st["wide_gate_scale"])
        rec.assoc = [(p, np.asarray(a)) for p, a in rec.assoc]
        del front
        gc.collect()
    return rec, (dets, counts), facts


# ------------------------------------------------------------- the metrics

def end_to_end(rec: Records, fps: float) -> dict:
    """Latency percentiles and the on-time share over every frame due in
    the window. A frame never answered counts as answered when the run
    stopped listening (the horizon), above every served frame."""
    period = 1.0 / fps
    w0, w1 = rec.window
    due = (rec.due >= w0) & (rec.due < w1)
    lat = np.where(np.isnan(rec.done), rec.horizon, rec.done) - rec.due
    served = rec.kind == "served"
    lat = np.where(served, lat, np.maximum(lat, rec.horizon - rec.due))
    lat_ms = np.sort(lat[due]) * 1e3
    on_time = served & (rec.done - rec.due <= period)
    sent = due & ~np.isnan(rec.submit)
    return dict(frames_due=int(due.sum()),
                frames_served=int((served & due).sum()),
                frame_p50_ms=float(np.percentile(lat_ms, 50)),
                frame_p95_ms=float(np.percentile(lat_ms, 95)),
                on_time_pct=float(100.0 * on_time[due].mean()),
                gen_lag_ms=(rec.submit[sent] - rec.due[sent]) * 1e3)


def _ms_in(a: float, b: float, intervals) -> float:
    """Milliseconds of the (start, end) intervals that fall in [a, b]."""
    return 1e3 * sum(max(0.0, min(b, y) - max(a, x)) for x, y in intervals)


def host_report(rec: Records) -> list:
    """Lines for standard error on where the window's host time went:
    Python's garbage collections, the slowest pumps split by the
    benchmark's spans, the generator's longest lag, and how many pumps
    served more than one tenant."""
    w0, w1 = rec.window

    gcs = [(a, b) for a, b, _ in rec.gc if w0 <= a < w1]
    gen2 = [(a, b) for a, b, g in rec.gc if w0 <= a < w1 and g == 2]
    pumps = [(a, b, len(u)) for (a, b), u in zip(rec.pump_t, rec.updates)
             if w0 <= a < w1]
    lines = [f"gc_in_window: collections={len(gcs)} "
             f"total_ms={_ms_in(w0, w1, gcs):.1f} "
             f"max_ms={max((1e3 * (b - a) for a, b in gcs), default=0):.1f} "
             f"gen2={len(gen2)} gen2_ms={_ms_in(w0, w1, gen2):.1f}",
             f"pumps_in_window={len(pumps)} "
             f"serving_several={sum(1 for *_, n in pumps if n > 1)}"]
    spans = {name: [(x, y) for n, x, y in rec.phases if n == name]
             for name in ("dispatch", "select", "snapshot", "checkpoint")}
    for a, b, _ in sorted(pumps, key=lambda p: p[0] - p[1])[:3]:
        parts = " ".join(f"{name}_ms={_ms_in(a, b, ivs):.1f}"
                         for name, ivs in spans.items())
        lines.append(f"slow_pump: at_s={a - w0:.3f} ms={1e3 * (b - a):.1f} "
                     f"{parts} gc_ms={_ms_in(a, b, gcs):.1f}")
    due = (rec.due >= w0) & (rec.due < w1) & ~np.isnan(rec.submit)
    if due.any():
        lag = np.where(due, rec.submit - rec.due, -np.inf)
        i = np.unravel_index(np.argmax(lag), lag.shape)
        d = rec.due[i]
        lines.append(f"max_generator_lag_ms={1e3 * lag[i]:.1f} "
                     f"at_s={d - w0:.3f} in_pumps_ms="
                     f"{_ms_in(d, rec.submit[i], [p[:2] for p in pumps]):.1f}")
    # a sleep that ends late: the host held the loop back outside any
    # pump or collection
    over = [(c - b, a) for a, b, c in rec.sleeps if w0 <= a < w1]
    worst = max(over, default=(0.0, w0))
    lines.append(f"sleep_overshoot: over_5ms={sum(o > 5e-3 for o, _ in over)} "
                 f"max_ms={1e3 * worst[0]:.1f} at_s={worst[1] - w0:.3f}")
    lines += _late_bursts(rec, pumps, spans, gcs)
    return lines


def _late_bursts(rec: Records, pumps, spans, gcs) -> list:
    """The three largest bursts of frames due in the window that were not
    served within a period, each with what the host did around it: its
    pumps by span, collections and overshooting sleeps."""
    w0, w1 = rec.window
    period = rec.period
    due = (rec.due >= w0) & (rec.due < w1)
    late = due & ~((rec.kind == "served") & (rec.done - rec.due <= period))
    times = np.sort(rec.due[late])
    if not len(times):
        return ["late_bursts: none"]
    cuts = np.nonzero(np.diff(times) > 0.2)[0] + 1
    bursts = sorted(np.split(times, cuts), key=len, reverse=True)

    lines = [f"late_bursts: count={len(bursts)} frames={len(times)}"]
    for b in bursts[:3]:
        a, z = b[0] - 0.1, b[-1] + period
        near = [(x, y) for x, y, _ in pumps if y > a and x < z]
        slow = max(near, key=lambda p: p[1] - p[0], default=(a, a))
        split = " ".join(f"{n}_ms={_ms_in(*slow, ivs):.1f}"
                         for n, ivs in spans.items())
        over = max((c - m for s, m, c in rec.sleeps if a <= s < z),
                   default=0.0)
        lines.append(
            f"late_burst: at_s={b[0] - w0:.3f} frames={len(b)} "
            f"span_ms={1e3 * (z - a):.1f} pumps={len(near)} "
            f"pump_ms={_ms_in(a, z, near):.1f} "
            f"slowest_pump_ms={1e3 * (slow[1] - slow[0]):.1f} ({split}) "
            f"gc_ms={_ms_in(a, z, gcs):.1f} "
            f"max_sleep_overshoot_ms={1e3 * over:.1f}")
    return lines


# --------------------------------------------------------------- the check

def check(cfg: dict, traffic: dict, rec: Records, inputs, facts) -> dict:
    """Follow every applied frame of every tenant with the float64
    reference, teacher-forced by the program's association, and return
    the compared numbers: the widest assoc_gap, the widest state and
    mode-probability deviations of confirmed tracks, and the count of
    lifecycle mismatches (wrong ids, counters, confirmed set, frame
    order, or a dispatch the updates do not account for)."""
    dets, counts = inputs
    t = cfg["tracker"]
    tenants = traffic["tenants"]
    M, C = t["max_meas"], t["capacity"]
    model = reference.model_from_config(cfg)
    ref = reference.Tracker(model, C, M, tenants,
                            reference.CHI2_99[model.m], t["max_misses"],
                            t["min_hits"])
    mismatches = 0
    per_tenant = [[] for _ in range(tenants)]
    by_pump = {}
    for p, a in rec.assoc:
        by_pump.setdefault(p, []).append(a)
    for p, ups in enumerate(rec.updates):
        shards = sorted({u.shard for u in ups.values()},
                        key=lambda s: int(s[5:]))
        got = by_pump.get(p, [])
        if len(got) != len(shards):
            mismatches += 1
            continue
        rows = dict(zip(shards, got))
        for name, u in ups.items():
            per_tenant[int(name[3:])].append(
                (u, rows[u.shard], facts["lane"][name]))
    n_frames = max(len(s) for s in per_tenant)
    gap_max = state_dev = mode_dev = 0.0
    wide = facts["gate_scale_wide"]
    for k in range(n_frames):
        act = [i for i in range(tenants) if len(per_tenant[i]) > k]
        b = len(act)
        z = np.zeros((b, M, 3))
        zv = np.zeros((b, M), bool)
        assoc = np.zeros((b, C), np.int64)
        scale = np.ones(b)
        for j, i in enumerate(act):
            u, a, lane = per_tenant[i][k]
            prev = per_tenant[i][k - 1][0].seq if k else -1
            if u.frame != k or u.seq <= prev:
                mismatches += 1
            if u.kind == "served":
                z[j] = dets[i, u.seq]
                zv[j] = np.arange(M) < counts[i, u.seq]
            assoc[j] = a[lane]
            scale[j] = wide if int(u.tier) == 1 else 1.0
        gap = ref.step(np.asarray(act), z, zv, assoc, scale)
        gap_max = max(gap_max, float(gap.max()))
        for j, i in enumerate(act):
            u = per_tenant[i][k][0]
            _, ids, x, hits, age, mu = ref.confirmed(i)
            ns = facts["ns_base"][f"cam{i:03d}"]
            if not (np.array_equal(u.ids, ns + ids)
                    and np.array_equal(u.hits, hits)
                    and np.array_equal(u.age, age)):
                mismatches += 1
                continue
            if len(ids):
                state_dev = max(state_dev, float(np.abs(u.states - x).max()))
                if u.modes is not None:
                    mode_dev = max(mode_dev, float(np.abs(u.modes - mu).max()))
    out = dict(assoc_gap=gap_max, state_dev=state_dev,
               lifecycle_mismatches=mismatches,
               frames_checked=sum(len(s) for s in per_tenant))
    if model.K > 1:
        out["mode_dev"] = mode_dev
    return out
