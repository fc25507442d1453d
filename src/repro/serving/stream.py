"""Fault-tolerant multi-tenant streaming front end over the fused
tracking engines.

KATANA's premise is a closed control loop: every measurement must be
fused before the next control cycle. At fleet scale that means the
*serving* layer — not the filter math — decides whether the loop
closes: many independent tenants (scenes/sensors) submit frames
asynchronously at different rates, shards die, sensors go dark, and
payloads arrive corrupt, late or duplicated. This module keeps the
loop closed under all of it:

* **Dynamic batch forming** — a ``SlotAllocator`` packs tenants onto
  the padded track/sensor lanes of the vmapped
  ``katana_frame``/``katana_imm_frame`` step (the same per-sensor step
  ``ShardedBankEngine`` serves): each tenant owns one lane of a
  shard's stacked bank, so ONE fused dispatch per shard serves every
  tenant that has a frame pending, and slots on the C axis can never
  be shared between tenants (lanes are disjoint by construction).
  Track ids live in per-tenant namespaces (``ns_base + local id``).
  Lanes whose tenant has nothing pending are *frozen* (their bank
  state is not advanced): a tenant's stream is frame-indexed, so an
  idle pump must not age its tracks.
* **Admission control + backpressure** — bounded per-tenant queues
  with explicit decisions (``Admission``): accept, duplicate-drop,
  deadline-expired shed, drop-oldest replacement, queue-full reject,
  overload reject. Overload never collapses the queues; it walks the
  **degradation ladder** (``ServiceTier``): FULL -> WIDE_GATE (the
  tracker's ``gate_scale`` knob) -> COAST_ONLY (frames served through
  the existing ``valid`` mask with the measurements shed) -> REJECT
  (admission closed). The ladder is monotone in load by construction.
  A ``CircuitBreaker`` guards the dispatch path: repeated failures
  open it (forced REJECT tier) and a half-open probe re-closes it.
* **Checkpointed failover** — every tenant lane is periodically
  snapshotted (``checkpoint.ckpt``: atomic, keep-N, validated
  restore) together with a write-ahead log of the frames applied
  since. When a shard dies (heartbeat timeout via
  ``runtime.ft.HeartbeatMonitor``, or repeated dispatch failures),
  its tenants are restored onto surviving shards: checkpoint restore
  seeds the lane's mode-conditioned (x, P, mu) bitwise, the WAL
  replays through the surviving shard's own fused step, and the
  resumed FrameResult stream is **bitwise-identical** to an
  uninterrupted run (``tests/test_chaos.py`` proves it) with track
  ids preserved.
* **Degraded-input robustness** — NaN/inf payloads coast through the
  tracker's ``nan_guard`` instead of poisoning the bank; a dark
  sensor submits empty frames (tracks coast, then prune); duplicates
  and stale frames are dropped at admission by sequence number.
* **Tracing** — the stages of a pump are ``jax.profiler``
  ``TraceAnnotation`` spans (``katana.pump`` around ``katana.form``,
  ``katana.dispatch``, ``katana.select``, ``katana.snapshot`` and
  ``katana.checkpoint``), on the profiler's clock beside the device's
  ops, and next to free while no profiler runs.

``serving/faults.py`` injects all of these faults deterministically;
``tests/test_chaos.py`` is the proof suite.
"""
from __future__ import annotations

import tempfile
import time
import warnings
from collections import deque
from dataclasses import dataclass, field, replace
from enum import Enum, IntEnum
from functools import wraps
from typing import (Callable, Deque, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.ckpt import CheckpointManager
from repro.core import bank as bank_lib
from repro.core.filters import IMMModel
from repro.core.tracker import (FrameResult, TrackerConfig,
                                make_multi_sensor_step)
from repro.runtime.ft import HeartbeatMonitor
from repro.serving.engine import TrackSnapshot

# Per-tenant track-id namespace stride: global id = ns_base + local id.
# 2^20 local ids per tenant epoch is far beyond any bank capacity.
NS_STRIDE = 1 << 20

# Profiler spans of a pump, nested in PUMP_SPAN. FORM_SPAN: deadline
# expiry, the plan, the batch and its host-to-device copies;
# DISPATCH_SPAN: one shard's tracker step through block_until_ready;
# SELECT_SPAN: the lane select; SNAPSHOT_SPAN: one update's
# device-to-host copies; CHECKPOINT_SPAN: one lane's blocking save.
PUMP_SPAN = "katana.pump"
FORM_SPAN = "katana.form"
DISPATCH_SPAN = "katana.dispatch"
SELECT_SPAN = "katana.select"
SNAPSHOT_SPAN = "katana.snapshot"
CHECKPOINT_SPAN = "katana.checkpoint"


class ServiceTier(IntEnum):
    """The degradation ladder, ordered: a HIGHER tier is strictly less
    service. More load can only move the tier up (monotone — the
    property tests pin this)."""

    FULL = 0        # measurements served, nominal gate
    WIDE_GATE = 1   # measurements served, gate widened (gate_scale)
    COAST_ONLY = 2  # frames consumed but measurements shed: coast via
                    # the valid mask — cadence kept, quality degraded
    REJECT = 3      # admission closed; queued frames coast-drain


class Admission(Enum):
    """Explicit per-submit decision — backpressure is a return value,
    never an exception and never a silent drop."""

    ACCEPTED = "accepted"
    REPLACED_OLDEST = "replaced-oldest"     # accepted; oldest was shed
    REJECTED_QUEUE_FULL = "rejected-queue-full"
    REJECTED_OVERLOAD = "rejected-overload"  # ladder/breaker at REJECT
    REJECTED_NO_CAPACITY = "rejected-no-capacity"  # no free lane
    DUPLICATE = "duplicate"                 # seq already consumed


@dataclass(frozen=True)
class StreamConfig:
    n_shards: int = 2
    lanes_per_shard: int = 4      # tenant lanes per shard
    queue_depth: int = 4          # bounded per-tenant queue
    checkpoint_every: int = 8     # tenant frames between snapshots
    # degradation-ladder thresholds on the load factor (queued frames /
    # total queue capacity, in [0, 1]); must be sorted ascending
    degrade_at: float = 0.375
    coast_at: float = 0.625
    reject_at: float = 0.875
    wide_gate_scale: float = 2.5  # gate multiplier at WIDE_GATE
    drop_oldest: bool = True      # queue-full: shed oldest, accept new
    # anti-starvation floor: after this many CONSECUTIVE ladder-shed
    # frames a tenant's next frame is served regardless of tier, so a
    # sustained overload degrades everyone instead of starving anyone
    starve_limit: int = 4
    heartbeat_timeout_s: float = 1.0
    breaker_failures: int = 3     # consecutive failures to open
    breaker_cooldown_s: float = 5.0

    def __post_init__(self):
        if not (0.0 < self.degrade_at <= self.coast_at <= self.reject_at):
            raise ValueError("ladder thresholds must be sorted: "
                             f"{self.degrade_at}, {self.coast_at}, "
                             f"{self.reject_at}")


@dataclass(frozen=True)
class DegradationLadder:
    """load in [0, inf) -> ServiceTier; monotone non-decreasing."""

    degrade_at: float
    coast_at: float
    reject_at: float

    def tier_for(self, load: float) -> ServiceTier:
        if load >= self.reject_at:
            return ServiceTier.REJECT
        if load >= self.coast_at:
            return ServiceTier.COAST_ONLY
        if load >= self.degrade_at:
            return ServiceTier.WIDE_GATE
        return ServiceTier.FULL


class CircuitBreaker:
    """Classic three-state breaker around the dispatch path.

    CLOSED: traffic flows, consecutive failures count up. At
    ``failure_threshold`` the breaker OPENs: ``allow()`` is False until
    ``cooldown_s`` elapses, after which it is HALF_OPEN — one probe is
    allowed; its success re-CLOSEs, its failure re-OPENs (fresh
    cooldown). The clock is injectable so chaos tests drive it
    deterministically."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"

    def __init__(self, failure_threshold: int = 3, cooldown_s: float = 5.0,
                 clock: Callable[[], float] = time.monotonic):
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self.clock = clock
        self.failures = 0
        self.trips = 0
        self._opened_at: Optional[float] = None

    @property
    def state(self) -> str:
        if self._opened_at is None:
            return self.CLOSED
        if self.clock() - self._opened_at >= self.cooldown_s:
            return self.HALF_OPEN
        return self.OPEN

    def allow(self) -> bool:
        return self.state != self.OPEN

    def record_success(self) -> None:
        self.failures = 0
        self._opened_at = None

    def record_failure(self) -> None:
        self.failures += 1
        if self.state == self.HALF_OPEN or \
                self.failures >= self.failure_threshold:
            self._opened_at = self.clock()  # (re)open, fresh cooldown
            self.trips += 1


class SlotAllocator:
    """Maps tenants onto (shard, lane) slots of the serving fleet.

    Invariants (property-tested): no two tenants ever hold the same
    (shard, lane); the tenant count never exceeds the live lane pool;
    released lanes are reusable; lanes of a dropped (dead) shard are
    never handed out again. Also owns the per-tenant track-id
    namespace counter — a namespace is never reissued, so ids from an
    evicted tenant can never collide with a later one's."""

    def __init__(self, n_shards: int, lanes_per_shard: int):
        self.lanes_per_shard = lanes_per_shard
        # pop() hands out the lowest free lane — deterministic packing
        self.free: Dict[int, List[int]] = {
            s: list(range(lanes_per_shard - 1, -1, -1))
            for s in range(n_shards)}
        self.where: Dict[str, Tuple[int, int]] = {}
        self._next_ns = 0

    def capacity(self) -> int:
        return len(self.where) + sum(len(f) for f in self.free.values())

    def acquire(self, tenant: str,
                prefer: Optional[int] = None) -> Optional[Tuple[int, int]]:
        """Claim a lane for ``tenant`` (must not hold one). Picks the
        shard with the most free lanes (balance), lowest index on
        ties; ``prefer`` pins a shard when it has room. None = full."""
        if tenant in self.where:
            raise ValueError(f"tenant {tenant!r} already holds "
                             f"{self.where[tenant]}")
        if prefer is not None and self.free.get(prefer):
            s = prefer
        else:
            with_room = [(len(f), -s) for s, f in self.free.items() if f]
            if not with_room:
                return None
            s = -max(with_room)[1]
        lane = self.free[s].pop()
        self.where[tenant] = (s, lane)
        return s, lane

    def release(self, tenant: str) -> Tuple[int, int]:
        s, lane = self.where.pop(tenant)
        if s in self.free:  # dead shards are out of the pool
            self.free[s].append(lane)
            self.free[s].sort(reverse=True)
        return s, lane

    def drop_shard(self, shard: int) -> None:
        """A dead shard's lanes leave the pool forever (its tenants
        must be released/re-acquired by the failover path first)."""
        self.free.pop(shard, None)

    def tenants_on(self, shard: int) -> List[str]:
        return sorted(t for t, (s, _) in self.where.items() if s == shard)

    def next_namespace(self) -> int:
        ns = self._next_ns
        self._next_ns += 1
        return ns * NS_STRIDE


@dataclass
class FrameRequest:
    seq: int
    z: np.ndarray               # (k, m), k may be 0 (dark sensor tick)
    t_submit: float
    deadline: Optional[float]   # absolute, front-end clock domain


@dataclass
class TenantUpdate:
    """One applied frame of one tenant's stream."""

    tenant: str
    frame: int                  # tenant-stream frame index (0-based)
    seq: int
    tier: ServiceTier
    kind: str                   # "served" | "coast" | "shed"
    shard: str
    snapshots: List[TrackSnapshot] = field(default_factory=list)


@dataclass
class StreamStats:
    submitted: int = 0
    accepted: int = 0
    duplicates: int = 0
    replaced_oldest: int = 0
    rejected_queue_full: int = 0
    rejected_overload: int = 0
    rejected_no_capacity: int = 0
    expired: int = 0            # deadline-shed before dispatch
    served: int = 0             # frames applied with measurements
    coasted: int = 0            # empty frames applied (dark sensor)
    shed: int = 0               # frames applied coast-only by the ladder
    dispatches: int = 0         # fused step calls
    lanes_dispatched: int = 0   # lanes that took part, over dispatches
    step_traces: int = 0        # tier steps traced by this front end
    select_traces: int = 0      # lane selects traced by this front end
    dispatch_errors: int = 0
    failovers: int = 0          # tenants migrated off dead shards
    shards_lost: int = 0
    checkpoints: int = 0
    parked: int = 0             # tenants with no surviving lane
    fanout_pumps: int = 0       # pumps that called more than one shard's step
    snapshot_prefetches: int = 0  # step outputs copied before the snapshots

    @property
    def applied(self) -> int:
        return self.served + self.coasted + self.shed


@dataclass
class _Tenant:
    name: str
    shard: int
    lane: int
    ns_base: int
    ckpt: CheckpointManager
    queue: Deque[FrameRequest] = field(default_factory=deque)
    next_seq: int = 0
    frames_applied: int = 0
    ckpt_frame: int = 0         # frames_applied at the last snapshot
    # write-ahead log since the last checkpoint: (tier, z_row, v_row)
    wal: List[Tuple[int, np.ndarray, np.ndarray]] = field(
        default_factory=list)
    sheds_in_row: int = 0       # consecutive ladder-shed frames
    parked: bool = False


@dataclass
class _Shard:
    name: str
    idx: int
    banks: object               # stacked BankState/IMMBankState, or None
    device: Optional[object] = None
    alive: bool = True          # False once failed over
    killed: bool = False        # fault-injection: silent death
    consecutive_failures: int = 0


class _TierStep(NamedTuple):
    one: object          # one empty single-sensor bank
    axes: object         # the sensor-axis pytree
    step: Callable       # jitted (banks, z, valid) -> FrameResult
    model: object        # keeps id(model) in the cache key alive
    traces: List[int]    # [times ``step`` was traced]


# one jitted multi-sensor step per (model, cfg, lane count) — shared by
# every shard and every front end so chaos tests don't recompile per
# fleet (the step closure keeps ``model`` alive, so id() keys are
# stable)
_STEP_CACHE: Dict[Tuple, _TierStep] = {}


def _multi_step(model, cfg: TrackerConfig, lanes: int) -> _TierStep:
    key = (id(model), cfg, lanes)
    if key not in _STEP_CACHE:
        one, axes, step = make_multi_sensor_step(model, cfg)
        traces = [0]

        @wraps(step)
        def counted(*args):
            traces[0] += 1  # runs while jit traces, never per call
            return step(*args)

        _STEP_CACHE[key] = _TierStep(one, axes, jax.jit(counted), model,
                                     traces)
    return _STEP_CACHE[key]


class _LaneSelect(NamedTuple):
    select: Callable     # jitted (mask, new, old) -> merged stacked bank
    traces: List[int]    # [times ``select`` was traced]


# one jitted lane select per sensor-axis pytree (BankState and
# IMMBankState stacks differ), shared by every front end; the mask is
# a traced argument, so every lane pattern runs the same program
_SELECT_CACHE: Dict[Tuple, _LaneSelect] = {}


def _lane_select(axes) -> _LaneSelect:
    key = (type(axes), axes)
    if key not in _SELECT_CACHE:
        traces = [0]

        def select(mask, new, old):
            traces[0] += 1  # runs while jit traces, never per call

            def sel(n, o, a):
                shape = (1,) * a + (mask.shape[0],) + (1,) * (n.ndim - a - 1)
                return jnp.where(mask.reshape(shape), n, o)

            return jax.tree.map(sel, new, old, axes)

        _SELECT_CACHE[key] = _LaneSelect(jax.jit(select), traces)
    return _SELECT_CACHE[key]


def _snapshot_fields(res: FrameResult, is_imm: bool) -> tuple:
    """The step outputs a lane's snapshots read, in order: confirmed,
    track ids, hits, ages, states, and (IMM) mode probabilities."""
    bank = res.bank
    if is_imm:
        return (res.confirmed, bank.track_id, bank.hits, bank.age,
                res.x_est, res.mode_probs)
    return res.confirmed, bank.track_id, bank.hits, bank.age, bank.x


def _select_lanes(mask: np.ndarray, new, old, axes):
    """Per-lane select over a stacked bank: lane i takes ``new`` where
    mask[i], else keeps ``old`` — how idle tenants' lanes are frozen
    while the dispatch still runs as one fused call. One compiled
    program per ``axes`` pytree does the whole merge."""
    with TraceAnnotation(SELECT_SPAN):
        return _lane_select(axes).select(mask, new, old)


class StreamFrontEnd:
    """The multi-tenant streaming facade over the fused frame step.

    ``attach`` a tenant, ``submit`` its frames (any rate, any order —
    admission answers with an explicit decision), ``pump`` once per
    serving cycle: one fused vmapped dispatch per live shard serves
    every tenant with a frame pending and returns the per-tenant
    ``TenantUpdate``s. ``kill_shard`` is the fault-injection surface;
    recovery (checkpoint restore + WAL replay onto a surviving shard)
    happens inside ``pump`` once the heartbeat monitor declares the
    shard dead.

    The ``clock`` is injectable (deadlines, heartbeats and the circuit
    breaker all read it) so every failure path is deterministic under
    test.
    """

    def __init__(self, model, cfg: Optional[StreamConfig] = None,
                 tracker: Optional[TrackerConfig] = None,
                 ckpt_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic,
                 devices: Optional[Sequence] = None):
        self.model = model
        self.cfg = cfg or StreamConfig()
        self.tracker = tracker or TrackerConfig(capacity=64, max_meas=32)
        self.is_imm = isinstance(model, IMMModel)
        self.clock = clock
        self.ckpt_root = ckpt_dir or tempfile.mkdtemp(
            prefix="katana_stream_ckpt_")
        self.ladder = DegradationLadder(self.cfg.degrade_at,
                                        self.cfg.coast_at,
                                        self.cfg.reject_at)
        self.breaker = CircuitBreaker(self.cfg.breaker_failures,
                                      self.cfg.breaker_cooldown_s, clock)
        self.alloc = SlotAllocator(self.cfg.n_shards,
                                   self.cfg.lanes_per_shard)
        self.stats = StreamStats()
        self.tenants: Dict[str, _Tenant] = {}
        self._tier_cfg = {
            ServiceTier.FULL: self.tracker,
            ServiceTier.WIDE_GATE: replace(
                self.tracker,
                gate_scale=self.tracker.gate_scale
                * self.cfg.wide_gate_scale),
        }
        L = self.cfg.lanes_per_shard
        one, axes, *_ = _multi_step(model, self.tracker, L)
        self._one, self._axes = one, axes
        devs = list(devices) if devices is not None else jax.devices()
        self.shards: List[_Shard] = []
        for s in range(self.cfg.n_shards):
            banks = bank_lib.stack_sensor_banks(one, L)
            dev = devs[s % len(devs)] if devs else None
            if dev is not None:
                banks = jax.device_put(banks, dev)
            self.shards.append(_Shard(f"shard{s}", s, banks, device=dev))
        self.monitor = HeartbeatMonitor([sh.name for sh in self.shards],
                                        self.cfg.heartbeat_timeout_s,
                                        clock)

    # ------------------------------------------------------------ admission
    def attach(self, tenant: str) -> Admission:
        """Admit a tenant: claim a lane, reset it to an empty bank, and
        write its frame-0 checkpoint (failover must always have a
        snapshot to restore from)."""
        if tenant in self.tenants:
            raise ValueError(f"tenant {tenant!r} already attached")
        alive = {sh.idx for sh in self.shards if sh.alive}
        while True:
            loc = self.alloc.acquire(tenant)
            if loc is None or loc[0] in alive:
                break
            # allocator still had room only on a dead shard
            self.alloc.release(tenant)
            self.alloc.drop_shard(loc[0])
        if loc is None:
            self.stats.rejected_no_capacity += 1
            return Admission.REJECTED_NO_CAPACITY
        s, lane = loc
        shard = self.shards[s]
        shard.banks = bank_lib.place_sensor_bank(shard.banks, lane,
                                                 self._one)
        t = _Tenant(tenant, s, lane, self.alloc.next_namespace(),
                    CheckpointManager(f"{self.ckpt_root}/{tenant}",
                                      keep_n=2))
        self.tenants[tenant] = t
        self._checkpoint(t)
        return Admission.ACCEPTED

    def detach(self, tenant: str) -> None:
        t = self.tenants.pop(tenant)
        if not t.parked:
            self.alloc.release(tenant)

    def submit(self, tenant: str, z, seq: Optional[int] = None,
               deadline: Optional[float] = None) -> Admission:
        """Queue one frame for ``tenant``. z: (k, m) measurements (k=0
        = dark-sensor tick: the frame coasts). ``seq`` defaults to the
        next expected; anything already consumed is a DUPLICATE (late
        and re-sent frames alike). ``deadline`` is absolute on the
        front-end clock; expired frames are shed before dispatch."""
        t = self.tenants[tenant]
        self.stats.submitted += 1
        z = np.asarray(z, np.float32).reshape(-1, self.model.m)
        seq = t.next_seq if seq is None else int(seq)
        if seq < t.next_seq:
            self.stats.duplicates += 1
            return Admission.DUPLICATE
        if self.effective_tier() >= ServiceTier.REJECT:
            self.stats.rejected_overload += 1
            return Admission.REJECTED_OVERLOAD
        req = FrameRequest(seq, z, self.clock(), deadline)
        decision = Admission.ACCEPTED
        if len(t.queue) >= self.cfg.queue_depth:
            if not self.cfg.drop_oldest:
                self.stats.rejected_queue_full += 1
                return Admission.REJECTED_QUEUE_FULL
            t.queue.popleft()  # stalest frame is the cheapest to lose
            self.stats.replaced_oldest += 1
            decision = Admission.REPLACED_OLDEST
        t.queue.append(req)
        t.next_seq = seq + 1
        self.stats.accepted += 1
        return decision

    # ------------------------------------------------------------- telemetry
    def pending(self) -> int:
        return sum(len(t.queue) for t in self.tenants.values())

    def load(self) -> float:
        cap = max(1, len(self.tenants)) * self.cfg.queue_depth
        return self.pending() / cap

    def effective_tier(self) -> ServiceTier:
        """Ladder tier from the current load, forced to REJECT while
        the circuit breaker is open."""
        tier = self.ladder.tier_for(self.load())
        if not self.breaker.allow():
            return ServiceTier.REJECT
        return tier

    def shards_alive(self) -> List[str]:
        return [sh.name for sh in self.shards if sh.alive]

    # ------------------------------------------------------------ fault hook
    def kill_shard(self, shard) -> None:
        """Fault injection: the shard dies silently — it stops serving
        and stops heartbeating, but the front end only learns of it
        when the heartbeat times out (or dispatches keep failing)."""
        sh = self._shard(shard)
        sh.killed = True
        sh.banks = None  # the state is gone with the host

    def _shard(self, shard) -> _Shard:
        if isinstance(shard, _Shard):
            return shard
        for sh in self.shards:
            if sh.idx == shard or sh.name == shard:
                return sh
        raise KeyError(shard)

    # ---------------------------------------------------------------- pump
    def pump(self) -> Dict[str, TenantUpdate]:
        """One serving cycle: detect/recover dead shards, then one
        fused dispatch per live shard over every tenant with a pending
        frame. Returns the applied updates keyed by tenant. Never
        raises on shard failure — errors feed the breaker and the
        failover path."""
        with TraceAnnotation(PUMP_SPAN):
            now = self.clock()
            # a reachable shard beats once per pump; a killed one goes
            # silent and crosses the timeout after enough clock passes
            for sh in self.shards:
                if sh.alive and not sh.killed:
                    self.monitor.beat(sh.name)
            self._recover_dead(now)
            tier = self.effective_tier()
            updates: Dict[str, TenantUpdate] = {}
            launched = sum(self._pump_shard(sh, tier, now, updates)
                           for sh in self.shards if sh.alive)
            if launched > 1:
                self.stats.fanout_pumps += 1
            return updates

    def _pump_shard(self, sh: _Shard, tier: ServiceTier, now: float,
                    updates: Dict[str, TenantUpdate]) -> bool:
        """Serve one shard's pending tenants; True if its tier step was
        called (a failed call included)."""
        L, M, m = (self.cfg.lanes_per_shard, self.tracker.max_meas,
                   self.model.m)
        with TraceAnnotation(FORM_SPAN):
            zb = np.zeros((L, M, m), np.float32)
            vb = np.zeros((L, M), bool)
            participate = np.zeros((L,), bool)
            plan: List[Tuple[_Tenant, FrameRequest, str]] = []
            for name in self.alloc.tenants_on(sh.idx):
                t = self.tenants[name]
                while t.queue and t.queue[0].deadline is not None \
                        and t.queue[0].deadline < now:
                    t.queue.popleft()
                    self.stats.expired += 1
                if not t.queue:
                    continue  # lane frozen this pump
                req = t.queue[0]  # peek — committed only if dispatch lands
                k = min(len(req.z), M)
                starving = t.sheds_in_row >= self.cfg.starve_limit - 1
                if tier >= ServiceTier.COAST_ONLY and k and not starving:
                    kind = "shed"  # ladder sheds the measurements, keeps
                    # the cadence: the lane coasts via the valid mask
                elif k == 0:
                    kind = "coast"
                else:
                    # nominal service — or the anti-starvation floor
                    # firing under a coasting tier
                    kind = "served"
                    zb[t.lane, :k] = req.z[:k]
                    vb[t.lane, :k] = True
                participate[t.lane] = True
                plan.append((t, req, kind))
            if sh.killed or not plan:
                return False  # dead: no result, queues intact; idle: frozen
            # straight from the host to the shard's chip, uncommitted like
            # the warm-up's batch, so the step's compiled signature holds
            with jax.default_device(sh.device):
                z, valid = jnp.asarray(zb), jnp.asarray(vb)
        step_tier = (ServiceTier.WIDE_GATE if tier == ServiceTier.WIDE_GATE
                     else ServiceTier.FULL)
        traces = _multi_step(self.model, self._tier_cfg[step_tier], L).traces
        traced = traces[0]
        with TraceAnnotation(DISPATCH_SPAN):
            try:
                res = self._step_for(step_tier)(sh.banks, z, valid)
                jax.block_until_ready(res.bank.x)
            except Exception:  # noqa: BLE001 — the loop must keep closing
                res = None
        self.stats.step_traces += traces[0] - traced
        if res is None:
            self.stats.dispatch_errors += 1
            self.breaker.record_failure()
            sh.consecutive_failures += 1
            if sh.consecutive_failures >= self.cfg.breaker_failures:
                sh.killed = True  # persistent failure == dead shard
                sh.banks = None
            return True
        sh.consecutive_failures = 0
        self.breaker.record_success()
        self.stats.dispatches += 1
        self.stats.lanes_dispatched += len(plan)
        # start every snapshot copy now, so the round trips overlap each
        # other and the select instead of running one after another
        fields = _snapshot_fields(res, self.is_imm)
        for a in fields:
            a.copy_to_host_async()
        self.stats.snapshot_prefetches += len(fields)
        select_traces = _lane_select(self._axes).traces
        traced = select_traces[0]
        sh.banks = _select_lanes(participate, res.bank, sh.banks,
                                 self._axes)
        self.stats.select_traces += select_traces[0] - traced
        counters = {"served": "served", "coast": "coasted", "shed": "shed"}
        for t, req, kind in plan:
            t.queue.popleft()  # commit
            # the WAL records the step tier that actually dispatched —
            # replay re-runs exactly that step, which is what makes the
            # resumed stream bitwise
            t.wal.append((int(step_tier), zb[t.lane].copy(),
                          vb[t.lane].copy()))
            frame = t.frames_applied
            t.frames_applied += 1
            t.sheds_in_row = t.sheds_in_row + 1 if kind == "shed" else 0
            field_name = counters[kind]
            setattr(self.stats, field_name,
                    getattr(self.stats, field_name) + 1)
            updates[t.name] = TenantUpdate(
                t.name, frame, req.seq, tier, kind, sh.name,
                self._lane_snapshots(res, t.lane, t.ns_base))
            if t.frames_applied - t.ckpt_frame >= self.cfg.checkpoint_every:
                self._checkpoint(t)
        return True

    def _step_for(self, tier: ServiceTier):
        cfg = self._tier_cfg[tier]
        return _multi_step(self.model, cfg, self.cfg.lanes_per_shard).step

    def _lane_snapshots(self, res: FrameResult, lane: int,
                        ns_base: int) -> List[TrackSnapshot]:
        with TraceAnnotation(SNAPSHOT_SPAN):
            fields = _snapshot_fields(res, self.is_imm)
            conf, ids, hits, age, xs, *mus = (np.asarray(a)[lane]
                                              for a in fields)
            return [TrackSnapshot(ns_base + int(ids[i]), xs[i].copy(),
                                  int(hits[i]), int(age[i]),
                                  mus[0][i].copy() if mus else None)
                    for i in np.nonzero(conf)[0]]

    # ----------------------------------------------------------- checkpoint
    def _checkpoint(self, t: _Tenant) -> None:
        with TraceAnnotation(CHECKPOINT_SPAN):
            sh = self.shards[t.shard]
            lane_bank = bank_lib.slice_sensor_bank(sh.banks, t.lane)
            try:
                t.ckpt.save(t.frames_applied, lane_bank,
                            extra=dict(tenant=t.name,
                                       frame=t.frames_applied,
                                       ns_base=t.ns_base,
                                       next_seq=t.next_seq),
                            blocking=True)
            except OSError as e:
                # keep the WAL — failover replays from the older snapshot
                warnings.warn(f"checkpoint for tenant {t.name!r} at frame "
                              f"{t.frames_applied} failed ({e!r}); WAL "
                              f"retained back to frame {t.ckpt_frame}",
                              RuntimeWarning, stacklevel=2)
                return
            t.ckpt_frame = t.frames_applied
            t.wal.clear()
            self.stats.checkpoints += 1

    # ------------------------------------------------------------- failover
    def _recover_dead(self, now: float) -> None:
        for name in self.monitor.dead_hosts():
            self._failover(self._shard(name))

    def _failover(self, sh: _Shard) -> None:
        """The dead shard's tenants restore onto survivors: checkpoint
        seeds the lane bitwise (mode-conditioned x/P/mu, lifecycle,
        ids), the WAL replays the frames applied since through the
        SURVIVING shard's own fused step (lanes are independent, so a
        scratch dispatch reproduces the lane bit-for-bit), and the
        tenant resumes where it left off — same track ids, same
        stream."""
        sh.alive = False
        self.stats.shards_lost += 1
        moved = self.alloc.tenants_on(sh.idx)
        for name in moved:
            self.alloc.release(name)
        self.alloc.drop_shard(sh.idx)
        self.monitor.remove(sh.name)
        sh.banks = None
        for name in moved:
            t = self.tenants[name]
            loc = None
            alive = {s.idx for s in self.shards if s.alive}
            while True:
                loc = self.alloc.acquire(name)
                if loc is None or loc[0] in alive:
                    break
                self.alloc.release(name)
                self.alloc.drop_shard(loc[0])
            if loc is None:
                t.parked = True
                self.stats.parked += 1
                warnings.warn(f"tenant {name!r} parked: no surviving "
                              f"lane to restore onto", RuntimeWarning,
                              stacklevel=2)
                continue
            self._restore_tenant(t, *loc)
            self.stats.failovers += 1

    def _restore_tenant(self, t: _Tenant, s: int, lane: int) -> None:
        target = self.shards[s]
        state, extra = t.ckpt.restore_latest(like=self._one)
        if extra["frame"] + len(t.wal) != t.frames_applied:
            warnings.warn(
                f"tenant {t.name!r}: WAL covers frames "
                f"{extra['frame']}..{extra['frame'] + len(t.wal)} but "
                f"{t.frames_applied} were applied — resuming from the "
                f"checkpoint loses the difference", RuntimeWarning,
                stacklevel=2)
        L = self.cfg.lanes_per_shard
        scratch = bank_lib.stack_sensor_banks(self._one, L)
        if target.device is not None:
            scratch = jax.device_put(scratch, target.device)
        scratch = bank_lib.place_sensor_bank(scratch, lane, state)
        M, m = self.tracker.max_meas, self.model.m
        for tier_i, z_row, v_row in t.wal:
            zb = np.zeros((L, M, m), np.float32)
            vb = np.zeros((L, M), bool)
            zb[lane], vb[lane] = z_row, v_row
            res = self._step_for(ServiceTier(tier_i))(
                scratch, jnp.asarray(zb), jnp.asarray(vb))
            scratch = res.bank
        target.banks = bank_lib.place_sensor_bank(
            target.banks, lane, bank_lib.slice_sensor_bank(scratch, lane))
        t.shard, t.lane, t.parked = s, lane, False
        # re-snapshot on the new shard so the next failover doesn't
        # replay this WAL again on top of the old checkpoint
        self._checkpoint(t)
