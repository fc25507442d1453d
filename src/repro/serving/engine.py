"""KATANA tracking engine: the paper's serving workload as a batched
request server.

One jitted frame step (predict -> gate -> associate -> update -> spawn
-> prune) services every client per frame — the paper's "single
inference call" — with a fixed-capacity bank per sensor. Under
``TrackerConfig.fused_frame`` (the default) the measurement cycle of
that step IS one ``katana_frame``/``katana_imm_frame`` Pallas dispatch
(gating and greedy assignment in-kernel, only spawn/prune bookkeeping
in XLA), so the closed-loop FPS the engine reports is the fused-kernel
number; ``fused_frame=False`` serves the einsum oracle path instead.
The engine is deliberately synchronous-deterministic: requests are
padded into the static measurement slots (Opt-2 discipline), so
serving latency is the latency of one kernel launch regardless of
load.

``ShardedBankEngine`` scales the same step across a mesh: banks are
data-parallel over sensors (each sensor's scene is independent), the
sensor axis is shard_mapped over the mesh data axes, and the step —
single-model or the full IMM multi-model cycle — is one XLA program
over the stacked banks. The IMM bank shards as (K, S, C, n): model
axis K replicated-by-construction (it's the lane-stacking axis inside
a shard), sensors S split across the mesh, so every shard runs the
bitwise-identical per-sensor ``imm_frame_step``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import bank as bank_lib
from repro.core.bank import BankState, init_bank, init_imm_bank
from repro.core.filters import FilterModel, IMMModel, as_imm
from repro.core.tracker import (FrameResult, TrackerConfig, frame_step,
                                imm_frame_step, make_multi_sensor_step)
from repro.execmode import active_mode
from repro.kernels.katana_bank.ops import (katana_bank_sequence,
                                           katana_imm_sequence)
from repro.sharding.rules import make_context, sensor_specs


@dataclass
class TrackSnapshot:
    track_id: int
    state: np.ndarray
    hits: int
    age: int
    # IMM engines only: per-mode probabilities (K,), aligned with
    # model.models; None for single-model engines
    mode_probs: Optional[np.ndarray] = None


@dataclass
class EngineStats:
    frames: int = 0
    total_latency_s: float = 0.0
    measurements: int = 0
    # offline replay is tracked separately so the real-time serving fps
    # metric is never diluted by batch dispatches
    replay_frames: int = 0
    replay_latency_s: float = 0.0

    @property
    def fps(self) -> float:
        return self.frames / self.total_latency_s if self.total_latency_s else 0.0

    @property
    def replay_fps(self) -> float:
        return (self.replay_frames / self.replay_latency_s
                if self.replay_latency_s else 0.0)


class TrackingEngine:
    """Single-sensor engine: submit measurements per frame, get
    confirmed tracks back.

    Accepts a plain FilterModel or an IMMModel — an IMM engine runs the
    multi-model frame step (K hypotheses per slot) and reports the
    moment-matched combined state plus per-mode probabilities in every
    snapshot; the serving surface is otherwise identical."""

    def __init__(self, model, cfg: Optional[TrackerConfig] = None):
        self.model = model
        self.cfg = cfg or TrackerConfig()
        # the backend's execution mode: recorded here so serving
        # telemetry can always say whether the kernels ran compiled or
        # through the interpreter
        self.exec_mode = active_mode()
        self.is_imm = isinstance(model, IMMModel)
        if self.is_imm:
            self.bank = init_imm_bank(model, self.cfg.capacity,
                                      jnp.dtype(self.cfg.dtype))
            self._step = jax.jit(
                lambda bank, z, valid: imm_frame_step(model, self.cfg, bank,
                                                      z, valid))
        else:
            self.bank = init_bank(model, self.cfg.capacity,
                                  jnp.dtype(self.cfg.dtype))
            self._step = jax.jit(
                lambda bank, z, valid: frame_step(model, self.cfg, bank, z,
                                                  valid))
        self.stats = EngineStats()
        # warm the compile so serving latency excludes tracing
        z0 = jnp.zeros((self.cfg.max_meas, model.m), jnp.float32)
        v0 = jnp.zeros((self.cfg.max_meas,), bool)
        self._step(self.bank, z0, v0).bank.x.block_until_ready()

    def submit(self, measurements: np.ndarray) -> List[TrackSnapshot]:
        """measurements: (k, m) this frame (k <= max_meas)."""
        mm = np.zeros((self.cfg.max_meas, self.model.m), np.float32)
        vv = np.zeros((self.cfg.max_meas,), bool)
        k = min(len(measurements), self.cfg.max_meas)
        if k:
            mm[:k] = measurements[:k]
            vv[:k] = True
        t0 = time.perf_counter()
        res = self._step(self.bank, jnp.asarray(mm), jnp.asarray(vv))
        res.bank.x.block_until_ready()
        self.stats.total_latency_s += time.perf_counter() - t0
        self.stats.frames += 1
        self.stats.measurements += int(k)
        self.bank = res.bank
        conf = np.asarray(res.confirmed)
        ids = np.asarray(self.bank.track_id)
        # IMM: report the combined (moment-matched) state, not the
        # model-conditioned bank.x
        xs = np.asarray(res.x_est if res.x_est is not None else self.bank.x)
        mus = (np.asarray(res.mode_probs) if res.mode_probs is not None
               else None)
        hits = np.asarray(self.bank.hits)
        age = np.asarray(self.bank.age)
        return [TrackSnapshot(int(ids[i]), xs[i].copy(), int(hits[i]),
                              int(age[i]),
                              mus[i].copy() if mus is not None else None)
                for i in np.nonzero(conf)[0]]

    def replay(self, zs: np.ndarray, x0: Optional[np.ndarray] = None,
               P0: Optional[np.ndarray] = None) -> np.ndarray:
        """Batch-filter a pre-associated (T, N, m) measurement stream in
        ONE fused kernel dispatch (the ``fused_scan`` stage).

        This is the offline/replay companion to ``submit``: when the
        measurement->track association is already known (log replay,
        re-scoring, smoothing passes), the per-frame gate/assign
        machinery is pure overhead — the whole sequence runs inside
        ``katana_bank_sequence`` with x/P kernel-resident across
        frames. Returns the (T, N, n) filtered states. Does not touch
        the live bank, and is accounted under the replay_* stats so the
        real-time serving fps stays meaningful. IMM engines replay
        through ``katana_imm_sequence`` — the fused IMM scan (mixing and
        mode posterior inside the kernel's time loop, one dispatch per
        chunk), combined estimates out.
        """
        zs = np.asarray(zs, np.float32)
        T, N, m = zs.shape
        if x0 is None:
            x0 = np.tile(self.model.x0, (N, 1)).astype(np.float32)
        if P0 is None:
            P0 = np.tile(self.model.P0, (N, 1, 1)).astype(np.float32)
        seq = katana_imm_sequence if self.is_imm else katana_bank_sequence
        t0 = time.perf_counter()
        out = seq(self.model, jnp.asarray(zs),
                  jnp.asarray(x0, jnp.float32),
                  jnp.asarray(P0, jnp.float32),
                  interpret=self.exec_mode.interpret)
        out.block_until_ready()
        self.stats.replay_latency_s += time.perf_counter() - t0
        self.stats.replay_frames += T
        return np.asarray(out)


class ShardedBankEngine:
    """S independent sensors, one sharded step over stacked banks.

    Accepts a plain FilterModel or an IMMModel, exactly like
    ``TrackingEngine``: an IMM fleet runs ``imm_frame_step`` per sensor
    (K hypotheses per slot, spawn/prune lifecycle and track ids shared
    across hypotheses) and every ``frame`` returns the stacked
    per-sensor ``FrameResult`` with mode probabilities and the
    moment-matched combined estimates.

    Banks stack on a sensor axis (position 1 — after the model axis K —
    for the IMM x/P leaves, leading elsewhere: the (K, S, C, n)
    placement) that is shard_mapped over the mesh data axes
    (``sharding.rules.sensor_specs`` + ``jax.shard_map``).
    Association stays per-sensor (vmapped), sensors are independent, so
    the step carries zero collectives and every shard computes the
    bitwise-identical unsharded per-sensor frame — the pod-scale
    version of the paper's N=200 batching. Without a mesh the same
    vmapped step runs as one jit call (the S=local case).
    """

    def __init__(self, model, n_sensors: int,
                 cfg: Optional[TrackerConfig] = None, mesh=None):
        self.model = model
        self.cfg = cfg or TrackerConfig(capacity=64, max_meas=32)
        self.exec_mode = active_mode()
        self.n = n_sensors
        self.is_imm = isinstance(model, IMMModel)
        self.mesh = mesh
        one, axes, step = make_multi_sensor_step(model, self.cfg)
        self._axes = axes
        self.banks = bank_lib.stack_sensor_banks(one, n_sensors)
        self.stats = EngineStats()
        self._ctx = make_context(mesh)
        self._bank_specs = sensor_specs(axes, self.banks, self._ctx)
        self._replay_fns: Dict[bool, callable] = {}
        if mesh is None:
            self._step = jax.jit(step)
        else:
            if n_sensors % self._ctx.data_size:
                raise ValueError(
                    f"n_sensors={n_sensors} must divide over the mesh "
                    f"data axes (size {self._ctx.data_size})")
            res_specs = FrameResult(
                bank=self._bank_specs,
                assoc=self._ctx.batch_spec(2),
                unassigned=self._ctx.batch_spec(2),
                confirmed=self._ctx.batch_spec(2),
                mode_probs=self._ctx.batch_spec(3),
                x_est=self._ctx.batch_spec(3))
            self._step = jax.jit(jax.shard_map(
                step, mesh=mesh,
                in_specs=(self._bank_specs, self._ctx.batch_spec(3),
                          self._ctx.batch_spec(2)),
                out_specs=res_specs, check_vma=False))
            self.banks = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                self.banks, self._bank_specs)
        # warm the compile so serving latency excludes tracing
        z0 = jnp.zeros((n_sensors, self.cfg.max_meas, model.m), jnp.float32)
        v0 = jnp.zeros((n_sensors, self.cfg.max_meas), bool)
        self._step(self.banks, z0, v0).bank.x.block_until_ready()

    def frame(self, z: np.ndarray, valid: np.ndarray) -> FrameResult:
        """z: (S, max_meas, m); valid: (S, max_meas). Returns the
        stacked per-sensor FrameResult (sensor-leading leaves; for IMM
        engines ``mode_probs (S, C, K)`` and ``x_est (S, C, n)``)."""
        t0 = time.perf_counter()
        res = self._step(self.banks, jnp.asarray(z, jnp.float32),
                         jnp.asarray(valid))
        res.bank.x.block_until_ready()
        self.stats.total_latency_s += time.perf_counter() - t0
        self.stats.frames += 1
        self.stats.measurements += int(np.asarray(valid).sum())
        self.banks = res.bank
        return res

    def snapshots(self, res: FrameResult) -> List[List[TrackSnapshot]]:
        """Per-sensor confirmed-track snapshots from a ``frame`` result
        — the fleet version of ``TrackingEngine.submit``'s return (IMM
        engines report the combined state + mode probabilities)."""
        conf = np.asarray(res.confirmed)
        ids = np.asarray(self.banks.track_id)
        hits = np.asarray(self.banks.hits)
        age = np.asarray(self.banks.age)
        if self.is_imm:
            xs = np.asarray(res.x_est)
            mus = np.asarray(res.mode_probs)
        else:
            xs, mus = np.asarray(self.banks.x), None
        return [[TrackSnapshot(int(ids[s, i]), xs[s, i].copy(),
                               int(hits[s, i]), int(age[s, i]),
                               mus[s, i].copy() if mus is not None else None)
                 for i in np.nonzero(conf[s])[0]]
                for s in range(self.n)]

    def _build_replay(self, has_valid: bool):
        """Jitted (and, under a mesh, shard_mapped) fused-replay fn:
        each shard flattens its local sensors onto the kernel's track
        axis and runs ``katana_imm_sequence`` ONCE — one dispatch per
        track batch per shard, coasting mask included. Single-model
        engines route through the degenerate K=1 IMM, which reduces
        bitwise to the single-model fused scan."""
        imm = self.model if self.is_imm else as_imm(self.model)
        C, K, n, m = self.cfg.capacity, imm.K, imm.n, imm.m
        is_imm = self.is_imm
        interp = self.exec_mode.interpret

        def body(banks, zs, *rest):
            T, S_loc = zs.shape[0], zs.shape[1]
            if is_imm:
                x0 = banks.x.reshape(K, S_loc * C, n)
                P0 = banks.P.reshape(K, S_loc * C, n, n)
                mu0 = banks.mu.reshape(S_loc * C, K)
            else:
                x0 = banks.x.reshape(S_loc * C, n)
                P0 = banks.P.reshape(S_loc * C, n, n)
                mu0 = None
            v = rest[0].reshape(T, S_loc * C) if rest else None
            out = katana_imm_sequence(imm, zs.reshape(T, S_loc * C, m),
                                      x0, P0, mu0=mu0, valid=v,
                                      interpret=interp)
            return out.reshape(T, S_loc, C, n)

        if self.mesh is None:
            return jax.jit(body)
        zspec = P(None, self._ctx.data_axes, None, None)
        in_specs = (self._bank_specs, zspec) + (
            (P(None, self._ctx.data_axes, None),) if has_valid else ())
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=in_specs, out_specs=zspec,
                                     check_vma=False))

    def replay(self, zs: np.ndarray,
               valid: Optional[np.ndarray] = None) -> np.ndarray:
        """Batch-refilter per-sensor pre-associated streams through the
        fused scan, seeded from the LIVE banks.

        zs: (T, S, C, m) slot-aligned measurement streams (C = the
        bank capacity — row c of sensor s feeds slot c, the
        ``replay_imm_bank`` contract per sensor); valid: optional
        (T, S, C) coasting mask (False = no measurement that frame:
        time update only, mu <- the Markov-predicted cbar). IMM engines
        resume the mode-conditioned (x, P, mu); the whole fleet is one
        ``katana_imm_sequence`` dispatch per track batch per shard.
        Returns the (T, S, C, n) moment-matched combined estimates.
        Does not modify the live banks; accounted under the replay_*
        stats like ``TrackingEngine.replay``.
        """
        zs = jnp.asarray(np.asarray(zs, np.float32))
        T, S, C, _ = zs.shape
        assert S == self.n and C == self.cfg.capacity, (zs.shape, self.n,
                                                        self.cfg.capacity)
        has_valid = valid is not None
        if has_valid not in self._replay_fns:
            self._replay_fns[has_valid] = self._build_replay(has_valid)
        args = (self.banks, zs) + (
            (jnp.asarray(np.asarray(valid, bool)),) if has_valid else ())
        t0 = time.perf_counter()
        out = self._replay_fns[has_valid](*args)
        out.block_until_ready()
        self.stats.replay_latency_s += time.perf_counter() - t0
        self.stats.replay_frames += T
        return np.asarray(out)
