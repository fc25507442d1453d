"""MOT association + track lifecycle on top of the filter bank.

Everything is a single jittable frame-step with static shapes:

  1. predict all slots (batched-lanes rewrite) — this ALSO yields the
     frame's innovation quantities S, S^{-1} and P·Hᵀ, computed exactly
     once,
  2. Mahalanobis gating against the precomputed S^{-1},
  3. greedy globally-ordered assignment (iterated masked argmin — a
     fixed ``max_assign`` rounds of lax.fori_loop),
  4. measurement update of associated slots, reusing the same S^{-1}
     and P·Hᵀ (no second cofactor inversion),
  5. spawn tentative tracks for unassigned measurements,
  6. prune coasted tracks.

The association cost is the squared Mahalanobis distance
``d = y^T S^{-1} y`` using the SAME cofactor inverse the update's
Kalman gain uses — one ``small_inv`` per frame, total; the chi-square
gate defaults to the 99% quantile for the measurement dimension.

``imm_frame_step`` is the multi-model twin: K motion hypotheses per
slot (see ``repro.core.bank.IMMBankState``), IMM mixing inside the
predict, mode-probability-weighted gating, and K reused inverses per
frame (one per model — still nothing inverted twice).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bank as bank_lib
from repro.core.bank import BankState, IMMBankState
from repro.core.filters import FilterModel, IMMModel
from repro.core.rewrites import einsum, imm_combine

# 99% chi-square quantiles by dof (m <= 6 covers the paper's workloads)
CHI2_99 = {1: 6.63, 2: 9.21, 3: 11.34, 4: 13.28, 5: 15.09, 6: 16.81}


@dataclass(frozen=True)
class TrackerConfig:
    capacity: int = 256
    max_meas: int = 64
    gate: float = 0.0         # 0 => chi2_99[m]
    max_misses: int = 5
    min_hits: int = 3         # confirmations before a track is "real"
    dtype: str = "float32"
    # Route the frame's measurement cycle (predict + gate + greedy
    # assignment + update) through the fused ``katana_frame`` /
    # ``katana_imm_frame`` Pallas dispatch. The einsum path remains the
    # equivalence oracle (and the automatic fallback for models the
    # kernel can't serve: non-selector H, nonlinear IMM members).
    fused_frame: bool = True
    # Degradation knobs (the streaming front end's service ladder,
    # repro.serving.stream): gate_scale multiplies the chi-square gate —
    # a widened gate keeps tracks associated under degraded measurement
    # quality at the cost of more clutter acceptance. 1.0 = nominal.
    gate_scale: float = 1.0
    # Guard against non-finite measurements: a z row containing NaN/inf
    # is treated as "no detection" (its valid bit is cleared, the slot
    # coasts) instead of poisoning the bank state through the update
    # einsums. Serving front ends rely on this to survive corrupt
    # sensor payloads without a bank reset.
    nan_guard: bool = True


class FrameResult(NamedTuple):
    bank: BankState           # BankState or IMMBankState
    assoc: jnp.ndarray        # (C,) measurement index per slot or -1
    unassigned: jnp.ndarray   # (M,) bool — measurements that spawned
    confirmed: jnp.ndarray    # (C,) bool — active & hits >= min_hits
    # IMM extensions (None for the single-model frame step):
    mode_probs: Optional[jnp.ndarray] = None  # (C, K) per-track mode probs
    x_est: Optional[jnp.ndarray] = None       # (C, n) combined state means


def mahalanobis_cost(z_pred: jnp.ndarray, Sinv: jnp.ndarray,
                     z: jnp.ndarray) -> jnp.ndarray:
    """(C, m), (C, m, m) precomputed S^{-1}, (M, m) -> (C, M) squared
    Mahalanobis. Takes the inverse ``predict_bank`` already produced —
    gating never re-inverts the innovation covariance."""
    y = z[None, :, :] - z_pred[:, None, :]        # (C, M, m)
    return einsum("cMm,cmn,cMn->cM", y, Sinv, y)


def greedy_assign(cost: jnp.ndarray, valid: jnp.ndarray, gate: float,
                  rounds: int) -> jnp.ndarray:
    """Globally-ordered greedy assignment.

    cost: (C, M); valid: (C, M) bool (active slot x real measurement,
    within gate). Returns assoc (C,) int32: measurement index or -1.
    Each round picks the global minimum of the masked cost, commits the
    (slot, measurement) pair, and masks its row+column. ``rounds`` is a
    static bound (min(C, M) at most).
    """
    C, M = cost.shape
    BIG = jnp.asarray(jnp.finfo(cost.dtype).max, cost.dtype)
    masked = jnp.where(valid & (cost <= gate), cost, BIG)

    def body(_, carry):
        masked, assoc = carry
        flat = masked.reshape(-1)
        idx = jnp.argmin(flat)
        c, mm = idx // M, idx % M
        ok = flat[idx] < BIG
        assoc = jnp.where(ok, assoc.at[c].set(mm.astype(jnp.int32)), assoc)
        row_mask = jnp.arange(C) == c
        col_mask = jnp.arange(M) == mm
        kill = row_mask[:, None] | col_mask[None, :]
        masked = jnp.where(ok & kill, BIG, masked)
        return masked, assoc

    assoc0 = jnp.full((C,), -1, jnp.int32)
    _, assoc = jax.lax.fori_loop(0, rounds, body, (masked, assoc0))
    return assoc


def _use_fused_frame(model, cfg: TrackerConfig) -> bool:
    from repro.kernels.katana_bank.ops import frame_kernel_supported

    return cfg.fused_frame and frame_kernel_supported(model)


def _frame_inputs(model, cfg: TrackerConfig, z: jnp.ndarray,
                  z_valid: jnp.ndarray):
    """Shared frame-step preamble: the (scaled) gate, the assignment
    round bound, the dtype-cast measurements and the (possibly
    NaN-guarded) validity mask.

    Applied BEFORE the fused/einsum route split so both paths see
    bit-identical inputs — the equivalence oracle covers the guarded
    path for free. With all-finite measurements the guard is the
    identity (bitwise)."""
    dtype = jnp.dtype(cfg.dtype)
    gate = (cfg.gate or CHI2_99.get(model.m, 16.0)) * cfg.gate_scale
    rounds = min(cfg.capacity, cfg.max_meas)
    zt = z.astype(dtype)
    if cfg.nan_guard:
        finite = jnp.isfinite(zt).all(axis=-1)
        z_valid = z_valid & finite
        # zero (not just mask) the corrupt rows: 0·NaN = NaN would still
        # poison the update einsums the select runs after
        zt = jnp.where(finite[:, None], zt, 0.0)
    return dtype, float(gate), rounds, zt, z_valid


def frame_step(model: FilterModel, cfg: TrackerConfig, bank: BankState,
               z: jnp.ndarray, z_valid: jnp.ndarray) -> FrameResult:
    """One tracking frame. z: (max_meas, m); z_valid: (max_meas,) bool.

    Under ``cfg.fused_frame`` (the default) the measurement cycle —
    predict, innovation, gated Mahalanobis cost, greedy assignment,
    Kalman update — is ONE ``katana_frame`` Pallas dispatch; XLA keeps
    only the spawn/prune lifecycle bookkeeping. The einsum branch below
    is the equivalence oracle (identical assoc/ids, float32-tolerance
    states — tests/test_frame_kernel.py) and the fallback for models
    outside the kernel's contract."""
    dtype, gate, rounds, zt, z_valid = _frame_inputs(model, cfg, z, z_valid)
    if _use_fused_frame(model, cfg):
        from repro.kernels.katana_bank.ops import katana_frame

        x2, P2, assoc = katana_frame(model, bank.x, bank.P, zt, z_valid,
                                     bank.active, gate=float(gate),
                                     rounds=rounds)
        hits, misses, age = bank_lib.lifecycle_counters(bank, assoc)
        bank_u = bank._replace(x=x2, P=P2, hits=hits, misses=misses,
                               age=age)
    else:
        bank_p, z_pred, _S, Sinv, PHt = bank_lib.predict_bank(model, bank,
                                                              dtype)
        cost = mahalanobis_cost(z_pred, Sinv, zt)
        valid = bank_p.active[:, None] & z_valid[None, :]
        assoc = greedy_assign(cost, valid, jnp.asarray(gate, dtype), rounds)
        bank_u = bank_lib.update_bank(model, bank_p, zt, assoc, PHt, Sinv,
                                      dtype)
    taken = jnp.zeros((cfg.max_meas,), bool).at[
        jnp.clip(assoc, 0, cfg.max_meas - 1)
    ].max(assoc >= 0)
    unassigned = z_valid & ~taken
    bank_s = bank_lib.spawn_tracks(model, bank_u, zt, unassigned, dtype)
    bank_f = bank_lib.prune_bank(bank_s, cfg.max_misses)
    confirmed = bank_f.active & (bank_f.hits >= cfg.min_hits)
    return FrameResult(bank_f, assoc, unassigned, confirmed)


def imm_frame_step(imm: IMMModel, cfg: TrackerConfig, bank: IMMBankState,
                   z: jnp.ndarray, z_valid: jnp.ndarray) -> FrameResult:
    """One IMM tracking frame (the multi-model ``frame_step``).

    Same single-pass discipline: ``predict_imm_bank`` performs the IMM
    mixing and produces every innovation quantity once per (model,
    frame); gating, the K measurement updates AND the mode likelihoods
    all reuse them (K ``small_inv`` calls per frame for K models —
    nothing is inverted twice). Gating uses the mode-probability-
    weighted Mahalanobis distance sum_k cbar_k · d_k, so a maneuver
    hypothesis with high predicted probability widens the gate in the
    right direction. ``FrameResult.mode_probs`` carries the per-track
    mode posterior; ``FrameResult.x_est`` the moment-matched combined
    state (use it instead of ``bank.x``, which is model-conditioned).

    Under ``cfg.fused_frame`` (the default) the whole cycle — mixing,
    K predicts, the weighted gate, assignment, K updates, mode
    posterior and the combined estimate — is ONE ``katana_imm_frame``
    dispatch; XLA keeps spawn/prune and patches the combined estimate
    of freshly-spawned slots (their combined state IS the seed state).
    """
    dtype, gate, rounds, zt, z_valid = _frame_inputs(imm, cfg, z, z_valid)
    fused = _use_fused_frame(imm, cfg)
    if fused:
        from repro.kernels.katana_bank.ops import katana_imm_frame

        x2, P2, mu2, x_c, assoc = katana_imm_frame(
            imm, bank.x, bank.P, bank.mu, zt, z_valid, bank.active,
            gate=float(gate), rounds=rounds)
        hits, misses, age = bank_lib.lifecycle_counters(bank, assoc)
        bank_u = bank._replace(x=x2, P=P2, mu=mu2, hits=hits,
                               misses=misses, age=age)
    else:
        bank_p, z_pred, S, Sinv, PHt, cbar = bank_lib.predict_imm_bank(
            imm, bank, dtype)
        cost = sum(cbar[:, k, None] * mahalanobis_cost(z_pred[k], Sinv[k],
                                                       zt)
                   for k in range(imm.K))
        valid = bank_p.active[:, None] & z_valid[None, :]
        assoc = greedy_assign(cost, valid, jnp.asarray(gate, dtype), rounds)
        bank_u = bank_lib.update_imm_bank(imm, bank_p, zt, assoc, z_pred,
                                          PHt, Sinv, S, cbar, dtype)
    taken = jnp.zeros((cfg.max_meas,), bool).at[
        jnp.clip(assoc, 0, cfg.max_meas - 1)
    ].max(assoc >= 0)
    unassigned = z_valid & ~taken
    bank_s = bank_lib.spawn_imm_tracks(imm, bank_u, zt, unassigned, dtype)
    bank_f = bank_lib.prune_bank(bank_s, cfg.max_misses)
    confirmed = bank_f.active & (bank_f.hits >= cfg.min_hits)
    if fused:
        # the kernel's moment-matched combination covers every surviving
        # slot; a slot spawned THIS frame seeds all modes identically,
        # so its combined state is exactly the seed (model-0 slab)
        spawned = bank_s.active & ~bank_u.active
        x_est = jnp.where(spawned[:, None], bank_f.x[0], x_c)
    else:
        x_est, _ = imm_combine(bank_f.x, bank_f.P, bank_f.mu)
    return FrameResult(bank_f, assoc, unassigned, confirmed,
                       mode_probs=bank_f.mu, x_est=x_est)


def make_multi_sensor_step(model, cfg: TrackerConfig):
    """Build the S-sensor frame step: ``frame_step`` (FilterModel) or
    ``imm_frame_step`` (IMMModel) vmapped over a sensor axis.

    Returns ``(bank, axes, step)`` where ``bank`` is one empty
    single-sensor bank, ``axes`` the sensor-axis pytree
    (``bank.bank_sensor_axes`` — sensor axis 1 for the model-
    conditioned IMM leaves, 0 elsewhere) and
    ``step(banks, z, valid)`` maps ``z (S, max_meas, m)`` /
    ``valid (S, max_meas)`` over S independent sensors in one XLA
    program. Association, spawn/prune lifecycle and (for IMM) the
    shared-across-hypotheses track ids all stay strictly per-sensor —
    vmap carries no cross-sensor coupling, which is what makes the
    step shard_map-able with zero collectives
    (``repro.serving.engine.ShardedBankEngine``)."""
    is_imm = isinstance(model, IMMModel)
    one = (bank_lib.init_imm_bank if is_imm else bank_lib.init_bank)(
        model, cfg.capacity, jnp.dtype(cfg.dtype))
    axes = bank_lib.bank_sensor_axes(one)
    base = imm_frame_step if is_imm else frame_step
    out_axes = FrameResult(bank=axes, assoc=0, unassigned=0, confirmed=0,
                           mode_probs=0, x_est=0)
    step = jax.vmap(
        lambda bank, z, valid: base(model, cfg, bank, z, valid),
        in_axes=(axes, 0, 0), out_axes=out_axes)
    return one, axes, step


def make_jitted_tracker(model: FilterModel, cfg: TrackerConfig):
    """Returns (init_bank, step) with step jitted over (bank, z, valid)."""

    def init():
        return bank_lib.init_bank(model, cfg.capacity, jnp.dtype(cfg.dtype))

    @jax.jit
    def step(bank: BankState, z: jnp.ndarray, z_valid: jnp.ndarray):
        return frame_step(model, cfg, bank, z, z_valid)

    return init, step


def make_jitted_imm_tracker(imm: IMMModel, cfg: TrackerConfig):
    """IMM twin of ``make_jitted_tracker``: (init, step) over an
    IMMBankState — still one jittable call per frame."""

    def init():
        return bank_lib.init_imm_bank(imm, cfg.capacity,
                                      jnp.dtype(cfg.dtype))

    @jax.jit
    def step(bank: IMMBankState, z: jnp.ndarray, z_valid: jnp.ndarray):
        return imm_frame_step(imm, cfg, bank, z, z_valid)

    return init, step
