"""Fixed-capacity filter bank: KATANA's "one inference call, N filters".

The bank is the deployable MOT substrate: a static-shape array of
``capacity`` filter slots (state, covariance, lifecycle counters) that
runs the batched-lanes rewrite every frame. Static shapes everywhere —
slots are (de)activated by masks, never by reshaping — which is exactly
the paper's Opt-2 (§IV-C static-fusion) discipline applied at the
*system* level, and what makes the whole tracker a single jittable step.

``IMMBankState`` is the multi-model extension: every slot carries K
model-conditioned (x, P) pairs plus mode probabilities mu, and the
predict step runs the IMM interaction (mixing) before the K per-model
time updates — the §IV-D batching axis reused for the model index.
Lifecycle (active/hits/misses/age/track_id) stays per-SLOT, shared by
all K hypotheses.

Pod-scale MOT shards the bank over the mesh data axis (see
``repro.serving.engine`` / ``repro.launch.serve``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import FilterModel, IMMModel
from repro.core.rewrites import (build_batched_lanes, einsum,
                                 gaussian_loglik, imm_mix,
                                 imm_mode_posterior, matmul, small_det,
                                 small_inv, stage_constants, sym_unpack,
                                 triu_pack)


class BankState(NamedTuple):
    x: jnp.ndarray        # (C, n) state means
    P: jnp.ndarray        # (C, n, n) covariances
    active: jnp.ndarray   # (C,) bool
    hits: jnp.ndarray     # (C,) int32 — consecutive associations
    misses: jnp.ndarray   # (C,) int32 — consecutive misses
    age: jnp.ndarray      # (C,) int32 — frames since spawn
    track_id: jnp.ndarray  # (C,) int32 — stable external id (-1 = free)
    next_id: jnp.ndarray  # () int32 — id counter


def init_bank(model: FilterModel, capacity: int, dtype=jnp.float32) -> BankState:
    n = model.n
    return BankState(
        x=jnp.zeros((capacity, n), dtype),
        P=jnp.broadcast_to(jnp.asarray(model.P0, dtype), (capacity, n, n)).copy(),
        active=jnp.zeros((capacity,), bool),
        hits=jnp.zeros((capacity,), jnp.int32),
        misses=jnp.zeros((capacity,), jnp.int32),
        age=jnp.zeros((capacity,), jnp.int32),
        track_id=jnp.full((capacity,), -1, jnp.int32),
        next_id=jnp.zeros((), jnp.int32),
    )


def _predict_lanes(model: FilterModel, x: jnp.ndarray, P: jnp.ndarray,
                   dtype=jnp.float32):
    """Batched-lanes time update + innovation quantities for (C, n)
    states: returns (x_pred, P_pred, z_pred, S, Sinv, PHt). This is the
    single place S is built and inverted per (model, frame) — shared by
    the plain and the IMM bank.

    The covariance propagation emits only the upper triangle of
    F·P·Fᵀ + Q and aliases the mirrors (``rewrites.triu_pack``) — the
    kernels' symmetrize=True discipline on the einsum path: exact
    symmetry by construction (no square-then-average pass) at
    n(n+1)/2 instead of n² second-contraction dots."""
    n = model.n
    iu, ju, _ = triu_pack(n)
    C = stage_constants(model, dtype)
    Qtri = C.Q[iu, ju]
    if model.is_linear:
        x_pred = einsum("ij,kj->ki", C.F, x)
        FP = einsum("ij,kjl->kil", C.F, P)
        tri = einsum("ktl,tl->kt", FP[:, iu, :], C.F[ju, :]) + Qtri
    else:
        x_pred = model.predict_mean(x)
        Fk = model.jacobian(x)
        FP = einsum("kij,kjl->kil", Fk, P)
        tri = einsum("ktl,ktl->kt", FP[:, iu, :], Fk[:, ju, :]) + Qtri
    P_pred = sym_unpack(tri, n)
    z_pred = einsum("mi,ki->km", C.H, x_pred)
    PHt = einsum("kij,mj->kim", P_pred, C.H)
    S = einsum("mi,kij,nj->kmn", C.H, P_pred, C.H) + C.R
    Sinv = small_inv(S, model.m)
    return x_pred, P_pred, z_pred, S, Sinv, PHt


def _kalman_update_lanes(model: FilterModel, x_pred, P_pred, zk, PHt, Sinv,
                         dtype=jnp.float32):
    """Subtract-free (H_neg, paper §IV-B) batched measurement update for
    (C, n) lanes, consuming the precomputed P·Hᵀ and S^{-1}. The
    posterior covariance P̂ + K·(H_neg·P̂) is emitted upper-triangle-only
    with aliased mirrors (exact symmetry — replaces the old
    0.5·(P + Pᵀ) averaging pass, see ``_predict_lanes``)."""
    n = model.n
    iu, ju, _ = triu_pack(n)
    C = stage_constants(model, dtype)
    y = zk + einsum("mi,ki->km", C.H_neg, x_pred)
    K = einsum("kim,kmn->kin", PHt, Sinv)
    x_new = x_pred + einsum("kin,kn->ki", K, y)
    HnP = einsum("mi,kij->kmj", C.H_neg, P_pred)
    tri = (P_pred[:, iu, ju]
           + einsum("ktm,kmt->kt", K[:, iu, :], HnP[:, :, ju]))
    return x_new, sym_unpack(tri, n)


def predict_bank(model: FilterModel, bank: BankState,
                 dtype=jnp.float32) -> Tuple[BankState, jnp.ndarray,
                                             jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray]:
    """Time-update every slot (inactive slots are harmlessly propagated —
    static shapes beat branching).

    Returns (bank', z_pred (C, m), S (C, m, m), Sinv (C, m, m),
    PHt (C, n, m)). The innovation covariance, its cofactor inverse and
    P·Hᵀ are computed HERE, exactly once per frame; gating
    (``tracker.mahalanobis_cost``) and the measurement update
    (``update_bank``) consume these instead of rebuilding them — the
    KATANA single-pass discipline applied to the MOT hot path.
    """
    x_pred, P_pred, z_pred, S, Sinv, PHt = _predict_lanes(
        model, bank.x, bank.P, dtype)
    return bank._replace(x=x_pred, P=P_pred), z_pred, S, Sinv, PHt


def update_bank(model: FilterModel, bank: BankState, z: jnp.ndarray,
                assoc: jnp.ndarray, PHt: Optional[jnp.ndarray] = None,
                Sinv: Optional[jnp.ndarray] = None,
                dtype=jnp.float32) -> BankState:
    """Measurement-update associated slots.

    z: (M, m) padded measurements; assoc: (C,) int32 — index into z for
    each slot, or -1 (no measurement → skip update, bump miss counter).
    PHt (C, n, m) and Sinv (C, m, m) are the innovation quantities
    ``predict_bank`` already computed for this frame — pass them through
    (as ``frame_step`` does) so the update never rebuilds S or inverts
    it a second time. The None fallback recomputes for standalone use.
    Runs the full batched update unconditionally and select-masks the
    result (static shapes; the redundant lanes are the price of zero
    control flow, the same trade the paper makes on the DPU).
    """
    C = stage_constants(model, dtype)
    has_z = assoc >= 0
    zk = z[jnp.clip(assoc, 0, z.shape[0] - 1)]  # (Cap, m), garbage where -1
    x_pred, P_pred = bank.x, bank.P
    if PHt is None:
        PHt = einsum("kij,mj->kim", P_pred, C.H)
    if Sinv is None:
        S = einsum("mi,kij,nj->kmn", C.H, P_pred, C.H) + C.R
        Sinv = small_inv(S, model.m)
    x_new, P_new = _kalman_update_lanes(model, x_pred, P_pred, zk, PHt, Sinv,
                                        dtype)

    upd = has_z & bank.active
    x_out = jnp.where(upd[:, None], x_new, x_pred)
    P_out = jnp.where(upd[:, None, None], P_new, P_pred)
    hits, misses, age = lifecycle_counters(bank, assoc)
    return bank._replace(x=x_out, P=P_out, hits=hits, misses=misses, age=age)


def lifecycle_counters(bank, assoc: jnp.ndarray):
    """The per-slot hit/miss/age advance for one frame, from the
    association result: assoc (C,) measurement index or -1. The ONE
    definition of this algebra — ``update_bank``/``update_imm_bank``
    interleave it with the measurement update, and the tracker's fused
    route (where the kernel owns the state update and XLA only advances
    the integer counters) applies it standalone. Returns (hits, misses,
    age)."""
    upd = (assoc >= 0) & bank.active
    hits = jnp.where(upd, bank.hits + 1, bank.hits)
    misses = jnp.where(upd, 0, jnp.where(bank.active, bank.misses + 1,
                                         bank.misses))
    age = jnp.where(bank.active, bank.age + 1, bank.age)
    return hits, misses, age


def _spawn_plan(active: jnp.ndarray, unassigned: jnp.ndarray):
    """Deterministic free-slot packing: the j-th unassigned measurement
    claims the j-th free slot (cumsum ranks — static shapes, no host
    round-trip). Returns (take (Cap, M), takes_any (Cap,),
    free_rank (Cap,))."""
    free = ~active  # (Cap,)
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1       # rank among free
    meas_rank = jnp.cumsum(unassigned.astype(jnp.int32)) - 1  # rank among new
    # slot s takes measurement j iff free[s] and meas_rank[j]==free_rank[s]
    take = (free[:, None] & unassigned[None, :] &
            (free_rank[:, None] == meas_rank[None, :]))  # (Cap, M)
    return take, take.any(axis=1), free_rank


def _spawn_init_state(model: FilterModel, take: jnp.ndarray, z: jnp.ndarray,
                      dtype=jnp.float32):
    """Measurement-seeded initial state per claiming slot: z mapped
    through Hᵀ (exact for position-selector H), the unobserved state
    components at the model defaults."""
    zsel = einsum("sm,mq->sq", take.astype(z.dtype), z)  # (Cap, m)
    Ht = jnp.asarray(model.H.T, dtype)
    return einsum("nm,sm->sn", Ht, zsel) + jnp.asarray(
        model.x0, dtype) * (1.0 - einsum("nm,m->n", Ht,
                                             jnp.ones((model.m,), dtype)))


def spawn_tracks(model: FilterModel, bank: BankState, z: jnp.ndarray,
                 unassigned: jnp.ndarray, dtype=jnp.float32) -> BankState:
    """Open new tracks for unassigned measurements in free slots.

    z: (M, m); unassigned: (M,) bool.
    """
    Cap = bank.x.shape[0]
    take, takes_any, free_rank = _spawn_plan(bank.active, unassigned)
    x_init = _spawn_init_state(model, take, z, dtype)
    P_init = jnp.broadcast_to(jnp.asarray(model.P0, dtype),
                              (Cap, model.n, model.n))
    new_ids = bank.next_id + free_rank.astype(jnp.int32)
    return bank._replace(
        x=jnp.where(takes_any[:, None], x_init, bank.x),
        P=jnp.where(takes_any[:, None, None], P_init, bank.P),
        active=bank.active | takes_any,
        hits=jnp.where(takes_any, 1, bank.hits),
        misses=jnp.where(takes_any, 0, bank.misses),
        age=jnp.where(takes_any, 0, bank.age),
        track_id=jnp.where(takes_any, new_ids, bank.track_id),
        next_id=bank.next_id + jnp.sum(takes_any.astype(jnp.int32)),
    )


def bank_sensor_axes(bank):
    """Per-leaf sensor-axis positions for stacking this bank over S
    independent sensors — the vmap in/out_axes pytree and the axis the
    serving mesh shards.

    Model-conditioned leaves of an ``IMMBankState`` (x, P) keep the
    model axis K outermost, so the sensor axis slots in at position 1
    and the stacked layout is ``(K, S, C, ...)`` — one contiguous
    (sensor, slot) block per model slab, which is what lets the sharded
    replay flatten a shard's sensors straight onto the kernel's track
    axis. Every other leaf (mu, lifecycle, ids) leads with S.
    """
    if isinstance(bank, IMMBankState):
        return IMMBankState(x=1, P=1, mu=0, active=0, hits=0, misses=0,
                            age=0, track_id=0, next_id=0)
    return BankState(x=0, P=0, active=0, hits=0, misses=0, age=0,
                     track_id=0, next_id=0)


def stack_sensor_banks(bank, n_sensors: int):
    """Broadcast one bank into an S-sensor stack along
    ``bank_sensor_axes`` (every sensor starts from the same empty
    bank). Works on BankState and IMMBankState alike."""

    def put(x, a):
        x = jnp.expand_dims(x, a)
        shape = x.shape[:a] + (n_sensors,) + x.shape[a + 1:]
        return jnp.broadcast_to(x, shape).copy()

    return jax.tree.map(put, bank, bank_sensor_axes(bank))


def slice_sensor_bank(banks, s: int):
    """Extract sensor/lane ``s`` of a stacked bank as a single-sensor
    bank (the inverse of one lane of ``stack_sensor_banks``).

    This is the checkpoint/failover surface: a tenant's lane of the
    serving fleet is snapshotted and restored as a plain
    BankState/IMMBankState pytree, so ``checkpoint.ckpt`` can save it
    and a different shard/lane can receive it without knowing the
    fleet layout. Works on BankState and IMMBankState alike."""
    return jax.tree.map(
        lambda x, a: jax.lax.index_in_dim(x, s, axis=a, keepdims=False),
        banks, bank_sensor_axes(banks))


def place_sensor_bank(banks, s: int, one):
    """Write a single-sensor bank into lane ``s`` of a stacked bank
    (the other lanes untouched) — the restore half of
    ``slice_sensor_bank``. Used by the streaming front end's failover
    path to graft a checkpointed tenant bank onto a surviving shard's
    stack. Returns the new stacked bank."""

    def put(full, x, a):
        idx = tuple(slice(None) for _ in range(a)) + (s,)
        return full.at[idx].set(jnp.asarray(x, full.dtype))

    return jax.tree.map(put, banks, one, bank_sensor_axes(banks))


def prune_bank(bank, max_misses: int = 5):
    """Retire tracks that coasted too long; their slots become free.
    Works on BankState and IMMBankState alike (shared lifecycle
    fields)."""
    dead = bank.active & (bank.misses > max_misses)
    return bank._replace(
        active=bank.active & ~dead,
        track_id=jnp.where(dead, -1, bank.track_id),
        hits=jnp.where(dead, 0, bank.hits),
        misses=jnp.where(dead, 0, bank.misses),
    )


# ---------------------------------------------------------------------------
# IMM multi-model bank: K hypotheses per slot, shared lifecycle.
# ---------------------------------------------------------------------------

class IMMBankState(NamedTuple):
    x: jnp.ndarray        # (K, C, n) model-conditioned state means
    P: jnp.ndarray        # (K, C, n, n) model-conditioned covariances
    mu: jnp.ndarray       # (C, K) mode probabilities (rows sum to 1)
    active: jnp.ndarray   # (C,) bool
    hits: jnp.ndarray     # (C,) int32 — consecutive associations
    misses: jnp.ndarray   # (C,) int32 — consecutive misses
    age: jnp.ndarray      # (C,) int32 — frames since spawn
    track_id: jnp.ndarray  # (C,) int32 — stable external id (-1 = free)
    next_id: jnp.ndarray  # () int32 — id counter


def init_imm_bank(imm: IMMModel, capacity: int,
                  dtype=jnp.float32) -> IMMBankState:
    n, K = imm.n, imm.K
    return IMMBankState(
        x=jnp.zeros((K, capacity, n), dtype),
        P=jnp.broadcast_to(jnp.asarray(imm.P0, dtype),
                           (K, capacity, n, n)).copy(),
        mu=jnp.broadcast_to(jnp.asarray(imm.mu0, dtype),
                            (capacity, K)).copy(),
        active=jnp.zeros((capacity,), bool),
        hits=jnp.zeros((capacity,), jnp.int32),
        misses=jnp.zeros((capacity,), jnp.int32),
        age=jnp.zeros((capacity,), jnp.int32),
        track_id=jnp.full((capacity,), -1, jnp.int32),
        next_id=jnp.zeros((), jnp.int32),
    )


def predict_imm_bank(imm: IMMModel, bank: IMMBankState, dtype=jnp.float32):
    """IMM interaction (mixing) + K model-conditioned time updates.

    Returns (bank', z_pred (K, C, m), S (K, C, m, m), Sinv (K, C, m, m),
    PHt (K, C, n, m), cbar (C, K)). Like ``predict_bank``, every
    innovation quantity is produced exactly once per (model, frame):
    gating, the measurement update AND the mode likelihoods all consume
    these — K ``small_inv`` calls per frame, total, for K models.
    ``cbar`` is the Markov-predicted mode probability (the coasting
    posterior when a track gets no measurement)."""
    Pi = jnp.asarray(imm.trans, dtype)
    x_mix, P_mix, cbar = imm_mix(bank.x, bank.P, bank.mu, Pi)
    outs = [_predict_lanes(model, x_mix[k], P_mix[k], dtype)
            for k, model in enumerate(imm.models)]
    x_pred, P_pred, z_pred, S, Sinv, PHt = (
        jnp.stack([o[i] for o in outs]) for i in range(6))
    return (bank._replace(x=x_pred, P=P_pred), z_pred, S, Sinv, PHt, cbar)


def update_imm_bank(imm: IMMModel, bank: IMMBankState, z: jnp.ndarray,
                    assoc: jnp.ndarray,
                    z_pred: Optional[jnp.ndarray] = None,
                    PHt: Optional[jnp.ndarray] = None,
                    Sinv: Optional[jnp.ndarray] = None,
                    S: Optional[jnp.ndarray] = None,
                    cbar: Optional[jnp.ndarray] = None,
                    dtype=jnp.float32) -> IMMBankState:
    """K model-conditioned measurement updates + the mode posterior.

    z: (M, m) padded measurements; assoc: (C,) measurement index or -1.
    z_pred/PHt/Sinv/S are the (K, ...) innovation quantities from
    ``predict_imm_bank`` — pass them through (as ``imm_frame_step``
    does) so nothing is rebuilt or re-inverted here; the mode
    likelihoods reuse the same S^{-1} as the Kalman gains
    (``gaussian_loglik``). The None fallback recomputes any missing
    quantity from the predicted bank for standalone use (``bank`` must
    be the POST-predict state; its ``mu`` is still the pre-mix
    distribution, so cbar is recoverable from the Markov chain) — same
    expressions as ``_predict_lanes``, so the fallback is bit-identical
    to the pass-through. Associated slots get the Bayes posterior
    mu ∝ cbar·N(y; 0, S); coasting slots keep the Markov-predicted cbar
    (which stays normalized — no renormalization drift while a track
    coasts). Lifecycle counters advance once per slot, not per model.
    """
    m = imm.m
    # each missing quantity recomputes independently — a caller short
    # only of cbar pays no innovation einsums at all
    consts = ([stage_constants(model, dtype) for model in imm.models]
              if z_pred is None or PHt is None or S is None else None)
    if z_pred is None:
        z_pred = jnp.stack([einsum("mi,ki->km", Ck.H, bank.x[k])
                            for k, Ck in enumerate(consts)])
    if PHt is None:
        PHt = jnp.stack([einsum("kij,mj->kim", bank.P[k], Ck.H)
                         for k, Ck in enumerate(consts)])
    if S is None:
        # S feeds the likelihood normalizer even when Sinv is given
        S = jnp.stack([einsum("mi,kij,nj->kmn", Ck.H, bank.P[k], Ck.H)
                       + Ck.R
                       for k, Ck in enumerate(consts)])
    if Sinv is None:
        Sinv = small_inv(S, m)
    if cbar is None:
        cbar = matmul(bank.mu, jnp.asarray(imm.trans, dtype))
    has_z = assoc >= 0
    zk = z[jnp.clip(assoc, 0, z.shape[0] - 1)]  # (C, m), garbage where -1
    x_new, P_new, loglik = [], [], []
    for k, model in enumerate(imm.models):
        xk, Pk = _kalman_update_lanes(model, bank.x[k], bank.P[k], zk,
                                      PHt[k], Sinv[k], dtype)
        x_new.append(xk)
        P_new.append(Pk)
        y = zk - z_pred[k]
        loglik.append(gaussian_loglik(y, Sinv[k],
                                      jnp.log(small_det(S[k], m)), m))
    x_new, P_new = jnp.stack(x_new), jnp.stack(P_new)
    mu_post = imm_mode_posterior(cbar, jnp.stack(loglik))

    upd = has_z & bank.active
    x_out = jnp.where(upd[None, :, None], x_new, bank.x)
    P_out = jnp.where(upd[None, :, None, None], P_new, bank.P)
    mu_out = jnp.where(upd[:, None], mu_post, cbar)
    hits, misses, age = lifecycle_counters(bank, assoc)
    return bank._replace(x=x_out, P=P_out, mu=mu_out, hits=hits,
                         misses=misses, age=age)


def replay_imm_bank(imm: IMMModel, bank: IMMBankState, zs, valid=None,
                    **kw):
    """Re-filter a pre-associated (T, C, m) measurement stream seeded
    from the live bank's mode-conditioned state — one fused IMM scan
    dispatch per time chunk (the ``imm_scan`` stage), with x/P and the
    mode probabilities kernel-resident across frames.

    ``valid`` is an optional (T, C) mask: False frames coast a slot
    (time update only, mu <- cbar), mirroring how ``update_imm_bank``
    treats an unassociated slot. Returns the (T, C, n) moment-matched
    combined estimates; pass ``return_final=True`` through ``kw`` to
    also get the final (x, P, mu) for reseeding a bank. The live bank
    is not modified."""
    from repro.kernels.katana_bank.ops import katana_imm_sequence

    return katana_imm_sequence(imm, zs, bank.x, bank.P, mu0=bank.mu,
                               valid=valid, **kw)


def spawn_imm_tracks(imm: IMMModel, bank: IMMBankState, z: jnp.ndarray,
                     unassigned: jnp.ndarray,
                     dtype=jnp.float32) -> IMMBankState:
    """Open new tracks for unassigned measurements: every mode starts
    from the same measurement-seeded state, covariance P0 and the prior
    mode distribution ``imm.mu0``."""
    K = imm.K
    Cap = bank.x.shape[1]
    take, takes_any, free_rank = _spawn_plan(bank.active, unassigned)
    x_init = _spawn_init_state(imm.models[0], take, z, dtype)  # shared H
    P_init = jnp.broadcast_to(jnp.asarray(imm.P0, dtype),
                              (Cap, imm.n, imm.n))
    mu_init = jnp.broadcast_to(jnp.asarray(imm.mu0, dtype), (Cap, K))
    new_ids = bank.next_id + free_rank.astype(jnp.int32)
    return bank._replace(
        x=jnp.where(takes_any[None, :, None], x_init[None], bank.x),
        P=jnp.where(takes_any[None, :, None, None], P_init[None], bank.P),
        mu=jnp.where(takes_any[:, None], mu_init, bank.mu),
        active=bank.active | takes_any,
        hits=jnp.where(takes_any, 1, bank.hits),
        misses=jnp.where(takes_any, 0, bank.misses),
        age=jnp.where(takes_any, 0, bank.age),
        track_id=jnp.where(takes_any, new_ids, bank.track_id),
        next_id=bank.next_id + jnp.sum(takes_any.astype(jnp.int32)),
    )
