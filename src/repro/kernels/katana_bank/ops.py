"""jit'd wrappers for the katana_bank kernels: canonical (N, n) layout
in, lane-packed (n, N) SoA inside, padding N to the lane tile.

Dispatch granularities:
  ``katana_bank``          one predict+update per call (per-frame).
  ``katana_bank_sequence`` a whole (T, N, m) measurement stream in ONE
        pallas_call — the AoS->SoA transposes and lane padding are paid
        once per sequence instead of once per frame, and x/P stay
        kernel-resident across frames (the time loop is inside the
        kernel, see kernel.make_scan_kernel).
  ``katana_bank_imm``      one IMM multi-model predict+update+loglik
        per call: the K model hypotheses of N tracks flatten to K·N
        stacked lanes of a single padded dispatch (model-major); each
        lane's F/Q/R constants come from a host-folded per-lane table
        indexed inside the kernel (see kernel.plan_imm_tables).
  ``katana_imm_sequence``  the fused IMM fast path: a whole (T, N, m)
        stream through ONE pallas_call per time chunk — mixing, the K
        per-model predict+updates, the mode posterior and the combined
        estimate all run inside the kernel's time loop, so x/P AND mu
        stay kernel-resident across frames and the AoS->SoA packing is
        paid once per sequence (see kernel.make_imm_scan_kernel).
        Supports a per-frame validity mask (coasting frames).
  ``imm_bank_sequence``    the per-frame reference driver: a full IMM
        cycle per frame under one jitted lax.scan — mix ->
        katana_bank_imm -> mode posterior, with the mixing running
        between kernel dispatches. Kept as the independently-built
        equivalence oracle for ``katana_imm_sequence`` (both paths
        require linear member models for K > 1).
  ``katana_frame`` / ``katana_imm_frame``  the LIVE serving frame:
        predict + gated Mahalanobis cost + greedy assignment + update
        (IMM: + mixing, mode posterior, combined estimate) in ONE
        dispatch — what ``tracker.frame_step`` / ``imm_frame_step``
        route through under ``TrackerConfig.fused_frame``; only
        spawn/prune lifecycle bookkeeping stays in XLA.
  ``katana_greedy_assign`` the in-kernel assignment standalone, for
        equivalence testing against ``tracker.greedy_assign``.

Execution mode: every op's ``interpret`` parameter defaults to ``None``
= "the backend's mode" (``repro.execmode``): compiled on a TPU, with no
probe and no fallback — a kernel the TPU compiler refuses raises — and
the Pallas interpreter on CPU. ``interpret=True`` pins the interpreter
(CPU only: the kernel equivalence tests do), ``interpret=False`` pins
compilation (the compile rehearsals for a described TPU do). The raw
``kernel.py`` step functions below this layer take ``interpret`` as a
required keyword; ops is where policy is resolved.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import FilterModel, IMMModel
from repro.core.rewrites import imm_combine, imm_mix, imm_mode_posterior
from repro.execmode import resolve_interpret
from repro.kernels.katana_bank.kernel import (
    LANE_TILE,
    _selector_rows,
    greedy_assign_step,
    katana_bank_imm_scan_step,
    katana_bank_imm_step,
    katana_bank_scan_step,
    katana_bank_step,
    katana_frame_step,
    katana_imm_frame_step,
    plan_imm_tables,
    scan_time_chunk,
)

# one 128-lane f32 register width. The frame kernels run grid=(1,) over
# the whole bank, so their lane pad only needs to keep the minor axis
# register-friendly — 128, not the scan kernels' per-program LANE_TILE;
# it is also the least IMM scan track tile a TPU block accepts
FRAME_LANE_PAD = 128


def _pad_to(x, N_pad, axis=-1):
    pad = N_pad - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def katana_bank(model: FilterModel, x, P, z, lane_tile: int = LANE_TILE,
                symmetrize: bool = True,
                interpret: Optional[bool] = None):
    """Fused batched KF step.

    x: (N, n); P: (N, n, n); z: (N, m)  ->  (x', P') same shapes.
    ``interpret=None`` resolves from the active execution mode.
    """
    interpret = resolve_interpret(interpret)
    return _katana_bank(model, x, P, z, lane_tile=lane_tile,
                        symmetrize=symmetrize, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("model", "lane_tile", "symmetrize",
                                    "interpret"))
def _katana_bank(model: FilterModel, x, P, z, lane_tile: int,
                 symmetrize: bool, interpret: bool):
    N = x.shape[0]
    N_pad = -(-N // lane_tile) * lane_tile
    # AoS -> SoA (lanes-minor): one transpose outside the kernel; inside,
    # the whole recursion is lane-parallel.
    xs = _pad_to(x.T, N_pad)
    Ps = _pad_to(P.transpose(1, 2, 0), N_pad)
    zs = _pad_to(z.T, N_pad)
    x2, P2 = katana_bank_step(model, xs, Ps, zs, lane_tile=lane_tile,
                              symmetrize=symmetrize, interpret=interpret)
    return x2[:, :N].T, P2[:, :, :N].transpose(2, 0, 1)


def katana_bank_sequence(model: FilterModel, zs, x0, P0,
                         lane_tile: int = LANE_TILE,
                         symmetrize: bool = True,
                         interpret: Optional[bool] = None,
                         return_final: bool = False,
                         time_chunk: int = 0):
    """Fused multi-frame filter: one kernel dispatch per sequence.

    zs: (T, N, m); x0: (N, n); P0: (N, n, n)  ->  xs (T, N, n), the
    filtered state after every frame. With ``return_final=True`` also
    returns ``(x_T (N, n), P_T (N, n, n))`` for carrying the bank into
    the next sequence chunk.

    Layout work (lane padding + AoS->SoA transposes) happens ONCE here,
    not per frame; the kernel's fori_loop keeps x/P resident across all
    T steps of a dispatch. The scan kernel holds whole-T zs/xs blocks
    in VMEM, so streams longer than ``time_chunk`` frames run as
    ceil(T / time_chunk) dispatches with (x, P) carried between them —
    the bank still only round-trips HBM once per CHUNK, not per frame.
    ``time_chunk=0`` takes the longest chunk whose whole-T blocks fit
    the scoped VMEM (``kernel.scan_time_chunk``); ``interpret=None``
    resolves from the active execution mode.
    """
    interpret = resolve_interpret(interpret)
    time_chunk = time_chunk or scan_time_chunk(model.n, model.m, lane_tile)
    return _katana_bank_sequence(model, zs, x0, P0, lane_tile=lane_tile,
                                 symmetrize=symmetrize, interpret=interpret,
                                 return_final=return_final,
                                 time_chunk=time_chunk)


@functools.partial(jax.jit,
                   static_argnames=("model", "lane_tile", "symmetrize",
                                    "interpret", "return_final",
                                    "time_chunk"))
def _katana_bank_sequence(model: FilterModel, zs, x0, P0, lane_tile: int,
                          symmetrize: bool, interpret: bool,
                          return_final: bool, time_chunk: int):
    zs = jnp.asarray(zs)
    T, N, m = zs.shape
    N_pad = -(-N // lane_tile) * lane_tile
    xs_s = _pad_to(jnp.asarray(x0).T, N_pad)            # (n, N_pad)
    Ps_s = _pad_to(jnp.asarray(P0).transpose(1, 2, 0), N_pad)
    zs_s = _pad_to(zs.transpose(0, 2, 1), N_pad)        # (T, m, N_pad)
    chunks = []
    for t0 in range(0, T, time_chunk):
        xs, xs_s, Ps_s = katana_bank_scan_step(
            model, xs_s, Ps_s, zs_s[t0:t0 + time_chunk],
            lane_tile=lane_tile, symmetrize=symmetrize, interpret=interpret)
        chunks.append(xs)
    xs = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
    out = xs[:, :, :N].transpose(0, 2, 1)               # (T, N, n)
    if return_final:
        return out, (xs_s[:, :N].T, Ps_s[:, :, :N].transpose(2, 0, 1))
    return out


def katana_bank_soa(model: FilterModel, x, P, z, **kw):
    """SoA entry point for callers that keep the lane layout end-to-end
    (the serving engine's resident bank)."""
    kw.setdefault("interpret", resolve_interpret(None))
    return katana_bank_step(model, x, P, z, **kw)


def frame_kernel_supported(model) -> bool:
    """True when the fused frame kernel can serve this model: selector
    measurement matrix (every H row a unit vector), and — for a K>1
    IMM — linear member models (constant F tables). The tracker's
    ``fused_frame`` flag falls back to the einsum path when this is
    False, so a general-H or nonlinear-member configuration still
    tracks, just not in one dispatch."""
    if isinstance(model, IMMModel):
        return (_selector_rows(np.asarray(model.H)) is not None
                and (model.K == 1
                     or all(mdl.is_linear for mdl in model.models)))
    return _selector_rows(np.asarray(model.H)) is not None


def katana_frame(model: FilterModel, x, P, z, z_valid, active, gate: float,
                 rounds: int, symmetrize: bool = True,
                 interpret: Optional[bool] = None):
    """Fused live tracking frame: the whole measurement cycle of
    ``tracker.frame_step`` — predict, gate, greedy assignment, update —
    as ONE kernel dispatch.

    x: (C, n); P: (C, n, n); z: (M, m) padded measurements;
    z_valid: (M,) bool; active: (C,) bool; ``gate``/``rounds`` are the
    tracker's (static) chi-square gate and assignment-round bound.
    Returns (x' (C, n), P' (C, n, n), assoc (C,) int32) — predicted
    state where a slot got no measurement, updated where it did, and
    the per-slot measurement index (or -1), byte-identical semantics to
    the einsum path's ``greedy_assign``. Spawn/prune stay with the
    caller. Padding lanes ride along inactive (their zero P predicts to
    P̂ = Q, so S = Q[obs][obs] + R stays invertible) and are sliced
    off."""
    return _katana_frame(model, x, P, z, z_valid, active, gate=gate,
                         rounds=rounds, symmetrize=symmetrize,
                         interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("model", "gate", "rounds", "symmetrize",
                                    "interpret"))
def _katana_frame(model: FilterModel, x, P, z, z_valid, active, gate: float,
                  rounds: int, symmetrize: bool, interpret: bool):
    C = x.shape[0]
    C_pad = -(-C // FRAME_LANE_PAD) * FRAME_LANE_PAD
    xs = _pad_to(x.T, C_pad)
    Ps = _pad_to(P.transpose(1, 2, 0), C_pad)
    act = _pad_to(active.astype(x.dtype)[None, :], C_pad)
    zs = z.T                                           # (m, M)
    zv = z_valid.astype(x.dtype)[None, :]
    x2, P2, assoc = katana_frame_step(model, xs, Ps, zs, zv, act,
                                      gate=gate, rounds=rounds,
                                      symmetrize=symmetrize,
                                      interpret=interpret)
    return (x2[:, :C].T, P2[:, :, :C].transpose(2, 0, 1), assoc[0, :C])


def katana_imm_frame(imm: IMMModel, x, P, mu, z, z_valid, active,
                     gate: float, rounds: int, symmetrize: bool = True,
                     interpret: Optional[bool] = None):
    """Fused live IMM tracking frame (the multi-model ``katana_frame``):
    mixing, K model-conditioned predicts, the cbar-weighted gate, greedy
    assignment, K updates + log-likelihoods, mode posterior and the
    moment-matched combined estimate in ONE dispatch.

    x: (K, C, n); P: (K, C, n, n); mu: (C, K); z: (M, m);
    z_valid: (M,) bool; active: (C,) bool. Returns (x' (K, C, n),
    P' (K, C, n, n), mu' (C, K), x_c (C, n) combined estimates,
    assoc (C,) int32). Coasting slots keep the predicted x̂/P̂ and the
    Markov-predicted cbar, exactly ``bank.update_imm_bank``; spawn and
    prune stay with the caller (``tracker.imm_frame_step``). Padding
    lanes get a uniform mode distribution so their (discarded)
    posterior algebra stays finite."""
    return _katana_imm_frame(imm, x, P, mu, z, z_valid, active, gate=gate,
                             rounds=rounds, symmetrize=symmetrize,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("imm", "gate", "rounds", "symmetrize",
                                    "interpret"))
def _katana_imm_frame(imm: IMMModel, x, P, mu, z, z_valid, active,
                      gate: float, rounds: int, symmetrize: bool,
                      interpret: bool):
    K, C, n = x.shape
    C_pad = -(-C // FRAME_LANE_PAD) * FRAME_LANE_PAD
    xs = _pad_to(x.transpose(0, 2, 1), C_pad)          # (K, n, C_pad)
    Ps = _pad_to(P.transpose(0, 2, 3, 1), C_pad)       # (K, n, n, C_pad)
    mu_s = jnp.pad(mu.T, ((0, 0), (0, C_pad - C)),
                   constant_values=1.0 / K)            # (K, C_pad)
    act = _pad_to(active.astype(x.dtype)[None, :], C_pad)
    zs = z.T                                           # (m, M)
    zv = z_valid.astype(x.dtype)[None, :]
    x2, P2, mu2, xc, assoc = katana_imm_frame_step(
        imm, xs, Ps, mu_s, zs, zv, act, gate=gate, rounds=rounds,
        symmetrize=symmetrize, interpret=interpret)
    return (x2[:, :, :C].transpose(0, 2, 1),
            P2[:, :, :, :C].transpose(0, 3, 1, 2),
            mu2[:, :C].T, xc[:, :C].T, assoc[0, :C])


def katana_greedy_assign(cost, valid, gate: float, rounds: int,
                         interpret: Optional[bool] = None):
    """The frame kernels' in-kernel greedy assignment as a standalone
    dispatch, canonical (C, M) layout — the direct test surface for
    equivalence with ``tracker.greedy_assign``. cost: (C, M);
    valid: (C, M) bool. Returns assoc (C,) int32."""
    return _katana_greedy_assign(cost, valid, gate=gate, rounds=rounds,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("gate", "rounds", "interpret"))
def _katana_greedy_assign(cost, valid, gate: float, rounds: int,
                          interpret: bool):
    C, M = cost.shape
    assoc = greedy_assign_step(cost.T, valid.astype(cost.dtype).T,
                               gate=gate, rounds=rounds,
                               interpret=interpret)
    return assoc[0, :C]


def _imm_lane_table(imm: IMMModel, N: int, L_pad: int,
                    dtype=np.float32) -> np.ndarray:
    """(E, L_pad) host-folded varying-constant table for the model-major
    lane layout: plan_imm_tables' per-model values contracted with the
    (static) one-hot model masks in numpy at trace time — the kernel's
    per-lane "model index" is a finished constant before dispatch.
    Padding lanes get model 0's values so their (discarded) algebra
    stays finite — zeros would fold S to 0 and the emitted 1/det to
    inf."""
    K = imm.K
    _, V = plan_imm_tables(imm.models)  # (E, K)
    sel = np.zeros((K, L_pad), np.float64)
    for k in range(K):
        sel[k, k * N:(k + 1) * N] = 1.0
    sel[0, K * N:] = 1.0
    return (V @ sel).astype(dtype)


def katana_bank_imm(imm: IMMModel, x, P, z, lane_tile: int = LANE_TILE,
                    symmetrize: bool = True,
                    interpret: Optional[bool] = None):
    """Fused multi-model (IMM) KF step + measurement log-likelihoods.

    x: (K, N, n) model-conditioned means (typically the IMM-mixed
    states); P: (K, N, n, n); z: (N, m) — every model sees the same
    measurement. Returns (x' (K, N, n), P' (K, N, n, n),
    loglik (K, N)).

    The (model, track) product flattens model-major onto the lane axis
    — K·N lanes, padded to the lane tile — so K hypotheses cost one
    kernel dispatch, exactly like K·N plain filters (paper §IV-D's
    batching argument applied to the model index).
    """
    interpret = resolve_interpret(interpret)
    return _katana_bank_imm(imm, x, P, z, lane_tile=lane_tile,
                            symmetrize=symmetrize, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("imm", "lane_tile", "symmetrize",
                                    "interpret"))
def _katana_bank_imm(imm: IMMModel, x, P, z, lane_tile: int,
                     symmetrize: bool, interpret: bool):
    K, N, n = x.shape
    m = z.shape[-1]
    L = K * N
    L_pad = -(-L // lane_tile) * lane_tile
    xs = _pad_to(x.reshape(L, n).T, L_pad)
    Ps = _pad_to(P.reshape(L, n, n).transpose(1, 2, 0), L_pad)
    zs = _pad_to(jnp.tile(z, (K, 1)).T, L_pad)
    tab = jnp.asarray(_imm_lane_table(imm, N, L_pad, dtype=x.dtype))
    x2, P2, ll = katana_bank_imm_step(imm, xs, Ps, zs, tab,
                                      lane_tile=lane_tile,
                                      symmetrize=symmetrize,
                                      interpret=interpret)
    return (x2[:, :L].T.reshape(K, N, n),
            P2[:, :, :L].transpose(2, 0, 1).reshape(K, N, n, n),
            ll[0, :L].reshape(K, N))


def imm_track_tile(K: int) -> int:
    """Default tracks per IMM scan program: the largest power of two
    <= LANE_TILE // K (a power of two even when K doesn't divide the
    lane tile: K=3 would otherwise give an 85-wide block), but never
    below one 128-lane register width — a TPU block's minor dim must be
    a multiple of 128."""
    return max(FRAME_LANE_PAD, 1 << (LANE_TILE // K).bit_length() - 1)


def katana_imm_sequence(imm: IMMModel, zs, x0, P0, mu0=None, valid=None,
                        lane_tile: int = 0, symmetrize: bool = True,
                        interpret: Optional[bool] = None,
                        return_final: bool = False,
                        time_chunk: int = 64):
    """Fused IMM filtering of a (T, N, m) measurement stream: ONE kernel
    dispatch per time chunk (the ``imm_scan`` stage fast path).

    zs: (T, N, m). x0/P0 seed the bank: (N, n)/(N, n, n) seeds every
    mode identically (fresh tracks), or (K, N, n)/(K, N, n, n) resumes a
    mode-conditioned bank (e.g. a live ``IMMBankState``). mu0: (N, K)
    initial mode probabilities (defaults to ``imm.mu0``). valid:
    optional (T, N) boolean/0-1 mask — a False frame coasts that track
    (time update only, mu <- the Markov-predicted cbar), the tracker's
    no-measurement semantics. Returns xs (T, N, n) moment-matched
    combined estimates; with ``return_final=True`` also
    ``(x (K, N, n), P (K, N, n, n), mu (N, K))`` for chunked streaming.

    ``lane_tile`` here counts TRACKS per program (each program holds all
    K model slabs of its tracks, K·lane_tile lanes); the default 0
    takes ``imm_track_tile`` (256 at K=1, 128 for K>1). The
    ``time_chunk`` default (64) bounds the per-program VMEM instead: at
    K=4, cv-9 and 128 tracks the streamed zs/xs/valid blocks take
    64 · (8 + 16 + 8) rows · 128 · 4 B · 2 buffers = 2 MiB, the x/P/mu
    state blocks about 1.3 MiB, well inside the 16 MiB scoped VMEM.

    Unlike ``imm_bank_sequence`` (one katana_bank_imm dispatch plus XLA
    mixing per frame), the mixing and mode-posterior algebra run INSIDE
    the scan kernel between the update of frame t and the predict of
    frame t+1: x, P and the mode probabilities are kernel-resident for
    a whole chunk, and the lane padding + AoS->SoA transposes are paid
    once per sequence. K=1 reduces exactly to ``katana_bank_sequence``.
    """
    interpret = resolve_interpret(interpret)
    lane_tile = lane_tile or imm_track_tile(imm.K)
    return _katana_imm_sequence(imm, zs, x0, P0, mu0, valid,
                                lane_tile=lane_tile, symmetrize=symmetrize,
                                interpret=interpret,
                                return_final=return_final,
                                time_chunk=time_chunk)


@functools.partial(jax.jit,
                   static_argnames=("imm", "lane_tile", "symmetrize",
                                    "interpret", "return_final",
                                    "time_chunk"))
def _katana_imm_sequence(imm: IMMModel, zs, x0, P0, mu0, valid,
                         lane_tile: int, symmetrize: bool, interpret: bool,
                         return_final: bool, time_chunk: int):
    zs = jnp.asarray(zs)
    T, N, m = zs.shape
    K, n = imm.K, imm.n
    x0 = jnp.asarray(x0)
    P0 = jnp.asarray(P0)
    if x0.ndim == 2:
        x0 = jnp.broadcast_to(x0[None], (K, N, n))
    if P0.ndim == 3:
        P0 = jnp.broadcast_to(P0[None], (K, N, n, n))
    mu = (jnp.broadcast_to(jnp.asarray(imm.mu0, zs.dtype), (N, K))
          if mu0 is None else jnp.asarray(mu0))
    N_pad = -(-N // lane_tile) * lane_tile
    xs_s = _pad_to(x0.transpose(0, 2, 1), N_pad)        # (K, n, N_pad)
    Ps_s = _pad_to(P0.transpose(0, 2, 3, 1), N_pad)     # (K, n, n, N_pad)
    # padding lanes get a uniform mode distribution so their (discarded)
    # posterior algebra stays finite — all-zero mu would make the
    # normalizing 1/sum(w) emit inf
    mu_s = jnp.pad(mu.T, ((0, 0), (0, N_pad - N)),
                   constant_values=1.0 / K)              # (K, N_pad)
    if valid is not None:
        # invalid frames never contribute (the kernel selects the
        # prediction), but their z still flows through the emitted
        # update before the select — zero it so a NaN-encoded "no
        # detection" in a replay log cannot poison the carry via 0·NaN
        zs = jnp.where(jnp.asarray(valid, bool)[:, :, None], zs, 0.0)
        # the validity rides along as one more measurement row
        zs = jnp.concatenate(
            [zs, jnp.asarray(valid, zs.dtype)[:, :, None]], axis=2)
    zs_s = _pad_to(zs.transpose(0, 2, 1), N_pad)        # (T, m [+1], N_pad)
    chunks = []
    for t0 in range(0, T, time_chunk):
        xs, xs_s, Ps_s, mu_s = katana_bank_imm_scan_step(
            imm, xs_s, Ps_s, mu_s, zs_s[t0:t0 + time_chunk],
            lane_tile=lane_tile, symmetrize=symmetrize,
            with_valid=valid is not None, interpret=interpret)
        chunks.append(xs)
    xs = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
    out = xs[:, :, :N].transpose(0, 2, 1)               # (T, N, n)
    if return_final:
        return out, (xs_s[:, :, :N].transpose(0, 2, 1),
                     Ps_s[:, :, :, :N].transpose(0, 3, 1, 2),
                     mu_s[:, :N].T)
    return out


def imm_bank_sequence(imm: IMMModel, zs, x0, P0, mu0=None,
                      lane_tile: int = LANE_TILE, symmetrize: bool = True,
                      interpret: Optional[bool] = None,
                      return_final: bool = False):
    """IMM-filter a (T, N, m) measurement stream: one jitted lax.scan,
    one fused multi-model kernel dispatch per frame.

    zs: (T, N, m); x0: (N, n); P0: (N, n, n) seed every mode
    identically; mu0: (N, K) initial mode probabilities (defaults to
    ``imm.mu0``). Returns xs (T, N, n) — the moment-matched combined
    estimate after every frame. With ``return_final=True`` also returns
    ``(x (K, N, n), P (K, N, n, n), mu (N, K))`` for chunked streaming.

    Per frame: IMM mixing (einsum algebra from ``repro.core.rewrites``)
    -> ``katana_bank_imm`` (predict+update+loglik, stacked lanes) ->
    mode posterior from the kernel's log-likelihoods. Mixing between
    dispatches means x/P round-trip HBM (and the packing is re-paid)
    every frame — ``katana_imm_sequence`` is the fused fast path; this
    driver remains as its independently-built equivalence oracle.
    """
    interpret = resolve_interpret(interpret)
    return _imm_bank_sequence(imm, zs, x0, P0, mu0, lane_tile=lane_tile,
                              symmetrize=symmetrize, interpret=interpret,
                              return_final=return_final)


@functools.partial(jax.jit,
                   static_argnames=("imm", "lane_tile", "symmetrize",
                                    "interpret", "return_final"))
def _imm_bank_sequence(imm: IMMModel, zs, x0, P0, mu0, lane_tile: int,
                       symmetrize: bool, interpret: bool,
                       return_final: bool):
    zs = jnp.asarray(zs)
    T, N, m = zs.shape
    K, n = imm.K, imm.n
    x = jnp.broadcast_to(jnp.asarray(x0)[None], (K, N, n))
    P = jnp.broadcast_to(jnp.asarray(P0)[None], (K, N, n, n))
    mu = (jnp.broadcast_to(jnp.asarray(imm.mu0, zs.dtype), (N, K))
          if mu0 is None else jnp.asarray(mu0))
    Pi = jnp.asarray(imm.trans, zs.dtype)

    def body(carry, z_t):
        x, P, mu = carry
        x_mix, P_mix, cbar = imm_mix(x, P, mu, Pi)
        x_new, P_new, ll = katana_bank_imm(imm, x_mix, P_mix, z_t,
                                           lane_tile=lane_tile,
                                           symmetrize=symmetrize,
                                           interpret=interpret)
        mu_new = imm_mode_posterior(cbar, ll)
        x_c, _ = imm_combine(x_new, P_new, mu_new)
        return (x_new, P_new, mu_new), x_c

    (x, P, mu), xs_out = jax.lax.scan(body, (x, P, mu), zs)
    if return_final:
        return xs_out, (x, P, mu)
    return xs_out
