"""katana_bank: fused batched Kalman predict+update Pallas TPU kernel.

This is the TPU-native realization of KATANA's three rewrites (paper
§IV-B/C/D; see docs/paper_mapping.md for the equation-level map):

  Opt-1 (subtract elimination)  -> signs folded into trace-time Python
        constants; the emitted op stream is mul/add only.
  Opt-2 (static fusion)         -> the ENTIRE predict+update recursion
        is one kernel: state x, covariance P, and every intermediate
        live in VMEM/VREGs for the whole step; zero HBM round-trips
        between ops (the TPU analogue of zero DPU<->DSP switches).
  Opt-3 (batching)              -> the filter index N lives on the
        128-lane minor axis ("lane packing"): every per-filter scalar
        in the n x n algebra is an (8,128)-vector op across 128+
        filters. No (N·n)x(N·n) block-diagonal expansion — the N^2
        FLOP blow-up of the paper's NPU formulation disappears.

Beyond the paper, the kernel exploits filter STRUCTURE the NPU's
GEMM-only pipeline could not:
  * selector measurement matrices (H rows are unit vectors, true for
    both paper workloads) turn H P H^T into a covariance row/col
    selection — no GEMM at all;
  * the CTRA Jacobian's sparsity (7 off-identity entries) makes
    F P F^T cost O(nnz·n) lane-ops instead of n^3.

Six kernel shapes share the same emitted step math:

  ``make_kernel``       one predict+update per pallas_call (the
        original per-frame dispatch, still used for single-frame
        serving).
  ``make_scan_kernel``  a (T, m, lane_tile) measurement stream in one
        pallas_call: fori_loop over T inside the kernel body with x and
        P carried in VMEM/VREGs across frames — the sequence-level
        extension of Opt-2. The covariance bank never round-trips
        through HBM between frames. Note the measurement/output blocks
        are whole-T VMEM blocks, so T is VMEM-bounded on real hardware;
        ``ops.katana_bank_sequence`` chunks long streams over
        ``time_chunk``-sized dispatches, carrying (x, P) between them.
  ``make_imm_kernel``   the IMM multi-model step: K motion hypotheses
        run as stacked lanes of one padded bank. Per-model constant
        tables (F, Q, R) are indexed inside the kernel: entries shared
        by every model stay trace-time Python floats (fully folded,
        zeros pruned), and the entries that differ are folded against
        the static model->lane layout ON THE HOST (``plan_imm_tables``)
        into one (E, lane) table input — inside the kernel a per-model
        entry is a single table-row read, so the model "index" costs
        zero arithmetic and the emitted stream stays pure mul/add on
        the matrix path. The kernel additionally emits the per-lane
        measurement log-likelihood from the SAME cofactor S^{-1} it
        computed for the Kalman gain (plus a closed-form determinant) —
        the IMM mode-probability update never inverts anything outside
        the kernel.
  ``make_imm_scan_kernel``  the sequence-level IMM: mixing, the K
        per-model predict+updates, the mode posterior AND the
        moment-matched combination all inside one fori_loop over T —
        a whole K-hypothesis IMM stream is ONE dispatch, with x/P/mu
        VMEM-resident across frames. Every state entry of a program's
        block is a (K, tt) model-major slab (row k = model k's
        hypotheses of the tile's tracks), so mixing reaches across
        models with static row reads; shared F/Q/R entries and the
        (K, K) Markov transition matrix fold to trace-time Python
        floats, model-varying entries to loop-invariant slabs.
  ``make_frame_kernel`` / ``make_imm_frame_kernel``  the LIVE serving
        frame: predict, innovation + cofactor S^{-1}, the gated
        Mahalanobis cost tile, the greedy assignment (wave-scheduled
        masked argmins over the (M, C) tile, exact vs the sequential
        reference) and the measurement update of the assigned lanes
        (IMM: + mixing, per-lane log-likelihood, mode posterior and
        the moment-matched combined estimate) — the entire closed-loop
        measurement cycle of ``tracker.frame_step`` in ONE dispatch,
        with only spawn/prune lifecycle bookkeeping left in XLA. The
        assignment is a global argmin, so these kernels run grid=(1,)
        over the whole bank instead of tiling the lane axis.

Layout: struct-of-arrays, lanes-minor —
  x (n, N), P (n, n, N), z (m, N) / zs (T, m, N); grid tiles N by
  ``lane_tile``. For the per-frame IMM kernel the lane axis is the
  flattened (model, track) product, model-major across the whole bank;
  the IMM scan and IMM frame kernels carry the model index as a leading
  block axis and keep it there in-kernel: (K, tt) slabs, never a rank-1
  (K·tt,) flattening (a shape cast Mosaic cannot lay out on TPU).
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.filters import FilterModel

LANE_TILE = 256  # filters per program: 2 f32 lane-groups

# Scoped VMEM a Mosaic kernel may allocate by default (16 MiB on TPU
# v5e). The scan kernels' whole-chunk blocks are sized against it.
SCOPED_VMEM_BYTES = 16 * 2**20


def _pad8(d: int) -> int:
    return -(-d // 8) * 8


def scan_time_chunk(n: int, m: int, lane_tile: int) -> int:
    """Frames per scan dispatch whose whole-chunk blocks fit in VMEM.

    A scan program holds its (T, m, lane_tile) measurement block and
    its (T, n, lane_tile) output block in VMEM as f32, each double-
    buffered, the two minor dims tiled (8, 128):
        T · (pad8(m) + pad8(n)) · lane_tile · 4 B · 2 buffers.
    Three quarters of the scoped VMEM go to these blocks; the rest
    holds the x/P state blocks and the compiler's scratch. LKF cv-6
    and EKF ctra-8 at lane_tile 256 stream 16 rows · 256 · 8 B = 32 KiB
    per frame, so 384 frames (12 MiB) per dispatch; cv-9 streams 24
    rows, 256 frames."""
    per_frame = (_pad8(m) + _pad8(n)) * lane_tile * 4 * 2
    return max(1, SCOPED_VMEM_BYTES * 3 // 4 // per_frame)


def _selector_rows(H: np.ndarray) -> Optional[List[int]]:
    """If every row of H is a unit vector, return the observed indices."""
    rows = []
    for r in H:
        nz = np.nonzero(r)[0]
        if len(nz) != 1 or abs(r[nz[0]] - 1.0) > 1e-12:
            return None
        rows.append(int(nz[0]))
    return rows


def _mat_from_np(A: np.ndarray):
    """Dense constant matrix -> python list-of-lists of floats (0 pruned
    at emit time)."""
    return [[float(v) for v in row] for row in A]


def _is_zero(v) -> bool:
    return isinstance(v, float) and v == 0.0


def _bc(v, lane):
    """Broadcast a constant-folded entry to a full lane vector at a
    store/stack boundary: python floats (all-zero F rows — e.g. the
    CV9/CT9 IMM models forget their acceleration states — can fold a
    whole entry away) and any under-broadcast array a folded entry
    left behind (shape-mismatched entries would break the fori_loop
    carry structure)."""
    if isinstance(v, (int, float)):
        return jnp.full_like(lane, v)
    return v if v.shape == lane.shape else jnp.broadcast_to(v, lane.shape)


def _emit_dot(row_consts, vec, n):
    """sum_k row[k] * vec[k] with float/lane-vector entries on either
    side; zero terms pruned, 1.0 coefficients elided. Returns 0.0 when
    the whole row folds away."""
    acc = None
    for k in range(n):
        f = row_consts[k]
        if _is_zero(f) or _is_zero(vec[k]):
            continue
        if isinstance(f, float):
            term = vec[k] if f == 1.0 else f * vec[k]
        else:
            term = f * vec[k]
        acc = term if acc is None else acc + term
    return 0.0 if acc is None else acc


def _emit_matvec(F, xv, n):
    """x' = F x on mixed float/lane-vector entries."""
    return [_emit_dot(F[i], xv, n) for i in range(n)]


def _emit_FP(F, P, n):
    """FP = F · P on mixed float/lane-vector entries (zeros pruned) —
    the shared first half of both F P Fᵀ emit paths."""
    return [[_emit_dot(F[i], [P[k][j] for k in range(n)], n)
             for j in range(n)] for i in range(n)]


def _emit_FPFt(F, P, n):
    """P' = F P F^T with F a list-of-lists whose entries are python
    floats (constants) or lane vectors (jnp arrays); zeros pruned."""
    FP = _emit_FP(F, P, n)
    return [[_emit_dot(F[j], FP[i], n) for j in range(n)] for i in range(n)]


def _emit_small_inv(S, m):
    """Cofactor inverse of an m x m matrix of lane vectors (m <= 4)."""
    if m == 1:
        return [[1.0 / S[0][0]]]
    if m == 2:
        det = S[0][0] * S[1][1] - S[0][1] * S[1][0]
        r = 1.0 / det
        return [[S[1][1] * r, -S[0][1] * r], [-S[1][0] * r, S[0][0] * r]]
    if m == 3:
        c00 = S[1][1] * S[2][2] - S[1][2] * S[2][1]
        c01 = S[1][2] * S[2][0] - S[1][0] * S[2][2]
        c02 = S[1][0] * S[2][1] - S[1][1] * S[2][0]
        c10 = S[0][2] * S[2][1] - S[0][1] * S[2][2]
        c11 = S[0][0] * S[2][2] - S[0][2] * S[2][0]
        c12 = S[0][1] * S[2][0] - S[0][0] * S[2][1]
        c20 = S[0][1] * S[1][2] - S[0][2] * S[1][1]
        c21 = S[0][2] * S[1][0] - S[0][0] * S[1][2]
        c22 = S[0][0] * S[1][1] - S[0][1] * S[1][0]
        r = 1.0 / (S[0][0] * c00 + S[0][1] * c01 + S[0][2] * c02)
        return [[c00 * r, c10 * r, c20 * r],
                [c01 * r, c11 * r, c21 * r],
                [c02 * r, c12 * r, c22 * r]]
    if m == 4:
        # Schur on 2x2 blocks, all lane ops
        A = [[S[i][j] for j in range(2)] for i in range(2)]
        B = [[S[i][j + 2] for j in range(2)] for i in range(2)]
        C = [[S[i + 2][j] for j in range(2)] for i in range(2)]
        D = [[S[i + 2][j + 2] for j in range(2)] for i in range(2)]

        def mul2(X, Y):
            return [[X[i][0] * Y[0][j] + X[i][1] * Y[1][j]
                     for j in range(2)] for i in range(2)]

        def sub2(X, Y):
            return [[X[i][j] - Y[i][j] for j in range(2)] for i in range(2)]

        Di = _emit_small_inv(D, 2)
        BDi = mul2(B, Di)
        Si = _emit_small_inv(sub2(A, mul2(BDi, C)), 2)
        DiC = mul2(Di, C)
        TL = Si
        TR = [[-(Si[i][0] * BDi[0][j] + Si[i][1] * BDi[1][j])
               for j in range(2)] for i in range(2)]
        BL = [[-(DiC[i][0] * Si[0][j] + DiC[i][1] * Si[1][j])
               for j in range(2)] for i in range(2)]
        BDiT = mul2(DiC, [[-TR[0][0], -TR[0][1]], [-TR[1][0], -TR[1][1]]])
        BR = [[Di[i][j] + BDiT[i][j] for j in range(2)] for i in range(2)]
        out = [[None] * 4 for _ in range(4)]
        for i in range(2):
            for j in range(2):
                out[i][j] = TL[i][j]
                out[i][j + 2] = TR[i][j]
                out[i + 2][j] = BL[i][j]
                out[i + 2][j + 2] = BR[i][j]
        return out
    raise NotImplementedError(m)


def _emit_det(S, m):
    """Closed-form determinant of an m x m matrix of lane vectors
    (m <= 4) — cofactor expansion, pure mul/add. Feeds the Gaussian
    normalizer of the IMM mode likelihood; the Mahalanobis part reuses
    the S^{-1} already emitted for the Kalman gain, so the likelihood
    adds zero inversions."""
    if m == 1:
        return S[0][0]
    if m == 2:
        return S[0][0] * S[1][1] - S[0][1] * S[1][0]
    if m == 3:
        return (S[0][0] * (S[1][1] * S[2][2] - S[1][2] * S[2][1])
                + S[0][1] * (S[1][2] * S[2][0] - S[1][0] * S[2][2])
                + S[0][2] * (S[1][0] * S[2][1] - S[1][1] * S[2][0]))
    if m == 4:
        # det = det(D) * det(A - B D^{-1} C), 2x2 blocks (Schur)
        A = [[S[i][j] for j in range(2)] for i in range(2)]
        B = [[S[i][j + 2] for j in range(2)] for i in range(2)]
        C = [[S[i + 2][j] for j in range(2)] for i in range(2)]
        D = [[S[i + 2][j + 2] for j in range(2)] for i in range(2)]
        Di = _emit_small_inv(D, 2)
        BDi = [[B[i][0] * Di[0][j] + B[i][1] * Di[1][j]
                for j in range(2)] for i in range(2)]
        Sc = [[A[i][j] - (BDi[i][0] * C[0][j] + BDi[i][1] * C[1][j])
               for j in range(2)] for i in range(2)]
        return _emit_det(D, 2) * _emit_det(Sc, 2)
    raise NotImplementedError(m)


def plan_imm_tables(models):
    """Fold the per-model F/Q/R constant tables for the stacked-lane IMM
    kernel.

    Entries every model agrees on stay trace-time Python floats (fully
    constant-folded, zeros pruned downstream — identical to the
    single-model emit). Entries that differ get a row in the varying-
    entry value matrix V (E, K): ops.py contracts V with the static
    one-hot model-lane masks ON THE HOST, so the kernel receives one
    (E, lane) table input and each varying entry is a single table-row
    read — the per-lane model "indexing" costs zero arithmetic inside
    the kernel (§IV-C constant folding, applied across models).

    Returns (entries, V) where entries[name][i][j] is a float or
    ("var", e) referencing row e of V.
    """
    entries = {}
    vals: List[np.ndarray] = []
    for name in ("F", "Q", "R"):
        Ms = [np.asarray(getattr(mdl, name), np.float64) for mdl in models]
        a, b = Ms[0].shape
        tabl = [[None] * b for _ in range(a)]
        for i in range(a):
            for j in range(b):
                vs = [float(M[i, j]) for M in Ms]
                if all(v == vs[0] for v in vs):
                    tabl[i][j] = vs[0]
                else:
                    tabl[i][j] = ("var", len(vals))
                    vals.append(np.array(vs))
        entries[name] = tabl
    V = np.zeros((max(1, len(vals)), len(models)))  # E >= 1: dummy row
    for e, v in enumerate(vals):                    # keeps BlockSpecs static
        V[e] = v
    return entries, V


def _resolve_mat(tabl, tab):
    """Planned entry table -> float / lane-vector table, reading varying
    entries out of the kernel's (E, lane) table input."""
    return [[cell if isinstance(cell, float) else tab[cell[1]]
             for cell in row] for row in tabl]


_LOG_2PI = float(np.log(2.0 * np.pi))


def _emit_add_Q(Pp, Q, n):
    """P̂ += Q on mixed float/lane entries (zeros pruned)."""
    for i in range(n):
        for j in range(n):
            if not _is_zero(Q[i][j]):
                Pp[i][j] = Pp[i][j] + Q[i][j]
    return Pp


def _emit_predict_cov(F, P, Q, n, sym):
    """P̂ = F P Fᵀ + Q. With ``sym`` (the symmetrize=True contract) only
    the upper triangle is emitted and the mirror entries alias it —
    exact for symmetric P (covariance propagation is symmetric in exact
    arithmetic), and it cuts the dominant n² cost of the step to
    n(n+1)/2 while enforcing symmetry for free (no averaging pass)."""
    if not sym:
        return _emit_add_Q(_emit_FPFt(F, P, n), Q, n)
    FP = _emit_FP(F, P, n)
    Pp = [[None] * n for _ in range(n)]
    for i in range(n):
        for j in range(i, n):
            v = _emit_dot(F[j], FP[i], n)
            if not _is_zero(Q[i][j]):
                v = v + Q[i][j]
            Pp[i][j] = Pp[j][i] = v
    return Pp


def _emit_innovation(Pp, R, obs, n, m):
    """Innovation quantities from the predicted covariance, on lane
    vectors: S = P̂[obs][obs] + R (pure selection for selector H — no
    GEMM), its cofactor inverse, and P̂·Hᵀ (a column selection).
    Returns (S, Sinv, PHt). Split out of ``_emit_update`` so the fused
    frame kernel can aim the SAME S^{-1} at the gating cost tile and
    the Kalman gain — one cofactor inversion per (model, frame), the
    tracker's single-pass discipline emitted in-kernel."""
    S = [[Pp[obs[r]][obs[c]] + R[r][c] if not _is_zero(R[r][c])
          else Pp[obs[r]][obs[c]] for c in range(m)] for r in range(m)]
    PHt = [[Pp[i][obs[r]] for r in range(m)] for i in range(n)]
    Sinv = _emit_small_inv(S, m)
    return S, Sinv, PHt


def _emit_update(xp, Pp, z, R, obs, n, m, symmetrize, with_loglik,
                 inno=None):
    """The fused measurement update on lane vectors (paper §IV-B/C):
    subtract-free innovation (sign folded at trace time), selector-H
    covariance selection instead of H P Hᵀ GEMMs, cofactor S^{-1}.
    Under ``symmetrize`` the posterior covariance is emitted
    upper-triangle-only with aliased mirrors (exact symmetry, ~half the
    covariance-update ops).

    With ``with_loglik`` also emits log N(y; 0, S) per lane from the
    same S^{-1} (+ a closed-form det) — the IMM mode likelihood.
    ``inno`` passes through precomputed ``_emit_innovation`` results
    (the frame kernels, whose gating already paid for them).
    """
    # y = z + H_neg x̂  (Opt-1: sign folded at trace time)
    y = [z[r] - xp[obs[r]] for r in range(m)]
    S, Sinv, PHt = (inno if inno is not None
                    else _emit_innovation(Pp, R, obs, n, m))
    K = [[None] * m for _ in range(n)]
    for i in range(n):
        for r in range(m):
            acc = None
            for c in range(m):
                t = PHt[i][c] * Sinv[c][r]
                acc = t if acc is None else acc + t
            K[i][r] = acc
    # x' = x̂ + K y
    xn = []
    for i in range(n):
        acc = xp[i]
        for r in range(m):
            acc = acc + K[i][r] * y[r]
        xn.append(acc)
    # P' = P̂ + K (H_neg P̂) = P̂ - K P̂[obs, :]
    Pn = [[None] * n for _ in range(n)]
    for i in range(n):
        cols = range(i, n) if symmetrize else range(n)
        for j in cols:
            acc = Pp[i][j]
            for r in range(m):
                acc = acc - K[i][r] * Pp[obs[r]][j]
            Pn[i][j] = acc
            if symmetrize:
                Pn[j][i] = acc  # exact symmetry by aliasing, no averaging
    if not with_loglik:
        return xn, Pn
    # Mahalanobis distance via the S^{-1} above — no second inversion
    d = None
    for r in range(m):
        Sy = None
        for c in range(m):
            t = Sinv[r][c] * y[c]
            Sy = t if Sy is None else Sy + t
        t = y[r] * Sy
        d = t if d is None else d + t
    loglik = -0.5 * (d + jnp.log(_emit_det(S, m)) + m * _LOG_2PI)
    return xn, Pn, loglik


def _check_selector(model: FilterModel) -> List[int]:
    obs = _selector_rows(np.asarray(model.H))
    if obs is None:
        raise NotImplementedError(
            "katana_bank requires a selector measurement matrix (every row "
            "of H a unit vector, true for both paper workloads); for a "
            "general dense H use the 'batched_lanes' rewrite stage instead.")
    return obs


def make_predict_fn(model: FilterModel, symmetrize: bool = True):
    """Emit the time update alone: ``pred(xv, P) -> (x̂, P̂)`` on lane
    vectors. Split out of ``make_step_fn`` so kernels that must keep the
    predicted state live past the update (the fused IMM scan's coasting
    frames select between x̂ and x') emit exactly the same op stream as
    the fused predict+update path."""
    n = model.n
    Qtab = _mat_from_np(np.asarray(model.Q, np.float64))
    Fnp = np.asarray(model.F, np.float64)
    dt = float(model.dt)
    is_linear = model.is_linear

    def pred(xv, P):
        if is_linear:
            F = _mat_from_np(Fnp)
            xp = _emit_matvec(F, xv, n)
        else:
            # CTRA-8: [px,py,pz,v,th,om,a,vz] (paper EKF workload §V)
            px, py, pz, v, th, om, a, vz = xv
            c, s = jnp.cos(th), jnp.sin(th)
            xp = [px + v * c * dt, py + v * s * dt, pz + vz * dt,
                  v + a * dt, th + om * dt, om, a, vz]
            F = [[1.0 if i == j else 0.0 for j in range(n)] for i in range(n)]
            F[0][3] = c * dt
            F[0][4] = -v * s * dt
            F[1][3] = s * dt
            F[1][4] = v * c * dt
            F[2][7] = dt
            F[3][6] = dt
            F[4][5] = dt
        Pp = _emit_predict_cov(F, P, Qtab, n, symmetrize)
        return xp, Pp

    return pred


def make_step_fn(model: FilterModel, symmetrize: bool = True,
                 with_loglik: bool = False):
    """Emit one fused predict+update on lane vectors.

    Returns ``step(xv, P, z) -> (x', P')`` where xv is a length-n list
    of (lane,) vectors, P an n x n nested list of lane vectors, z a
    length-m list (``with_loglik`` appends the per-lane measurement
    log-likelihood). Shared by the per-frame kernel, the multi-frame
    scan kernel and the K=1 IMM degenerate case, so all dispatch shapes
    are numerically identical.
    """
    n, m = model.n, model.m
    obs = _check_selector(model)
    Rtab = _mat_from_np(np.asarray(model.R, np.float64))
    pred = make_predict_fn(model, symmetrize)

    def step(xv, P, z):
        xp, Pp = pred(xv, P)
        return _emit_update(xp, Pp, z, Rtab, obs, n, m, symmetrize,
                            with_loglik)

    return step


def make_imm_step_fn(models, symmetrize: bool = True):
    """Emit one fused multi-model predict+update+log-likelihood.

    ``step(xv, P, z, tab) -> (x', P', loglik)`` where ``tab`` is the
    length-E list of (lane,) folded varying-constant rows (see
    ``plan_imm_tables``): shared F/Q/R entries stay trace-time floats,
    per-model entries are direct table-row reads — the model index
    never leaves the matrix path and costs no runtime arithmetic. K=1
    delegates to ``make_step_fn`` (bitwise the plain bank, which is
    what makes the IMM degenerate case exact).
    """
    if len(models) == 1:
        base = make_step_fn(models[0], symmetrize, with_loglik=True)
        return lambda xv, P, z, tab: base(xv, P, z)
    n, m = models[0].n, models[0].m
    obs = _check_selector(models[0])
    for mdl in models:
        if not mdl.is_linear:
            raise NotImplementedError(
                "multi-model katana_bank_imm requires linear member models "
                "(constant F tables); got " + mdl.name)
        assert (mdl.n, mdl.m) == (n, m)
        assert _check_selector(mdl) == obs
    entries, _ = plan_imm_tables(models)

    def step(xv, P, z, tab):
        F = _resolve_mat(entries["F"], tab)
        Q = _resolve_mat(entries["Q"], tab)
        R = _resolve_mat(entries["R"], tab)
        xp = _emit_matvec(F, xv, n)
        Pp = _emit_predict_cov(F, P, Q, n, symmetrize)
        return _emit_update(xp, Pp, z, R, obs, n, m, symmetrize, True)

    return step


_F32_TINY = float(np.finfo(np.float32).tiny)


def _emit_imm_mix(xv, P, mu, Pi, n, K, sym):
    """IMM interaction (mixing) on model-major (K, tt) slabs: every
    state entry xv[d] / P[r][c] is one (K, tt) value whose row i holds
    model i's hypotheses of the tile's tt tracks, so model i's slab is
    the STATIC row ``v[i]``; mu is the K (tt,) mode-probability rows.
    The K x K interaction unrolls into row / scaled-add ops on (tt,)
    vectors and one row stack per mixed entry, keeping the whole
    frame's op stream elementwise. (A rank-1 (K·tt,) flattening of the
    same slabs is a shape cast Mosaic cannot lay out on TPU.) ``Pi`` is
    the (K, K) transition matrix as
    trace-time Python floats: zeros prune whole terms and ones elide
    multiplies, §IV-C constant folding applied to the Markov chain.

    Returns (x_mix, P_mix, cbar_parts) mirroring ``rewrites.imm_mix``:
    x_mix / P_mix are (K, tt) slabs, cbar_parts the K per-mode (tt,)
    predicted probabilities. The same tiny-clamped denominator keeps an
    unreachable mode's 0/0 finite, and the spread term
    (x_i - x_mix_j)(·)ᵀ keeps P_mix consistent. Under ``sym`` only the
    upper triangle of P_mix is computed, mirrors aliased.
    """
    mu_i = [mu[i] for i in range(K)]
    x_i = [[xv[d][i] for i in range(K)] for d in range(n)]
    cbar_parts, w = [], []
    for j in range(K):
        cj = _emit_dot([Pi[i][j] for i in range(K)], mu_i, K)
        cbar_parts.append(cj)
        rden = 1.0 / jnp.maximum(cj, _F32_TINY)
        w.append([0.0 if Pi[i][j] == 0.0 else
                  (mu_i[i] if Pi[i][j] == 1.0 else Pi[i][j] * mu_i[i]) * rden
                  for i in range(K)])
    # Centered moment form of the spread: with x̃_i = x_i - x_0 (model
    # 0's slab as the per-track reference — the spread is shift
    # invariant, and centering keeps the squared terms at inter-model
    # magnitude, so no cancellation),
    #   Σ_i w_ij (x_i - m_j)(x_i - m_j)ᵀ
    #     = Σ_i w_ij x̃_i x̃_iᵀ - m̃_j m̃_jᵀ,   m̃_j = Σ_i w_ij x̃_i.
    # The per-model squares fold INTO the P contraction (A_i = P_i +
    # x̃ x̃ᵀ, shared across all K targets j) instead of K per-(i, j)
    # outer products — and every model-0 term x̃_0 = 0 prunes away.
    xt = [[0.0 if i == 0 else x_i[d][i] - x_i[d][0] for i in range(K)]
          for d in range(n)]
    mt = [[_emit_dot(w[j], xt[d], K) for j in range(K)] for d in range(n)]
    x_mix = [jnp.stack([_bc(mt[d][j] + x_i[d][0], mu_i[0])
                        for j in range(K)]) for d in range(n)]
    P_mix = [[None] * n for _ in range(n)]
    for r in range(n):
        for c in (range(r, n) if sym else range(n)):
            A_i = [P[r][c][i] if _is_zero(xt[r][i]) or _is_zero(xt[c][i])
                   else P[r][c][i] + xt[r][i] * xt[c][i]
                   for i in range(K)]
            # _bc: a mode with an all-zero transition column folds its
            # whole slab to the float 0.0 (w[j] is all-zero), which
            # jnp.stack cannot take
            parts = [_bc(_emit_dot(w[j], A_i, K) - mt[r][j] * mt[c][j],
                         mu_i[0]) for j in range(K)]
            P_mix[r][c] = jnp.stack(parts)
            if sym:
                P_mix[c][r] = P_mix[r][c]
    return x_mix, P_mix, cbar_parts


def _emit_mode_posterior(cbar_parts, ll, K):
    """mu'_k ∝ cbar_k exp(ll_k - max ll), per-mode rows of the (K, tt)
    log-likelihood slab — the shift-stable mode-probability update
    (``rewrites.imm_mode_posterior`` emitted in-kernel; the max
    guarantees at least one finite weight). Returns the K (tt,)
    posterior rows."""
    ll_k = [ll[k] for k in range(K)]
    mx = ll_k[0]
    for k in range(1, K):
        mx = jnp.maximum(mx, ll_k[k])
    ws = [cbar_parts[k] * jnp.exp(ll_k[k] - mx) for k in range(K)]
    s = ws[0]
    for k in range(1, K):
        s = s + ws[k]
    r = 1.0 / s
    return [wk * r for wk in ws]


def _col(v):
    """Lane entry -> (1, lane) row for broadcasting against an
    (M, lane) tile (python floats pass through)."""
    return v if isinstance(v, (int, float)) else v[None, :]


def _emit_cost_tile(z_pred, Sinv, z_rows, m):
    """Squared-Mahalanobis cost tile on lanes-minor layout:
    d[j, c] = yᵀ S_c^{-1} y with y = z_j − ẑ_c. ``z_pred``/``Sinv``
    entries are (lane,) vectors, ``z_rows[r]`` the (M,) r-th coordinate
    of every measurement. Returns the (M, lane) tile, contracted in the
    same order as ``tracker.mahalanobis_cost`` (S^{-1}·y, then y·) so
    the fused and einsum gates see the same float32 rounding."""
    y = [z_rows[r][:, None] - _col(z_pred[r]) for r in range(m)]  # (M, lane)
    d = None
    for r in range(m):
        Sy = None
        for c in range(m):
            t = _col(Sinv[r][c]) * y[c]
            Sy = t if Sy is None else Sy + t
        t = y[r] * Sy
        d = t if d is None else d + t
    return d


def _row(v, k):
    """Model k's (tt,) row of a (K, tt) slab entry (python floats pass
    through)."""
    return v if isinstance(v, (int, float)) else v[k]


def _emit_model_cost_tile(xp, Sinv, z_rows, obs, m, k):
    """Model k's (M, tt) Mahalanobis cost tile from the (K, tt) slabs of
    the predicted state and S^{-1}."""
    return _emit_cost_tile([_row(xp[obs[r]], k) for r in range(m)],
                           [[_row(v, k) for v in row] for row in Sinv],
                           z_rows, m)


def _imm_table_slabs(V, tt, dtype):
    """The (E, K) varying-constant values of ``plan_imm_tables`` as E
    (K, tt) slabs: row k of slab e holds model k's value of entry e."""
    return [jnp.stack([jnp.full((tt,), float(v), dtype) for v in row])
            for row in V]


_BIG = float(np.finfo(np.float32).max)


def _emit_greedy_assign(cost, act, zval, gate, rounds):
    """Globally-ordered greedy assignment emitted in-kernel, on the
    (M, lane) cost tile (tracks lanes-minor). Exactly
    ``tracker.greedy_assign`` — same gate, same first-occurrence
    (track-major) tie-break, same -1 padding — but wave-scheduled:

    every trip of the loop commits EVERY pair that is simultaneously
    the first argmin of its track row and of its measurement column.
    Any such mutual argmin is provably committed by sequential greedy
    (nothing cheaper can kill its row or column first), committed pairs
    are pairwise row/col-disjoint by construction, and the surviving
    matrix is what sequential greedy would also see — so iterating
    waves reproduces the one-at-a-time result EXACTLY, tie-breaks
    included, while committing many pairs per trip. The global minimum
    is always a mutual argmin, so a wave that commits nothing means
    nothing assignable remains — which makes the early-exit
    ``while_loop`` exact too: ``rounds`` (= min(C, M), the sequential
    bound) caps the trip count, but a typical frame converges in a
    handful of waves instead of paying min(C, M) sequential argmins.

    cost: (M, lane) f32; act: (lane,) 0/1 active-slot mask; zval: (M,)
    0/1 real-measurement mask; gate/rounds are trace-time constants.
    Returns assoc (lane,) int32 — measurement index per track or -1.
    """
    M, C = cost.shape
    BIG = jnp.asarray(_BIG, cost.dtype)
    valid = (act[None, :] > 0) & (zval[:, None] > 0)
    masked = jnp.where(valid & (cost <= gate), cost, BIG)
    iM = jax.lax.broadcasted_iota(jnp.int32, (M, C), 0)
    iC = jax.lax.broadcasted_iota(jnp.int32, (M, C), 1)

    def cond(carry):
        r, go, _, _ = carry
        return go & (r < rounds)

    def body(carry):
        r, _, masked, assoc = carry
        tmin = masked.min(axis=0)                             # (C,)
        targ = jnp.argmin(masked, axis=0).astype(jnp.int32)   # (C,) meas
        marg = jnp.argmin(masked, axis=1).astype(jnp.int32)   # (M,) track
        # mutual-argmin pairs, gather-free: hit[j, c] <=> row c's first
        # argmin is j AND column j's first argmin is c
        hit = (iM == targ[None, :]) & (iC == marg[:, None])
        commit = hit.any(axis=0) & (tmin < BIG)               # (C,)
        assoc = jnp.where(commit, targ, assoc)
        meas_taken = (hit & commit[None, :]).any(axis=1)      # (M,)
        masked = jnp.where(commit[None, :] | meas_taken[:, None], BIG,
                           masked)
        return r + 1, commit.any(), masked, assoc

    assoc0 = jnp.full((C,), -1, jnp.int32)
    carry = (jnp.int32(0), jnp.asarray(True), masked, assoc0)
    *_, assoc = jax.lax.while_loop(cond, body, carry)
    return assoc


def _emit_gather_assigned(assoc, z_rows, m):
    """zk[r] (lane,) = z[assoc, r] via a one-hot contraction (garbage-
    free: unassigned lanes read 0, and their update is select-masked
    away downstream — no dynamic gather, the shape class TPU lanes
    like)."""
    M = z_rows[0].shape[0]
    iM = jax.lax.broadcasted_iota(jnp.int32, (M, assoc.shape[0]), 0)
    onehot = (iM == assoc[None, :]).astype(z_rows[0].dtype)   # (M, lane)
    return [jnp.sum(onehot * z_rows[r][:, None], axis=0) for r in range(m)]


def make_frame_kernel(model: FilterModel, gate: float, rounds: int,
                      symmetrize: bool = True):
    """Build the fused FRAME kernel body: the entire single-model
    measurement cycle — predict, innovation + cofactor S^{-1}, the
    gated Mahalanobis cost tile, the greedy assignment waves, and the
    Kalman update of the assigned lanes — in ONE Pallas dispatch. Only
    spawn/prune lifecycle bookkeeping stays in XLA (``tracker.frame_step``).

    The S^{-1} emitted for the gate IS the S^{-1} of the Kalman gain
    (``_emit_innovation``), so the whole frame still performs exactly
    one cofactor inversion per model. The greedy rounds run as an
    in-kernel ``while_loop`` over the (M, lane) cost tile
    (``_emit_greedy_assign``) — the assignment is a global argmin, so
    the frame kernel runs as a single program over the whole bank
    (grid=(1,)) rather than tiling the lane axis.

    Inputs: x (n, C), P (n, n, C), z (m, M), z_valid (1, M) 0/1,
    active (1, C) 0/1. Outputs: x' (n, C), P' (n, n, C) — predicted
    values where a lane got no measurement, updated where it did —
    and assoc (1, C) int32.
    """
    n, m = model.n, model.m
    obs = _check_selector(model)
    Rtab = _mat_from_np(np.asarray(model.R, np.float64))
    pred = make_predict_fn(model, symmetrize)

    def kernel(x_ref, P_ref, z_ref, zv_ref, act_ref, x_out, P_out, a_out):
        lane = x_ref[0, :]
        xv = [x_ref[i, :] for i in range(n)]
        P = [[P_ref[i, j, :] for j in range(n)] for i in range(n)]
        xp, Pp = pred(xv, P)
        inno = _emit_innovation(Pp, Rtab, obs, n, m)
        _, Sinv, _ = inno
        z_rows = [z_ref[r, :] for r in range(m)]              # (M,)
        z_pred = [xp[obs[r]] for r in range(m)]
        cost = _emit_cost_tile(z_pred, Sinv, z_rows, m)       # (M, C)
        assoc = _emit_greedy_assign(cost, act_ref[0, :], zv_ref[0, :],
                                    gate, rounds)
        zk = _emit_gather_assigned(assoc, z_rows, m)
        xn, Pn = _emit_update(xp, Pp, zk, Rtab, obs, n, m, symmetrize,
                              False, inno=inno)
        upd = (assoc >= 0) & (act_ref[0, :] > 0)
        for i in range(n):
            x_out[i, :] = jnp.where(upd, _bc(xn[i], lane), _bc(xp[i], lane))
            for j in range(n):
                P_out[i, j, :] = jnp.where(upd, _bc(Pn[i][j], lane),
                                           _bc(Pp[i][j], lane))
        a_out[0, :] = assoc

    return kernel


def make_imm_frame_kernel(models, trans, gate: float, rounds: int,
                          symmetrize: bool = True):
    """Build the fused IMM FRAME kernel body: mixing, the K
    model-conditioned predicts, innovation + cofactor S^{-1} per model,
    the cbar-weighted gated cost tile, the greedy assignment waves, the
    K Kalman updates + per-lane log-likelihoods, the mode posterior and
    the moment-matched combined estimate — the whole multi-model
    measurement cycle in ONE dispatch; only spawn/prune stays in XLA
    (``tracker.imm_frame_step``).

    Layout matches ``make_imm_scan_kernel``: blocks arrive as
    x (K, n, C), P (K, n, n, C), mu (K, C) and every state entry stays
    a (K, C) model-major slab in-kernel, so the mixing reaches across
    models with static row reads and the K predict+updates emit ONE op
    stream (shared F/Q/R entries fold to trace-time floats via
    ``plan_imm_tables``; varying entries become loop-invariant slabs).
    The gate weighs each model's Mahalanobis distance by the
    Markov-predicted cbar — exactly ``tracker.imm_frame_step``'s
    mode-probability-weighted gate. Coasting lanes (no measurement)
    keep the predicted x̂/P̂ and the Markov-predicted cbar, matching
    ``bank.update_imm_bank``.

    K=1 skips the mixing/posterior arithmetic and emits exactly
    ``make_frame_kernel``'s op stream with a passthrough mu — the
    degenerate IMM reduces to the plain fused frame, nonlinear (EKF)
    members included.

    Inputs: x (K, n, C), P (K, n, n, C), mu (K, C), z (m, M),
    z_valid (1, M) 0/1, active (1, C) 0/1. Outputs: x' (K, n, C),
    P' (K, n, n, C), mu' (K, C), x_c (n, C) combined estimates,
    assoc (1, C) int32.
    """
    K = len(models)
    n, m = models[0].n, models[0].m
    obs = _check_selector(models[0])
    if K == 1:
        pred = make_predict_fn(models[0], symmetrize)
        entries = V = None
        Rtab0 = _mat_from_np(np.asarray(models[0].R, np.float64))
    else:
        for mdl in models:
            if not mdl.is_linear:
                raise NotImplementedError(
                    "multi-model katana_imm_frame requires linear member "
                    "models (constant F tables); got " + mdl.name)
            assert (mdl.n, mdl.m) == (n, m)
            assert _check_selector(mdl) == obs
        entries, V = plan_imm_tables(models)
        pred = Rtab0 = None
    Pi = [[float(v) for v in row] for row in np.asarray(trans, np.float64)]

    def kernel(x_ref, P_ref, mu_ref, z_ref, zv_ref, act_ref,
               x_out, P_out, mu_out, xc_out, a_out):
        tt = x_ref.shape[-1]
        proto = mu_ref[:, :]            # (K, tt) broadcast target for _bc
        mu = [mu_ref[k, :] for k in range(K)]                # (tt,) rows
        xv = [x_ref[:, i, :] for i in range(n)]
        P = [[P_ref[:, i, j, :] for j in range(n)] for i in range(n)]
        act = act_ref[0, :] > 0                              # (tt,)
        z_rows = [z_ref[r, :] for r in range(m)]             # (M,)
        if K == 1:
            xp, Pp = pred(xv, P)
            inno = _emit_innovation(Pp, Rtab0, obs, n, m)
            _, Sinv, _ = inno
            cost = _emit_model_cost_tile(xp, Sinv, z_rows, obs, m, 0)
            assoc = _emit_greedy_assign(cost, act_ref[0, :], zv_ref[0, :],
                                        gate, rounds)
            zk = _emit_gather_assigned(assoc, z_rows, m)
            xn, Pn = _emit_update(xp, Pp, zk, Rtab0, obs, n, m, symmetrize,
                                  False, inno=inno)
            upd = (assoc >= 0) & act
            mu_parts = cbar_parts = None
        else:
            tabv = _imm_table_slabs(V, tt, proto.dtype)
            Ftab, Qtab, Rtab = (_resolve_mat(entries[nm], tabv)
                                for nm in ("F", "Q", "R"))
            x_mix, P_mix, cbar_parts = _emit_imm_mix(
                xv, P, mu, Pi, n, K, symmetrize)
            xp = _emit_matvec(Ftab, x_mix, n)
            Pp = _emit_predict_cov(Ftab, P_mix, Qtab, n, symmetrize)
            inno = _emit_innovation(Pp, Rtab, obs, n, m)
            _, Sinv, _ = inno
            # cbar-weighted gate: sum_k cbar_k · d_k over the model rows
            cost = None
            for k in range(K):
                t = _col(cbar_parts[k]) * _emit_model_cost_tile(
                    xp, Sinv, z_rows, obs, m, k)              # (M, tt)
                cost = t if cost is None else cost + t
            assoc = _emit_greedy_assign(cost, act_ref[0, :], zv_ref[0, :],
                                        gate, rounds)
            # every model row sees the same assigned measurement: the
            # (tt,) rows broadcast against the (K, tt) slabs
            zk = _emit_gather_assigned(assoc, z_rows, m)
            xn, Pn, ll = _emit_update(xp, Pp, zk, Rtab, obs, n, m,
                                      symmetrize, True, inno=inno)
            mu_parts = _emit_mode_posterior(cbar_parts, ll, K)
            upd = (assoc >= 0) & act
        # coasting select, exactly bank.update_imm_bank: predicted x̂/P̂
        # where a lane got no measurement, mu <- the Markov cbar
        uL = jnp.broadcast_to(upd, (K, tt))
        xs = [jnp.where(uL, _bc(xn[i], proto), _bc(xp[i], proto))
              for i in range(n)]
        Ps = [[None] * n for _ in range(n)]
        for i in range(n):
            for j in (range(i, n) if symmetrize else range(n)):
                Ps[i][j] = jnp.where(uL, _bc(Pn[i][j], proto),
                                     _bc(Pp[i][j], proto))
                if symmetrize:
                    Ps[j][i] = Ps[i][j]
        lane1 = act_ref[0, :]                                # float (tt,)
        if K == 1:
            mu_sel = [mu[0]]
            xc = [u[0] for u in xs]
        else:
            mu_sel = [jnp.where(upd, _bc(mu_parts[k], lane1),
                                _bc(cbar_parts[k], lane1)) for k in range(K)]
            xc = [_emit_dot(mu_sel, [u[k] for k in range(K)], K)
                  for u in xs]
        mu_out[:, :] = jnp.stack([_bc(p, lane1) for p in mu_sel])
        for i in range(n):
            x_out[:, i, :] = xs[i]
            xc_out[i, :] = _bc(xc[i], lane1)
            for j in range(n):
                P_out[:, i, j, :] = Ps[i][j]
        a_out[0, :] = assoc

    return kernel


def make_kernel(model: FilterModel, symmetrize: bool = True):
    """Build the per-frame Pallas kernel body for this filter model."""
    n, m = model.n, model.m
    step = make_step_fn(model, symmetrize)

    def kernel(x_ref, P_ref, z_ref, x_out, P_out):
        xv = [x_ref[i, :] for i in range(n)]
        P = [[P_ref[i, j, :] for j in range(n)] for i in range(n)]
        z = [z_ref[i, :] for i in range(m)]
        xn, Pn = step(xv, P, z)
        lane = x_ref[0, :]
        for i in range(n):
            x_out[i, :] = _bc(xn[i], lane)
            for j in range(n):
                P_out[i, j, :] = _bc(Pn[i][j], lane)

    return kernel


def make_imm_kernel(models, symmetrize: bool = True):
    """Build the multi-model (IMM) Pallas kernel body: the per-frame
    predict+update over K-model stacked lanes, plus the per-lane
    measurement log-likelihood output used by the IMM mode-probability
    update (paper §IV-D batching, reused for the model axis)."""
    n, m = models[0].n, models[0].m
    step = make_imm_step_fn(models, symmetrize)

    def kernel(x_ref, P_ref, z_ref, tab_ref, x_out, P_out, ll_out):
        xv = [x_ref[i, :] for i in range(n)]
        P = [[P_ref[i, j, :] for j in range(n)] for i in range(n)]
        z = [z_ref[i, :] for i in range(m)]
        tab = [tab_ref[e, :] for e in range(tab_ref.shape[0])]
        xn, Pn, ll = step(xv, P, z, tab)
        lane = x_ref[0, :]
        for i in range(n):
            x_out[i, :] = _bc(xn[i], lane)
            for j in range(n):
                P_out[i, j, :] = _bc(Pn[i][j], lane)
        ll_out[0, :] = _bc(ll, lane)

    return kernel


def make_scan_kernel(model: FilterModel, T: int, symmetrize: bool = True):
    """Build the multi-frame Pallas kernel body: fori_loop over T with
    x and P resident in VMEM/VREGs for the whole sequence; each step
    reads one (m, lane) slice of the T-frame measurement block and
    writes one (n, lane) slice of the T-frame output block (both blocks
    live in VMEM for the dispatch — see katana_bank_scan_step on the
    resulting T bound)."""
    n, m = model.n, model.m
    step = make_step_fn(model, symmetrize)

    def kernel(x_ref, P_ref, zs_ref, xs_out, x_fin, P_fin):
        x0 = [x_ref[i, :] for i in range(n)]
        P0 = [[P_ref[i, j, :] for j in range(n)] for i in range(n)]

        def body(t, carry):
            xv, P = carry
            zt = zs_ref[pl.ds(t, 1)]  # (1, m, lane)
            z = [zt[0, r, :] for r in range(m)]
            xn, Pn = step(xv, P, z)
            lane = x_ref[0, :]
            # broadcast any constant-folded entries so the fori_loop
            # carry keeps a uniform (lane,)-vector structure
            xn = [_bc(v, lane) for v in xn]
            Pn = [[_bc(v, lane) for v in row] for row in Pn]
            xs_out[pl.ds(t, 1)] = jnp.stack(xn)[None]
            return xn, Pn

        xT, PT = jax.lax.fori_loop(0, T, body, (x0, P0))
        for i in range(n):
            x_fin[i, :] = xT[i]
            for j in range(n):
                P_fin[i, j, :] = PT[i][j]

    return kernel


def make_imm_scan_kernel(models, trans, T: int, symmetrize: bool = True,
                         with_valid: bool = False):
    """Build the fused IMM multi-frame kernel body: the ENTIRE
    K-hypothesis IMM recursion over T frames inside one fori_loop, with
    the model-conditioned x/P banks AND the mode probabilities mu
    VMEM-resident across frames.

    Layout: blocks arrive as x (K, n, tt), P (K, n, n, tt), mu (K, tt)
    with tt tracks per program; in-kernel every state entry is ONE
    (K, tt) slab, model-major — row i holds model i's hypotheses of the
    tile's tracks, a static row read. That keeps the entire per-frame op
    stream same-shape elementwise (the class the backend fuses like the
    single-model kernels). The per-model F/Q/R constants fold through
    ``plan_imm_tables``: entries shared by every model stay trace-time
    Python floats (zeros pruned, exactly the single-model emit), entries
    that differ materialize ONCE, outside the time loop, as
    loop-invariant (K, tt) slabs — so the K model-conditioned
    predict+updates emit ONE op stream whose length is independent of K.
    The (K, K) Markov transition matrix folds to float literals inside
    ``_emit_imm_mix``.

    Per frame t the body emits:
      mix (mode-conditioned reblending of x/P from mu, slice/scaled-add
      over the K slabs)
      -> predict+update over all K models at once (+ the per-(model,
         track) log-likelihood from the same cofactor S^{-1} as the
         Kalman gain)
      -> mode posterior -> moment-matched combined estimate (written to
         xs_out[t]).

    K=1 skips the mixing/posterior arithmetic and emits exactly
    ``make_scan_kernel``'s op stream (the ``imm_scan`` stage reduces
    bitwise to ``fused_scan``, nonlinear members included).

    ``with_valid``: the zs block carries one more row, a 0/1
    measurement validity (a (T, 1, tt) block of its own crashes the
    Mosaic compiler). An invalid frame coasts — the carry keeps the
    predicted x̂/P̂ and the Markov-predicted cbar (the tracker's
    no-measurement semantics), via a lane select (no control flow,
    static shapes).
    """
    K = len(models)
    n, m = models[0].n, models[0].m
    obs = _check_selector(models[0])
    if K == 1:
        pred = make_predict_fn(models[0], symmetrize)
        entries = V = None
        Rtab0 = _mat_from_np(np.asarray(models[0].R, np.float64))
    else:
        for mdl in models:
            if not mdl.is_linear:
                raise NotImplementedError(
                    "multi-model imm_scan requires linear member models "
                    "(constant F tables); got " + mdl.name)
            assert (mdl.n, mdl.m) == (n, m)
            assert _check_selector(mdl) == obs
        entries, V = plan_imm_tables(models)
        pred = Rtab0 = None
    Pi = [[float(v) for v in row] for row in np.asarray(trans, np.float64)]

    def kernel(x_ref, P_ref, mu_ref, zs_ref, xs_out, x_fin, P_fin, mu_fin):
        tt = x_ref.shape[-1]
        proto = mu_ref[:, :]  # (K, tt) broadcast target for _bc
        mu0 = [mu_ref[k, :] for k in range(K)]              # (tt,) rows
        xv0 = [x_ref[:, i, :] for i in range(n)]
        P0 = [[P_ref[:, i, j, :] for j in range(n)] for i in range(n)]
        if K > 1:
            # materialize the model-varying constants once, OUTSIDE the
            # time loop: V[e] (one float per model) -> a loop-invariant
            # (K, tt) slab whose row k is the constant for model k
            tabv = _imm_table_slabs(V, tt, proto.dtype)
            Ftab, Qtab, Rtab = (_resolve_mat(entries[nm], tabv)
                                for nm in ("F", "Q", "R"))
        else:
            Rtab = Rtab0

        def body(t, carry):
            xv, P, mu = carry
            zt = zs_ref[pl.ds(t, 1)]  # (1, m [+1], tt)
            # every model row sees the same measurement: the (tt,) rows
            # broadcast against the (K, tt) slabs
            zr = [zt[0, r, :] for r in range(m)]
            if K == 1:
                xp, Pp = pred(xv, P)
                xn, Pn = _emit_update(xp, Pp, zr, Rtab, obs, n, m,
                                      symmetrize, False)
            else:
                x_mix, P_mix, cbar_parts = _emit_imm_mix(
                    xv, P, mu, Pi, n, K, symmetrize)
                xp = _emit_matvec(Ftab, x_mix, n)
                Pp = _emit_predict_cov(Ftab, P_mix, Qtab, n, symmetrize)
                xn, Pn, ll = _emit_update(xp, Pp, zr, Rtab, obs, n, m,
                                          symmetrize, True)
                mu_parts = _emit_mode_posterior(cbar_parts, ll, K)
            if with_valid:
                # coasting select: x̂/P̂ where v=0, x'/P' where v=1; mu
                # falls back to the Markov-predicted cbar (still
                # normalized; matches bank.update_imm_bank coasting)
                v = zs_ref[t, m, :] > 0                        # (tt,)
                vK = jnp.broadcast_to(v, (K, tt))
                xn = [jnp.where(vK, _bc(a, proto), _bc(b, proto))
                      for a, b in zip(xn, xp)]
                Pc = [[None] * n for _ in range(n)]
                for i in range(n):
                    for j in (range(i, n) if symmetrize else range(n)):
                        Pc[i][j] = jnp.where(vK, _bc(Pn[i][j], proto),
                                             _bc(Pp[i][j], proto))
                        if symmetrize:
                            Pc[j][i] = Pc[i][j]
                Pn = Pc
                if K > 1:
                    mu_parts = [jnp.where(v, a, b)
                                for a, b in zip(mu_parts, cbar_parts)]
            # broadcast constant-folded entries: uniform carry structure
            xn = [_bc(u, proto) for u in xn]
            Pn = [[_bc(u, proto) for u in row] for row in Pn]
            # moment-matched combined estimate, (tt,) per state dim
            if K == 1:
                mu_new = mu
                xc = [u[0] for u in xn]
            else:
                mu_new = mu_parts
                xc = [_emit_dot(mu_parts, [u[k] for k in range(K)], K)
                      for u in xn]
            xs_out[pl.ds(t, 1)] = jnp.stack(xc)[None]
            return xn, Pn, mu_new

        xT, PT, muT = jax.lax.fori_loop(0, T, body, (xv0, P0, mu0))
        for k in range(K):
            mu_fin[k, :] = muT[k]
        for i in range(n):
            x_fin[:, i, :] = xT[i]
            for j in range(n):
                P_fin[:, i, j, :] = PT[i][j]

    return kernel


@functools.partial(jax.jit, static_argnames=("model", "lane_tile",
                                             "symmetrize", "interpret"))
def katana_bank_step(model: FilterModel, x, P, z, lane_tile: int = LANE_TILE,
                     symmetrize: bool = True, *, interpret: bool):
    """x: (n, N); P: (n, n, N); z: (m, N) — lanes-minor (SoA) layout.

    N must be a multiple of lane_tile (ops.py pads)."""
    n, m = model.n, model.m
    N = x.shape[-1]
    assert N % lane_tile == 0, (N, lane_tile)
    grid = (N // lane_tile,)
    kern = make_kernel(model, symmetrize)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((n, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((m, lane_tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((n, n, lane_tile), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, N), x.dtype),
            jax.ShapeDtypeStruct((n, n, N), P.dtype),
        ],
        interpret=interpret,
    )(x, P, z)


@functools.partial(jax.jit, static_argnames=("imm", "lane_tile",
                                             "symmetrize", "interpret"))
def katana_bank_imm_step(imm, x, P, z, tab, lane_tile: int = LANE_TILE,
                         symmetrize: bool = True, *, interpret: bool):
    """Multi-model fused step over stacked lanes.

    x: (n, L); P: (n, n, L); z: (m, L); tab: (E, L) host-folded
    varying-constant table (``plan_imm_tables`` x the one-hot model
    masks) — lanes-minor (SoA), L = K·N flattened model-major (ops.py
    packs and pads). Returns (x' (n, L), P' (n, n, L), loglik (1, L))."""
    n, m = imm.n, imm.m
    E = tab.shape[0]
    L = x.shape[-1]
    assert L % lane_tile == 0, (L, lane_tile)
    grid = (L // lane_tile,)
    kern = make_imm_kernel(imm.models, symmetrize)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((n, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((m, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((E, lane_tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((n, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((1, lane_tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, L), x.dtype),
            jax.ShapeDtypeStruct((n, n, L), P.dtype),
            jax.ShapeDtypeStruct((1, L), x.dtype),
        ],
        interpret=interpret,
    )(x, P, z, tab)


@functools.partial(jax.jit, static_argnames=("model", "lane_tile",
                                             "symmetrize", "interpret"))
def katana_bank_scan_step(model: FilterModel, x, P, zs,
                          lane_tile: int = LANE_TILE,
                          symmetrize: bool = True, *, interpret: bool):
    """Whole-sequence fused scan, one pallas_call per sequence.

    x: (n, N); P: (n, n, N); zs: (T, m, N) — lanes-minor (SoA) layout.
    Returns (xs (T, n, N), x_fin (n, N), P_fin (n, n, N)).

    The grid tiles N only; the time loop runs INSIDE the kernel, so the
    covariance bank stays VMEM-resident across all T frames (one HBM
    read of P at entry + one write at exit, vs 2·T round-trips for the
    per-frame dispatch). The zs/xs blocks are whole-T VMEM blocks,
    which bounds T to a few hundred frames per dispatch on a TPU
    (``scan_time_chunk`` has the arithmetic: 384 at cv-6 and 256
    lanes); ops.katana_bank_sequence chunks longer streams. N must be
    a multiple of lane_tile (ops.py pads)."""
    n, m = model.n, model.m
    T = zs.shape[0]
    N = x.shape[-1]
    assert N % lane_tile == 0, (N, lane_tile)
    grid = (N // lane_tile,)
    kern = make_scan_kernel(model, T, symmetrize)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((n, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((T, m, lane_tile), lambda i: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((T, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((n, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((n, n, lane_tile), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, n, N), x.dtype),
            jax.ShapeDtypeStruct((n, N), x.dtype),
            jax.ShapeDtypeStruct((n, n, N), P.dtype),
        ],
        interpret=interpret,
    )(x, P, zs)


@functools.partial(jax.jit, static_argnames=("imm", "lane_tile",
                                             "symmetrize", "with_valid",
                                             "interpret"))
def katana_bank_imm_scan_step(imm, x, P, mu, zs, lane_tile: int = LANE_TILE,
                              symmetrize: bool = True,
                              with_valid: bool = False, *,
                              interpret: bool):
    """Whole-sequence fused IMM scan, one pallas_call per sequence.

    x: (K, n, N); P: (K, n, n, N); mu: (K, N); zs: (T, m, N) — the track
    index N lanes-minor; ``lane_tile`` counts TRACKS per program, whose
    block is read as (K, lane_tile) model-major slabs (see
    ``make_imm_scan_kernel``); on TPU it must be a multiple of 128 or
    the whole N. With ``with_valid`` zs is (T, m + 1, N), its last row a
    0/1 validity stream: invalid frames coast (predict only,
    mu <- cbar). Returns (xs (T, n, N) moment-matched combined
    estimates, x_fin, P_fin, mu_fin).

    The grid tiles N only; mixing, the K predict+updates, the mode
    posterior and the combination all run INSIDE the kernel's time loop,
    so an entire IMM stream costs ONE dispatch — x, P and mu never
    round-trip HBM between frames (vs one katana_bank_imm dispatch plus
    XLA mixing per frame in ``ops.imm_bank_sequence``). The same
    whole-T VMEM-block bound as ``katana_bank_scan_step`` applies (at
    K· the block bytes); ``ops.katana_imm_sequence`` chunks longer
    streams."""
    K, n = imm.K, imm.n
    T, rows, N = zs.shape
    assert N % lane_tile == 0, (N, lane_tile)
    assert rows == imm.m + with_valid, (zs.shape, imm.m, with_valid)
    grid = (N // lane_tile,)
    kern = make_imm_scan_kernel(imm.models, imm.trans, T, symmetrize,
                                with_valid=with_valid)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((K, n, n, lane_tile), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((K, lane_tile), lambda i: (0, i)),
            pl.BlockSpec((T, rows, lane_tile), lambda i: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((T, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((K, n, lane_tile), lambda i: (0, 0, i)),
            pl.BlockSpec((K, n, n, lane_tile), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((K, lane_tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, n, N), x.dtype),
            jax.ShapeDtypeStruct((K, n, N), x.dtype),
            jax.ShapeDtypeStruct((K, n, n, N), P.dtype),
            jax.ShapeDtypeStruct((K, N), mu.dtype),
        ],
        interpret=interpret,
    )(x, P, mu, zs)


@functools.partial(jax.jit, static_argnames=("model", "gate", "rounds",
                                             "symmetrize", "interpret"))
def katana_frame_step(model: FilterModel, x, P, z, zval, act, gate: float,
                      rounds: int, symmetrize: bool = True, *,
                      interpret: bool):
    """Whole-frame fused dispatch: predict + gate + greedy-assign +
    update in one pallas_call.

    x: (n, C); P: (n, n, C); z: (m, M); zval: (1, M) 0/1; act: (1, C)
    0/1 — lanes-minor (SoA). Returns (x' (n, C), P' (n, n, C),
    assoc (1, C) int32). The greedy assignment is a GLOBAL argmin over
    the (M, C) cost tile, so the grid is (1,): one program holds the
    whole bank (C·n² f32 ≈ 0.3 MB at C=1024 for n=9 — comfortably
    VMEM-resident; the frame kernel trades the scan kernels' lane
    tiling for whole-bank visibility)."""
    n, m = model.n, model.m
    C = x.shape[-1]
    M = z.shape[-1]
    kern = make_frame_kernel(model, gate, rounds, symmetrize)
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n, C), lambda i: (0, 0)),
            pl.BlockSpec((n, n, C), lambda i: (0, 0, 0)),
            pl.BlockSpec((m, M), lambda i: (0, 0)),
            pl.BlockSpec((1, M), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, C), lambda i: (0, 0)),
            pl.BlockSpec((n, n, C), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, C), x.dtype),
            jax.ShapeDtypeStruct((n, n, C), P.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.int32),
        ],
        interpret=interpret,
    )(x, P, z, zval, act)


@functools.partial(jax.jit, static_argnames=("imm", "gate", "rounds",
                                             "symmetrize", "interpret"))
def katana_imm_frame_step(imm, x, P, mu, z, zval, act, gate: float,
                          rounds: int, symmetrize: bool = True, *,
                          interpret: bool):
    """Whole-frame fused IMM dispatch: mix + K predicts + cbar-weighted
    gate + greedy-assign + K updates + mode posterior + combined
    estimate in one pallas_call.

    x: (K, n, C); P: (K, n, n, C); mu: (K, C); z: (m, M); zval: (1, M)
    0/1; act: (1, C) 0/1 — track axis lanes-minor, (K, C) model-major
    slabs in-kernel (the ``make_imm_scan_kernel`` layout). Returns
    (x' (K, n, C), P' (K, n, n, C), mu' (K, C), x_c (n, C),
    assoc (1, C) int32). grid=(1,) for the same global-argmin reason as
    ``katana_frame_step``."""
    K, n, m = imm.K, imm.n, imm.m
    C = x.shape[-1]
    M = z.shape[-1]
    kern = make_imm_frame_kernel(imm.models, imm.trans, gate, rounds,
                                 symmetrize)
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((K, n, C), lambda i: (0, 0, 0)),
            pl.BlockSpec((K, n, n, C), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((K, C), lambda i: (0, 0)),
            pl.BlockSpec((m, M), lambda i: (0, 0)),
            pl.BlockSpec((1, M), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((K, n, C), lambda i: (0, 0, 0)),
            pl.BlockSpec((K, n, n, C), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((K, C), lambda i: (0, 0)),
            pl.BlockSpec((n, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, n, C), x.dtype),
            jax.ShapeDtypeStruct((K, n, n, C), P.dtype),
            jax.ShapeDtypeStruct((K, C), mu.dtype),
            jax.ShapeDtypeStruct((n, C), x.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.int32),
        ],
        interpret=interpret,
    )(x, P, mu, z, zval, act)


@functools.partial(jax.jit, static_argnames=("gate", "rounds", "interpret"))
def greedy_assign_step(cost, valid, gate: float, rounds: int, *,
                       interpret: bool):
    """Standalone dispatch of the in-kernel greedy assignment
    (``_emit_greedy_assign``) for direct equivalence testing against
    ``tracker.greedy_assign``: cost (M, C) lanes-minor, valid (M, C)
    0/1 -> assoc (1, C) int32."""
    M, C = cost.shape

    def kern(cost_ref, valid_ref, a_out):
        cost = cost_ref[:, :]
        # fold the 2-D pair validity through the per-axis masks the
        # frame kernels use: rows of an all-ones act/zval, entrywise
        # invalid pairs pushed past the gate
        vbad = valid_ref[:, :] <= 0
        big = jnp.asarray(_BIG, cost.dtype)
        cost = jnp.where(vbad, big, cost)
        ones_c = jnp.ones((C,), cost.dtype)
        ones_m = jnp.ones((M,), cost.dtype)
        a_out[0, :] = _emit_greedy_assign(cost, ones_c, ones_m, gate, rounds)

    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((M, C), lambda i: (0, 0)),
                  pl.BlockSpec((M, C), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.int32),
        interpret=interpret,
    )(cost, valid)
