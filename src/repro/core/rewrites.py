"""KATANA's three NPU-aware graph rewrites, adapted to XLA/TPU.

Four stage builders mirror the paper's Fig. 3 pipeline, plus the
TPU-native beyond-paper batching:

  ``baseline``          naive export: runtime Subtract, runtime
                        Transpose of system matrices (passed as runtime
                        tensors, exactly like un-folded ONNX
                        initializers), dummy batch axes with
                        Unsqueeze/Squeeze bookkeeping, generic
                        ``linalg.inv``.
  ``opt1``              Subtract elimination: the precomputed
                        negative-projection matrix ``H_neg`` turns every
                        innovation/covariance subtraction into a GEMM +
                        Add (paper §IV-B).
  ``opt2``              Static tensor fusion: all system matrices and
                        their transposes folded as trace-time constants,
                        dummy axes removed, closed-form cofactor
                        inversion — the steady-state graph is dot/add
                        only (paper §IV-C).
  ``batched_blockdiag`` Paper §IV-D: N filters packed into one
                        (N·n)x(N·n) block-diagonal system; dense GEMMs.
                        Faithful reproduction — including its N^2 FLOP
                        expansion on covariance GEMMs.
  ``batched_lanes``     Beyond-paper TPU-native batching: filter index
                        on the minor (lane) axis, per-filter n x n
                        algebra batched via einsum; identical numerics
                        at ~N^2 less compute. This is the layout the
                        ``katana_bank`` Pallas kernel implements.
  ``fused_scan``        Sequence-level Opt-2: the whole (T, N, m)
                        measurement stream through ONE Pallas dispatch
                        (``katana_bank_sequence``) — the time loop runs
                        inside the kernel with x/P VMEM-resident across
                        frames, instead of a per-frame pallas_call with
                        the covariance bank bouncing through HBM.
  ``imm_bank``          Multi-model (IMM) estimation on the fused
                        kernel: K motion hypotheses per track run as
                        stacked lanes of one padded bank (the §IV-D
                        batching axis reused for the model index), the
                        per-lane kernel also emits the measurement
                        log-likelihood from the SAME cofactor S^{-1} it
                        computed for the Kalman gain, and the IMM
                        mixing / mode-probability algebra (this module)
                        closes the loop between frames — no inversion
                        anywhere outside the kernel.
  ``imm_scan``          Sequence-level IMM fusion: the mixing and
                        mode-posterior algebra move INSIDE the scan
                        kernel's time loop, so a whole K-hypothesis
                        stream over T frames is ONE Pallas dispatch with
                        x/P and the mode probabilities VMEM-resident
                        across frames (``make_imm_scan_kernel`` /
                        ``katana_imm_sequence``). The Markov transition
                        matrix and every per-model constant fold at
                        trace time; K=1 reduces exactly (bitwise) to
                        ``fused_scan``.

Every stage is algebraically the same filter (``imm_bank``/``imm_scan``
with K=1 degenerate to it exactly); tests assert equivalence against
the float64 oracles in ``repro.core.ref``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import FilterModel, IMMModel, as_imm

# The tracker's XLA contractions run at full f32 precision on every
# backend: a TPU's default for an f32 dot is one bf16 pass (about three
# significant digits), which would round the einsum path's states and
# every spawned track's position. The CPU computes f32 either way.
_F32 = jax.lax.Precision.HIGHEST


def einsum(subscripts, *operands):
    return jnp.einsum(subscripts, *operands, precision=_F32)


def matmul(a, b):
    return jnp.matmul(a, b, precision=_F32)


STAGES = ("baseline", "opt1", "opt2", "batched_blockdiag", "batched_lanes",
          "fused_scan", "imm_bank", "imm_scan")


# ---------------------------------------------------------------------------
# Closed-form small-matrix inversion (cofactor / Schur), batched-friendly.
# Pure mul/add + one reciprocal — the TPU analogue of the paper's §IV-C
# replacement of the generic inversion op, keeping the whole update on
# the matrix pipeline (see docs/architecture.md).
# ---------------------------------------------------------------------------

def inv1(M):
    return 1.0 / M


def inv2(M):
    a = M[..., 0, 0]
    b = M[..., 0, 1]
    c = M[..., 1, 0]
    d = M[..., 1, 1]
    rdet = 1.0 / (a * d - b * c)
    row0 = jnp.stack([d * rdet, -b * rdet], axis=-1)
    row1 = jnp.stack([-c * rdet, a * rdet], axis=-1)
    return jnp.stack([row0, row1], axis=-2)


def inv3(M):
    m = [[M[..., i, j] for j in range(3)] for i in range(3)]
    c00 = m[1][1] * m[2][2] - m[1][2] * m[2][1]
    c01 = m[1][2] * m[2][0] - m[1][0] * m[2][2]
    c02 = m[1][0] * m[2][1] - m[1][1] * m[2][0]
    c10 = m[0][2] * m[2][1] - m[0][1] * m[2][2]
    c11 = m[0][0] * m[2][2] - m[0][2] * m[2][0]
    c12 = m[0][1] * m[2][0] - m[0][0] * m[2][1]
    c20 = m[0][1] * m[1][2] - m[0][2] * m[1][1]
    c21 = m[0][2] * m[1][0] - m[0][0] * m[1][2]
    c22 = m[0][0] * m[1][1] - m[0][1] * m[1][0]
    rdet = 1.0 / (m[0][0] * c00 + m[0][1] * c01 + m[0][2] * c02)
    rows = [
        jnp.stack([c00, c10, c20], axis=-1),
        jnp.stack([c01, c11, c21], axis=-1),
        jnp.stack([c02, c12, c22], axis=-1),
    ]
    return jnp.stack(rows, axis=-2) * rdet[..., None, None]


def inv4(M):
    """2x2-block Schur-complement inversion; mul/add + inv2 reciprocals."""
    A = M[..., :2, :2]
    B = M[..., :2, 2:]
    C = M[..., 2:, :2]
    D = M[..., 2:, 2:]
    Di = inv2(D)
    BDi = matmul(B, Di)
    S = A - matmul(BDi, C)  # Schur complement
    Si = inv2(S)
    SiBDi = matmul(Si, BDi)
    DiC = matmul(Di, C)
    top = jnp.concatenate([Si, -SiBDi], axis=-1)
    bot = jnp.concatenate([-matmul(DiC, Si), Di + matmul(DiC, SiBDi)],
                          axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


_SMALL_INV = {1: inv1, 2: inv2, 3: inv3, 4: inv4}


@functools.lru_cache(maxsize=None)
def triu_pack(n: int):
    """Upper-triangle packing plan for symmetric (..., n, n) einsum
    products — the einsum-stage analogue of the kernels'
    symmetrize=True triangle emission (ROADMAP item): compute only the
    n(n+1)/2 upper entries and reconstitute the full matrix by ALIASING
    the mirrors (exact symmetry, no averaging pass), cutting the
    dominant second contraction of F·P·Fᵀ-shaped products by
    ~n(n-1)/2n² ≈ 44% for n=9.

    Returns (rows, cols, mirror): ``rows``/``cols`` index the packed
    (i <= j) entries; ``mirror[i, j]`` is the packed index of
    (min(i,j), max(i,j)), so ``tri[..., mirror]`` is the one gather
    that unpacks a (..., T) triangle into the (..., n, n) symmetric
    matrix."""
    rows, cols = np.triu_indices(n)
    mirror = np.zeros((n, n), np.int32)
    for t, (i, j) in enumerate(zip(rows, cols)):
        mirror[i, j] = mirror[j, i] = t
    return rows, cols, mirror


def sym_unpack(tri, n: int):
    """(..., n(n+1)/2) packed upper triangle -> (..., n, n) symmetric
    matrix with aliased mirrors (see ``triu_pack``)."""
    _, _, mirror = triu_pack(n)
    return tri[..., mirror]


def small_inv(M, dim: int):
    if dim in _SMALL_INV:
        return _SMALL_INV[dim](M)
    return jnp.linalg.inv(M)  # general fallback (not used by the paper dims)


def small_det(M, dim: int):
    """Closed-form determinant of a (..., dim, dim) batch, dim <= 4 —
    pure mul/add (cofactor expansion; Schur product for dim=4), no
    factorization. Used for the IMM mode likelihoods: the Gaussian
    normalizer needs det(S), and this keeps it on the same
    matrix-pipeline discipline as ``small_inv`` (paper §IV-C)."""
    if dim == 1:
        return M[..., 0, 0]
    if dim == 2:
        return M[..., 0, 0] * M[..., 1, 1] - M[..., 0, 1] * M[..., 1, 0]
    if dim == 3:
        m = [[M[..., i, j] for j in range(3)] for i in range(3)]
        return (m[0][0] * (m[1][1] * m[2][2] - m[1][2] * m[2][1])
                + m[0][1] * (m[1][2] * m[2][0] - m[1][0] * m[2][2])
                + m[0][2] * (m[1][0] * m[2][1] - m[1][1] * m[2][0]))
    if dim == 4:
        D = M[..., 2:, 2:]
        S = M[..., :2, :2] - matmul(matmul(M[..., :2, 2:], inv2(D)),
                                    M[..., 2:, :2])
        return small_det(D, 2) * small_det(S, 2)
    return jnp.linalg.det(M)


# ---------------------------------------------------------------------------
# IMM mixing / mode-probability algebra (the "imm_bank" stage glue).
# Shared by the tracker bank (repro.core.bank), the kernel sequence
# runner (repro.kernels.katana_bank.ops) and the jnp oracle. Everything
# is einsum/mul/add over a (K, B, ...) model-major layout — the same
# static-shape discipline as the rest of the stage ladder.
# ---------------------------------------------------------------------------

_LOG_2PI = float(np.log(2.0 * np.pi))


def imm_mix(x, P, mu, Pi):
    """IMM interaction (mixing) step.

    x: (K, B, n) model-conditioned means; P: (K, B, n, n); mu: (B, K)
    mode probabilities; Pi: (K, K) row-stochastic transition matrix.
    Returns (x_mix (K, B, n), P_mix (K, B, n, n), cbar (B, K)) where
    cbar[b, j] = sum_i mu[b, i] Pi[i, j] is the predicted mode
    probability. The spread term (x_i - x_mix_j)(x_i - x_mix_j)^T keeps
    P_mix consistent (and PSD) under mode disagreement.
    """
    cbar = matmul(mu, Pi)                                    # (B, K)
    # cbar_j = 0 (a mode the chain cannot reach, e.g. an identity
    # transition with mu_j = 0) would divide 0/0 here; clamping the
    # denominator keeps w finite and exactly 0 for that column, and the
    # dead mode's posterior weight stays 0 via cbar in
    # imm_mode_posterior — no NaN ever enters the track state.
    cbar_safe = jnp.maximum(cbar, jnp.finfo(cbar.dtype).tiny)
    w = mu[:, :, None] * Pi[None, :, :] / cbar_safe[:, None, :]  # (B, i, j)
    x_mix = einsum("bij,ibd->jbd", w, x)
    dx = x[:, None] - x_mix[None, :]                         # (i, j, B, n)
    P_mix = (einsum("bij,ibuv->jbuv", w, P)
             + einsum("bij,ijbu,ijbv->jbuv", w, dx, dx))
    return x_mix, P_mix, cbar


def imm_mode_posterior(cbar, loglik):
    """Mode-probability update: mu'_k ∝ cbar_k exp(loglik_k), computed
    shift-stably (the max log-likelihood is subtracted before exp, so
    at least one mode always contributes a finite weight).

    cbar: (B, K); loglik: (K, B) per-mode measurement log-likelihoods.
    Returns mu' (B, K), rows summing to 1."""
    ll = jnp.swapaxes(loglik, 0, 1)                          # (B, K)
    w = cbar * jnp.exp(ll - ll.max(axis=1, keepdims=True))
    return w / w.sum(axis=1, keepdims=True)


def imm_combine(x, P, mu):
    """Moment-matched combined estimate: x_c = sum_k mu_k x_k and the
    mixture covariance with the spread term.

    x: (K, B, n); P: (K, B, n, n); mu: (B, K) -> (x_c (B, n),
    P_c (B, n, n))."""
    x_c = einsum("bk,kbd->bd", mu, x)
    dx = x - x_c[None]                                       # (K, B, n)
    P_c = (einsum("bk,kbuv->buv", mu, P)
           + einsum("bk,kbu,kbv->buv", mu, dx, dx))
    return x_c, P_c


def gaussian_loglik(y, Sinv, logdetS, m: int):
    """log N(y; 0, S) from the innovation y (..., m), the precomputed
    cofactor inverse Sinv (..., m, m) and log det S (...). No inversion
    happens here — the whole point is to reuse the S^{-1} the Kalman
    gain already paid for (predict_bank / the kernel's emitted Sinv)."""
    d = einsum("...u,...uv,...v->...", y, Sinv, y)
    return -0.5 * (d + logdetS + m * _LOG_2PI)


# ---------------------------------------------------------------------------
# Stage constants
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StageConstants:
    """Trace-time constants for opt1+ stages (paper's graph initializers)."""

    F: jnp.ndarray
    FT: jnp.ndarray
    H: jnp.ndarray
    HT: jnp.ndarray
    H_neg: jnp.ndarray
    H_negT: jnp.ndarray
    Q: jnp.ndarray
    R: jnp.ndarray
    I_n: jnp.ndarray


def stage_constants(model: FilterModel, dtype=jnp.float32) -> StageConstants:
    F = jnp.asarray(model.F, dtype)
    H = jnp.asarray(model.H, dtype)
    return StageConstants(
        F=F, FT=F.T, H=H, HT=H.T, H_neg=-H, H_negT=(-H).T,
        Q=jnp.asarray(model.Q, dtype), R=jnp.asarray(model.R, dtype),
        I_n=jnp.eye(model.n, dtype=dtype),
    )


def block_diag_batched(blocks: jnp.ndarray) -> jnp.ndarray:
    """(N, a, b) -> (N*a, N*b) block-diagonal (paper §IV-D expansion)."""
    N, a, b = blocks.shape
    out = jnp.zeros((N, a, N, b), blocks.dtype)
    idx = jnp.arange(N)
    out = out.at[idx, :, idx, :].set(blocks)
    return out.reshape(N * a, N * b)


def block_diag_const(M: np.ndarray, N: int) -> np.ndarray:
    """kron(I_N, M): replicate one block N times on the diagonal."""
    return np.kron(np.eye(N), M)


# ---------------------------------------------------------------------------
# Stage builders. Each returns step(x, P, z, sys?) -> (x, P) with the
# state layout documented per stage.
# ---------------------------------------------------------------------------

def build_baseline(model: FilterModel, dtype=jnp.float32,
                   symmetrize: bool = False) -> Tuple[Callable, Dict]:
    """Naive export. State: x (1, n, 1); P (1, n, n); z (1, m, 1).

    System matrices are *runtime tensors* (like un-folded initializers),
    so the Transposes, Subtracts and the generic inversion are real ops
    in the lowered graph — this is the graph the paper's Fig. 3 calls
    Baseline.
    """
    n, m = model.n, model.m
    sys = dict(
        F=jnp.asarray(model.F, dtype), H=jnp.asarray(model.H, dtype),
        Q=jnp.asarray(model.Q, dtype), R=jnp.asarray(model.R, dtype),
    )

    def step(x, P, z, sys=sys):
        F, H, Q, R = sys["F"], sys["H"], sys["Q"], sys["R"]
        # -- exporter-style shape bookkeeping (Squeeze/Unsqueeze/Reshape) --
        xs = jnp.reshape(x, (1, n))           # Squeeze
        if model.is_linear:
            x_pred = jnp.expand_dims(xs, -1)  # Unsqueeze
            x_pred = jnp.matmul(F, x_pred)    # (n,n)@(1,n,1)
        else:
            x_pred = jnp.expand_dims(model.predict_mean(xs), -1)
        Fk = model.jacobian(xs)               # (1, n, n)
        P_pred = jnp.matmul(jnp.matmul(Fk, P), jnp.transpose(Fk, (0, 2, 1))) + Q
        # -- innovation with runtime Subtract (the op the NPU's DSP eats) --
        y = z - jnp.matmul(H, x_pred)
        S = jnp.matmul(jnp.matmul(H, P_pred), jnp.transpose(H)) + R
        K = jnp.matmul(jnp.matmul(P_pred, jnp.transpose(H)), jnp.linalg.inv(S))
        x_new = x_pred + jnp.matmul(K, y)
        I = jnp.eye(n, dtype=dtype)
        P_new = jnp.matmul(I - jnp.matmul(K, H), P_pred)
        if symmetrize:
            P_new = 0.5 * (P_new + jnp.transpose(P_new, (0, 2, 1)))
        return jnp.reshape(x_new, (1, n, 1)), P_new

    meta = dict(stage="baseline", layout="dummy-batch", n=n, m=m)
    return step, meta


def build_opt1(model: FilterModel, dtype=jnp.float32,
               symmetrize: bool = False) -> Tuple[Callable, Dict]:
    """Subtract elimination (paper §IV-B). Same layout as baseline, but
    every ``a - b`` becomes ``a + neg(b)`` with the negation folded into
    a precomputed constant: H_neg for the innovation, and the covariance
    update rewritten ``P = P_pred + K (H_neg P_pred)``."""
    n, m = model.n, model.m
    sys = dict(
        F=jnp.asarray(model.F, dtype), H=jnp.asarray(model.H, dtype),
        H_neg=jnp.asarray(-model.H, dtype),
        Q=jnp.asarray(model.Q, dtype), R=jnp.asarray(model.R, dtype),
    )

    def step(x, P, z, sys=sys):
        F, H, H_neg = sys["F"], sys["H"], sys["H_neg"]
        Q, R = sys["Q"], sys["R"]
        xs = jnp.reshape(x, (1, n))
        if model.is_linear:
            x_pred = jnp.matmul(F, jnp.expand_dims(xs, -1))
        else:
            x_pred = jnp.expand_dims(model.predict_mean(xs), -1)
        Fk = model.jacobian(xs)
        P_pred = jnp.matmul(jnp.matmul(Fk, P), jnp.transpose(Fk, (0, 2, 1))) + Q
        # subtract-free innovation: z + H_neg x̂
        y = z + jnp.matmul(H_neg, x_pred)
        S = jnp.matmul(jnp.matmul(H, P_pred), jnp.transpose(H)) + R
        K = jnp.matmul(jnp.matmul(P_pred, jnp.transpose(H)), jnp.linalg.inv(S))
        x_new = x_pred + jnp.matmul(K, y)
        # subtract-free covariance: P + K (H_neg P)
        P_new = P_pred + jnp.matmul(K, jnp.matmul(H_neg, P_pred))
        if symmetrize:
            P_new = 0.5 * (P_new + jnp.transpose(P_new, (0, 2, 1)))
        return jnp.reshape(x_new, (1, n, 1)), P_new

    meta = dict(stage="opt1", layout="dummy-batch", n=n, m=m)
    return step, meta


def build_opt2(model: FilterModel, dtype=jnp.float32,
               symmetrize: bool = False) -> Tuple[Callable, Dict]:
    """Static tensor fusion (paper §IV-C). State: x (n,); P (n, n);
    z (m,). All system matrices and their transposes are trace-time
    constants; no dummy axes; cofactor inversion. The steady-state graph
    is exclusively dot/add/mul."""
    n, m = model.n, model.m
    C = stage_constants(model, dtype)

    def step(x, P, z):
        if model.is_linear:
            x_pred = C.F @ x
            P_pred = C.F @ P @ C.FT + C.Q
        else:
            x_pred = model.predict_mean(x)
            Fk = model.jacobian(x)
            P_pred = Fk @ P @ jnp.swapaxes(Fk, -1, -2) + C.Q
        y = z + C.H_neg @ x_pred
        PHt = P_pred @ C.HT
        S = C.H @ PHt + C.R
        K = PHt @ small_inv(S, m)
        x_new = x_pred + K @ y
        P_new = P_pred + K @ (C.H_neg @ P_pred)
        if symmetrize:
            P_new = 0.5 * (P_new + jnp.swapaxes(P_new, -1, -2))
        return x_new, P_new

    meta = dict(stage="opt2", layout="flat", n=n, m=m)
    return step, meta


def build_batched_blockdiag(model: FilterModel, N: int, dtype=jnp.float32,
                            symmetrize: bool = False) -> Tuple[Callable, Dict]:
    """Paper §IV-D, faithful: expand every per-filter matrix into an
    (N·n)x(N·n) block-diagonal system matrix and run ONE dense GEMM
    chain per step. State: x (N*n,); P (N*n, N*n); z (N*m,).

    For the LKF all block-diagonal system matrices are constants
    (folded, like the paper's ONNX initializers). For the EKF the
    Jacobian blocks are rebuilt each step and scattered onto the
    diagonal, exactly as the paper rebuilds its per-frame Jacobians.
    The S inversion is performed blockwise (cofactor) and scattered
    back to dense — the paper keeps "a single inversion" per recursion;
    a dense (N·m) inversion would change the numerics class, a
    blockwise one is exact.
    """
    n, m = model.n, model.m
    Nn, Nm = N * n, N * m
    F_bd = jnp.asarray(block_diag_const(model.F, N), dtype)
    FT_bd = F_bd.T
    H_bd = jnp.asarray(block_diag_const(model.H, N), dtype)
    HT_bd = H_bd.T
    Hneg_bd = -H_bd
    Q_bd = jnp.asarray(block_diag_const(model.Q, N), dtype)
    R_blocks = jnp.broadcast_to(jnp.asarray(model.R, dtype), (N, m, m))
    R_bd = block_diag_batched(R_blocks)

    def step(x, P, z):
        if model.is_linear:
            x_pred = F_bd @ x
            P_pred = F_bd @ P @ FT_bd + Q_bd  # dense (Nn)^3 GEMMs — the
            # paper's N^2 FLOP expansion, kept faithfully.
        else:
            xs = x.reshape(N, n)
            x_pred = model.predict_mean(xs).reshape(Nn)
            Fk_bd = block_diag_batched(model.jacobian(xs))
            P_pred = Fk_bd @ P @ Fk_bd.T + Q_bd
        y = z + Hneg_bd @ x_pred
        PHt = P_pred @ HT_bd
        S = H_bd @ PHt + R_bd  # (Nm, Nm), block-diagonal by construction
        S_blocks = extract_diag_blocks(S, N, m)
        Sinv_bd = block_diag_batched(small_inv(S_blocks, m))
        K = PHt @ Sinv_bd
        x_new = x_pred + K @ y
        P_new = P_pred + K @ (Hneg_bd @ P_pred)
        if symmetrize:
            P_new = 0.5 * (P_new + P_new.T)
        return x_new, P_new

    meta = dict(stage="batched_blockdiag", layout="blockdiag", n=n, m=m, N=N)
    return step, meta


def extract_diag_blocks(M: jnp.ndarray, N: int, b: int) -> jnp.ndarray:
    """(N*b, N*b) -> (N, b, b) diagonal blocks."""
    M4 = M.reshape(N, b, N, b)
    idx = jnp.arange(N)
    return M4[idx, :, idx, :]


def build_batched_lanes(model: FilterModel, N: int, dtype=jnp.float32,
                        symmetrize: bool = False) -> Tuple[Callable, Dict]:
    """Beyond-paper TPU-native batching: the filter index k lives on the
    minor (lane) axis and the per-filter n x n algebra is batched via
    einsum. State: x (N, n); P (N, n, n); z (N, m). Identical numerics
    to ``batched_blockdiag`` at ~N^2 less covariance compute; this is
    the reference semantics for the ``katana_bank`` Pallas kernel.

    Under ``symmetrize`` the covariance products are emitted
    upper-triangle-only with aliased mirrors (``triu_pack``), the same
    contract as the kernels' symmetrize=True: exact symmetry at
    n(n+1)/2 instead of n² second-contraction dots, no averaging pass.
    ``symmetrize=False`` keeps the faithful full-square emission
    (asymmetry of the float product preserved) for blockdiag
    equivalence."""
    n, m = model.n, model.m
    C = stage_constants(model, dtype)
    iu, ju, _ = triu_pack(n)

    def step(x, P, z):
        if model.is_linear:
            x_pred = jnp.einsum("ij,kj->ki", C.F, x)
            FP = jnp.einsum("ij,kjl->kil", C.F, P)
            if symmetrize:
                P_pred = sym_unpack(
                    jnp.einsum("ktl,tl->kt", FP[:, iu, :], C.F[ju, :])
                    + C.Q[iu, ju], n)
            else:
                P_pred = jnp.einsum("kil,jl->kij", FP, C.F) + C.Q
        else:
            x_pred = model.predict_mean(x)
            Fk = model.jacobian(x)  # (N, n, n)
            FP = jnp.einsum("kij,kjl->kil", Fk, P)
            if symmetrize:
                P_pred = sym_unpack(
                    jnp.einsum("ktl,ktl->kt", FP[:, iu, :], Fk[:, ju, :])
                    + C.Q[iu, ju], n)
            else:
                P_pred = jnp.einsum("kil,kjl->kij", FP, Fk) + C.Q
        y = z + jnp.einsum("mi,ki->km", C.H_neg, x_pred)
        PHt = jnp.einsum("kij,mj->kim", P_pred, C.H)
        S = jnp.einsum("mi,kij,nj->kmn", C.H, P_pred, C.H) + C.R
        K = jnp.einsum("kim,kmn->kin", PHt, small_inv(S, m))
        x_new = x_pred + jnp.einsum("kin,kn->ki", K, y)
        HnP = jnp.einsum("mi,kij->kmj", C.H_neg, P_pred)
        if symmetrize:
            P_new = sym_unpack(
                P_pred[:, iu, ju]
                + jnp.einsum("ktm,kmt->kt", K[:, iu, :], HnP[:, :, ju]), n)
        else:
            P_new = P_pred + jnp.einsum("kim,kmj->kij", K, HnP)
        return x_new, P_new

    meta = dict(stage="batched_lanes", layout="batched", n=n, m=m, N=N)
    return step, meta


def build_fused_scan(model: FilterModel, N: int, dtype=jnp.float32,
                     symmetrize: bool = False) -> Tuple[Callable, Dict]:
    """The Pallas ``katana_bank`` kernel as a stage. State: x (N, n);
    P (N, n, n); z (N, m) — canonical layout, same as batched_lanes.

    The per-step view dispatches the fused single-frame kernel; the
    sequence view (``run_sequence``) dispatches ONE multi-frame scan
    kernel for the whole stream — see
    ``repro.kernels.katana_bank.ops.katana_bank_sequence``. The kernel
    computes in f32 lanes regardless of ``dtype``.
    """
    from repro.kernels.katana_bank.ops import katana_bank

    n, m = model.n, model.m

    def step(x, P, z):
        return katana_bank(model, x, P, z, symmetrize=symmetrize)

    meta = dict(stage="fused_scan", layout="batched", n=n, m=m, N=N)
    return step, meta


def build_imm_bank(model, N: int, dtype=jnp.float32,
                   symmetrize: bool = True) -> Tuple[Callable, Dict]:
    """The IMM multi-model bank as a stage. A plain FilterModel is
    wrapped as a degenerate K=1 IMM (``as_imm``), so every single-model
    workload is also a valid imm_bank workload.

    Unlike the other stages the step carries mode probabilities:
    ``step(x (K, N, n), P (K, N, n, n), z (N, m), mu (N, K)) ->
    (x', P', mu')`` — one IMM cycle: mix -> fused multi-model kernel
    (predict+update+log-likelihood, stacked lanes) -> mode posterior.
    ``run_sequence`` adapts it to the canonical (N, n) layout by
    combining the per-model estimates each frame.
    """
    from repro.kernels.katana_bank.ops import katana_bank_imm

    imm = as_imm(model)
    Pi = jnp.asarray(imm.trans, dtype)

    def step(x, P, z, mu):
        x_mix, P_mix, cbar = imm_mix(x, P, mu, Pi)
        x_new, P_new, loglik = katana_bank_imm(imm, x_mix, P_mix, z,
                                               symmetrize=symmetrize)
        mu_new = imm_mode_posterior(cbar, loglik)
        return x_new, P_new, mu_new

    meta = dict(stage="imm_bank", layout="model-major", n=imm.n, m=imm.m,
                N=N, K=imm.K)
    return step, meta


def build_imm_scan(model, N: int, dtype=jnp.float32,
                   symmetrize: bool = True) -> Tuple[Callable, Dict]:
    """The fused IMM scan as a stage: same step signature as
    ``imm_bank`` (``step(x, P, z, mu) -> (x', P', mu')``), but the whole
    cycle — mixing, the K predict+updates, the mode posterior — runs
    inside ONE scan-kernel dispatch (at T=1 here; ``run_sequence``
    dispatches the whole stream at once). K=1 reduces exactly to
    ``fused_scan``."""
    from repro.kernels.katana_bank.ops import katana_imm_sequence

    imm = as_imm(model)

    def step(x, P, z, mu):
        _, (x2, P2, mu2) = katana_imm_sequence(
            imm, z[None], x, P, mu0=mu, symmetrize=symmetrize,
            return_final=True)
        return x2, P2, mu2

    meta = dict(stage="imm_scan", layout="model-block", n=imm.n, m=imm.m,
                N=N, K=imm.K)
    return step, meta


def build_stage(model: FilterModel, stage: str, N: Optional[int] = None,
                dtype=jnp.float32, symmetrize: bool = False):
    """Uniform entry point; returns (step, meta)."""
    if stage == "baseline":
        return build_baseline(model, dtype, symmetrize)
    if stage == "opt1":
        return build_opt1(model, dtype, symmetrize)
    if stage == "opt2":
        return build_opt2(model, dtype, symmetrize)
    if stage == "batched_blockdiag":
        assert N is not None
        return build_batched_blockdiag(model, N, dtype, symmetrize)
    if stage == "batched_lanes":
        assert N is not None
        return build_batched_lanes(model, N, dtype, symmetrize)
    if stage == "fused_scan":
        assert N is not None
        return build_fused_scan(model, N, dtype, symmetrize)
    if stage == "imm_bank":
        assert N is not None
        return build_imm_bank(model, N, dtype, symmetrize)
    if stage == "imm_scan":
        assert N is not None
        return build_imm_scan(model, N, dtype, symmetrize)
    raise KeyError(f"unknown stage {stage!r}; known: {STAGES}")


# ---------------------------------------------------------------------------
# Layout adapters: every stage exposes run_sequence() with the canonical
# (N, n) / (N, n, n) layout so tests and benches drive them uniformly.
# ---------------------------------------------------------------------------

def canonical_to_stage(stage: str, x, P, z, n: int, m: int):
    if stage in ("baseline", "opt1"):
        return x.reshape(1, n, 1), P.reshape(1, n, n), z.reshape(1, m, 1)
    if stage == "opt2":
        return x.reshape(n), P.reshape(n, n), z.reshape(m)
    if stage == "batched_blockdiag":
        N = x.shape[0]
        return x.reshape(N * n), block_diag_batched(P), z.reshape(N * m)
    return x, P, z  # batched_lanes / fused_scan are canonical


def stage_to_canonical(stage: str, x, P, n: int, m: int, N: int):
    if stage in ("baseline", "opt1"):
        return x.reshape(1, n), P.reshape(1, n, n)
    if stage == "opt2":
        return x.reshape(1, n), P.reshape(1, n, n)
    if stage == "batched_blockdiag":
        return x.reshape(N, n), extract_diag_blocks(P, N, n)
    return x, P


def run_sequence(model: FilterModel, stage: str, zs, x0, P0,
                 dtype=jnp.float32, symmetrize: bool = False):
    """Drive a stage over a (T, N, m) measurement sequence.

    x0: (N, n); P0: (N, n, n). N must be 1 for single-filter stages.
    Returns (T, N, n) filtered states (float32).
    """
    zs = jnp.asarray(zs, dtype)
    T, N, m = zs.shape
    n = model.n
    if stage in ("baseline", "opt1", "opt2"):
        assert N == 1, f"stage {stage} is single-filter"
    if stage == "fused_scan":
        # Sequence-native stage: one kernel dispatch for the whole
        # stream instead of a lax.scan over per-frame steps.
        from repro.kernels.katana_bank.ops import katana_bank_sequence

        return katana_bank_sequence(model, zs, jnp.asarray(x0, dtype),
                                    jnp.asarray(P0, dtype),
                                    symmetrize=symmetrize)
    if stage == "imm_bank":
        # Multi-model stage: (x0, P0) seed every mode identically; the
        # returned track is the moment-matched combined estimate.
        from repro.kernels.katana_bank.ops import imm_bank_sequence

        return imm_bank_sequence(as_imm(model), zs, jnp.asarray(x0, dtype),
                                 jnp.asarray(P0, dtype),
                                 symmetrize=symmetrize)
    if stage == "imm_scan":
        # Sequence-native multi-model stage: the whole stream (mixing
        # and mode posterior included) through one kernel dispatch.
        from repro.kernels.katana_bank.ops import katana_imm_sequence

        return katana_imm_sequence(as_imm(model), zs, jnp.asarray(x0, dtype),
                                   jnp.asarray(P0, dtype),
                                   symmetrize=symmetrize)
    step, _ = build_stage(model, stage, N=N, dtype=dtype, symmetrize=symmetrize)

    x, P, _ = canonical_to_stage(stage, jnp.asarray(x0, dtype),
                                 jnp.asarray(P0, dtype),
                                 jnp.zeros((N, m), dtype), n, m)

    def scan_body(carry, z_t):
        x, P = carry
        _, _, z_s = canonical_to_stage(stage, jnp.zeros((N, n), dtype),
                                       jnp.zeros((N, n, n), dtype), z_t, n, m)
        x, P = step(x, P, z_s)
        x_c, _ = stage_to_canonical(stage, x, P, n, m, N)
        return (x, P), x_c

    (_, _), xs = jax.lax.scan(scan_body, (x, P), zs)
    return xs
