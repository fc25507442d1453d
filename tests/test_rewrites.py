"""Stage-equivalence: every KATANA rewrite is an exact algebraic
transform — all stages must track the float64 oracle, and hypothesis
sweeps random linear systems through the rewrite algebra."""
import numpy as np
import pytest
from _hypothesis_compat import example, given, settings, st

import jax.numpy as jnp

from repro.core import ref
from repro.core.filters import FilterModel, get_filter
from repro.core.rewrites import (
    STAGES,
    block_diag_batched,
    build_stage,
    extract_diag_blocks,
    run_sequence,
    small_inv,
)

TOL = 2e-4  # fp32 vs fp64 over 50 recursions


@pytest.mark.parametrize("kind", ["lkf", "ekf"])
@pytest.mark.parametrize("stage", STAGES)
def test_stage_matches_oracle(kind, stage):
    model = get_filter(kind)
    rng = np.random.default_rng(0)
    T = 50
    N = 1 if stage in ("baseline", "opt1", "opt2") else 8
    zs = rng.normal(size=(T, N, model.m)) * 0.5
    x0 = np.tile(model.x0, (N, 1)) + rng.normal(size=(N, model.n)) * 0.1
    P0 = np.tile(model.P0, (N, 1, 1))
    want, _, _ = ref.run_batched(model, zs, x0, P0)
    got = np.asarray(run_sequence(model, stage, zs, x0, P0))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("kind", ["lkf", "ekf"])
def test_blockdiag_equals_lanes(kind):
    """Paper batching and TPU-native batching are numerically twins."""
    model = get_filter(kind)
    rng = np.random.default_rng(1)
    T, N = 30, 16
    zs = rng.normal(size=(T, N, model.m)) * 0.5
    x0 = np.tile(model.x0, (N, 1)) + rng.normal(size=(N, model.n)) * 0.1
    P0 = np.tile(model.P0, (N, 1, 1))
    bd = np.asarray(run_sequence(model, "batched_blockdiag", zs, x0, P0))
    ln = np.asarray(run_sequence(model, "batched_lanes", zs, x0, P0))
    np.testing.assert_allclose(bd, ln, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("dim", [1, 2, 3, 4])
def test_small_inv_matches_numpy(dim):
    rng = np.random.default_rng(dim)
    A = rng.normal(size=(32, dim, dim))
    A = A @ np.swapaxes(A, -1, -2) + 3 * np.eye(dim)  # well-conditioned SPD
    got = np.asarray(small_inv(jnp.asarray(A, jnp.float32), dim))
    want = np.linalg.inv(A)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)


@given(st.integers(1, 12), st.integers(1, 5), st.integers(1, 5),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_blockdiag_roundtrip(N, a, b, seed):
    rng = np.random.default_rng(seed)
    blocks = rng.normal(size=(N, a, b)).astype(np.float32)
    bd = np.asarray(block_diag_batched(jnp.asarray(blocks)))
    assert bd.shape == (N * a, N * b)
    # diagonal blocks round-trip; off-diagonal blocks are zero
    if a == b:
        back = np.asarray(extract_diag_blocks(jnp.asarray(bd), N, a))
        np.testing.assert_allclose(back, blocks)
    mask = np.kron(np.eye(N), np.ones((a, b)))
    np.testing.assert_allclose(bd * (1 - mask), 0)


@given(st.integers(2, 4), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
@example(m=3, seed=36632)
def test_random_linear_system_stage_equivalence(m, seed):
    """hypothesis: random stable linear systems — opt2 == oracle.

    The systems are well conditioned, as float32 needs: F is a
    contraction (spectral norm 0.9, no transient growth) and H's
    singular values lie in [0.5, 2]. A random Gaussian H can be nearly
    rank-deficient (seed 36632 drew cond(H) = 737, so cond(S) = 6.6e3),
    and opt2's closed-form cofactor inverse then loses most of its
    float32 digits to cancellation in det(S)."""
    rng = np.random.default_rng(seed)
    n = m + rng.integers(0, 3)
    A = rng.normal(size=(n, n))
    F = 0.9 * A / np.linalg.norm(A, 2)
    U, _, Vt = np.linalg.svd(rng.normal(size=(m, n)), full_matrices=False)
    H = U @ np.diag(rng.uniform(0.5, 2.0, size=m)) @ Vt
    Q = np.eye(n) * 10.0 ** rng.uniform(-4, -1)
    R = np.eye(m) * 10.0 ** rng.uniform(-3, 0)
    model = FilterModel(
        name="rand", n=n, m=m, is_linear=True, F=F, H=H, Q=Q, R=R,
        x0=np.zeros(n), P0=np.eye(n))
    zs = rng.normal(size=(20, 1, m))
    x0 = np.zeros((1, n))
    P0 = np.tile(model.P0, (1, 1, 1))
    want, _, _ = ref.run_batched(model, zs, x0, P0)
    got = np.asarray(run_sequence(model, "opt2", zs, x0, P0))
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-2)


# ---------------------------------------------------------------------------
# Property-based IMM algebra invariants (rewrites.imm_*). These are the
# contracts every fused path (imm_bank / imm_scan kernels, the sharded
# serving engine) inherits — run via tests/_hypothesis_compat, so they
# degrade to fixed-seed parametrized cases when hypothesis is absent.
# ---------------------------------------------------------------------------

def _imm_random(K, B, n, rng, dirichlet=True):
    """Random mode-conditioned states: x (K, B, n), PSD P (K, B, n, n),
    normalized mu (B, K), row-stochastic Pi (K, K)."""
    x = rng.normal(size=(K, B, n)).astype(np.float32)
    A = rng.normal(size=(K, B, n, n)) * 0.4
    P = (A @ A.transpose(0, 1, 3, 2) + np.eye(n)).astype(np.float32)
    mu = (rng.random((B, K)) + 1e-3).astype(np.float32)
    mu /= mu.sum(1, keepdims=True)
    Pi = (rng.random((K, K)) + 1e-3).astype(np.float32)
    Pi /= Pi.sum(1, keepdims=True)
    return x, P, mu, Pi


@given(st.integers(1, 5), st.integers(1, 6), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_prop_imm_mode_posterior_normalized_nonnegative(K, B, seed):
    """mu' = posterior(cbar, loglik) is a distribution for ANY finite
    log-likelihoods (the shift-stable exp never over/underflows all
    modes at once): rows sum to 1, entries in [0, 1], no NaN."""
    from repro.core.rewrites import imm_mode_posterior

    rng = np.random.default_rng(seed)
    _, _, cbar, _ = _imm_random(K, B, 2, rng)
    # wild dynamic range, incl. the hugely-negative logliks a gated-out
    # mode produces
    loglik = (rng.uniform(-1e4, 1e2, size=(K, B))).astype(np.float32)
    mu = np.asarray(imm_mode_posterior(jnp.asarray(cbar),
                                       jnp.asarray(loglik)))
    assert np.isfinite(mu).all()
    assert (mu >= 0).all() and (mu <= 1 + 1e-6).all()
    np.testing.assert_allclose(mu.sum(1), 1.0, atol=1e-5)


@given(st.integers(1, 4), st.integers(1, 5), st.integers(2, 6),
       st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_prop_imm_combine_covariance_symmetric_psd(K, B, n, seed):
    """The moment-matched mixture covariance is symmetric PSD whenever
    the per-mode covariances are (the spread term can only ADD
    dispersion), and the mean is inside the convex hull of the
    per-mode means."""
    from repro.core.rewrites import imm_combine

    rng = np.random.default_rng(seed)
    x, P, mu, _ = _imm_random(K, B, n, rng)
    x_c, P_c = imm_combine(jnp.asarray(x), jnp.asarray(P), jnp.asarray(mu))
    x_c, P_c = np.asarray(x_c), np.asarray(P_c)
    assert np.isfinite(P_c).all()
    for b in range(B):
        np.testing.assert_allclose(P_c[b], P_c[b].T, atol=1e-4)
        assert np.linalg.eigvalsh(P_c[b].astype(np.float64)).min() > -1e-3
        assert (x_c[b] <= x[:, b].max(0) + 1e-5).all()
        assert (x_c[b] >= x[:, b].min(0) - 1e-5).all()


@given(st.integers(2, 4), st.integers(1, 5), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_prop_imm_mix_permutation_equivariant(K, B, seed):
    """Relabeling the K models (permuting x/P slabs, mu columns, and
    both axes of the transition matrix) permutes imm_mix's outputs the
    same way — the mixing algebra carries no hidden model-order
    dependence. Exercised with n=4 states."""
    from repro.core.rewrites import imm_mix

    n = 4
    rng = np.random.default_rng(seed)
    x, P, mu, Pi = _imm_random(K, B, n, rng)
    perm = rng.permutation(K)
    xm, Pm, cbar = imm_mix(jnp.asarray(x), jnp.asarray(P), jnp.asarray(mu),
                           jnp.asarray(Pi))
    xm2, Pm2, cbar2 = imm_mix(jnp.asarray(x[perm]), jnp.asarray(P[perm]),
                              jnp.asarray(mu[:, perm]),
                              jnp.asarray(Pi[np.ix_(perm, perm)]))
    np.testing.assert_allclose(np.asarray(xm2), np.asarray(xm)[perm],
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(Pm2), np.asarray(Pm)[perm],
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(cbar2), np.asarray(cbar)[:, perm],
                               atol=1e-6)


@pytest.mark.parametrize("kind", ["lkf", "ekf"])
def test_covariance_stays_psd(kind):
    model = get_filter(kind)
    rng = np.random.default_rng(2)
    N, T = 4, 80
    step, _ = build_stage(model, "batched_lanes", N=N, symmetrize=True)
    x = jnp.asarray(np.tile(model.x0, (N, 1)), jnp.float32)
    P = jnp.asarray(np.tile(model.P0, (N, 1, 1)), jnp.float32)
    for t in range(T):
        z = jnp.asarray(rng.normal(size=(N, model.m)), jnp.float32)
        x, P = step(x, P, z)
    Pn = np.asarray(P)
    for k in range(N):
        np.testing.assert_allclose(Pn[k], Pn[k].T, atol=1e-5)
        assert np.linalg.eigvalsh(Pn[k]).min() > -1e-5
