"""The benchmark's float64 reference agrees with the program's own
float64 oracle (``repro.core.ref``) and its assignment rule."""
import numpy as np
import pytest

import reference


def _program_models():
    from repro.core.filters import make_cv_lkf, make_imm

    return make_cv_lkf(dt=1 / 30), make_imm(dt=1 / 25)


def test_models_match_the_program_configuration():
    lkf, imm = _program_models()
    r = reference.cv6(1 / 30, 0.01, 0.1, 1.0)
    np.testing.assert_array_equal(r.F[0], lkf.F)
    np.testing.assert_allclose(r.Q[0], lkf.Q, rtol=1e-12, atol=1e-20)
    np.testing.assert_array_equal(r.R, lkf.R)
    ri = reference.imm4(1 / 25, 0.01, 0.5, 0.7, 0.95, 0.1, 1.0)
    for k, mdl in enumerate(imm.models):
        np.testing.assert_allclose(ri.F[k], mdl.F, rtol=0, atol=1e-15)
        np.testing.assert_allclose(ri.Q[k], mdl.Q, rtol=1e-12, atol=1e-20)
    np.testing.assert_array_equal(ri.trans, imm.trans)


@pytest.mark.parametrize("has_z", [True, False])
def test_imm_cycle_matches_core_ref(has_z):
    from repro.core import ref

    _, imm = _program_models()
    r = reference.imm4(1 / 25, 0.01, 0.5, 0.7, 0.95, 0.1, 1.0)
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(4, 9))
    Ps = np.stack([np.eye(9) * (1 + k) for k in range(4)])
    mu = np.array([0.4, 0.3, 0.2, 0.1])
    z = rng.normal(size=3)
    want = ref.imm_step(imm, xs, Ps, mu, z, has_z=has_z)
    xp, Pp, cbar = reference._predict(r, xs[None], Ps[None], mu[None])
    zp, det, Sinv = reference._innovation(r, xp, Pp)
    if has_z:
        x, P, ll = reference._update(r, xp, Pp, zp, det, Sinv, z[None])
        mu_new = reference._posterior(cbar, ll)
    else:
        x, P, mu_new = xp, Pp, cbar
    x, P, mu_new = x[0], P[0], mu_new[0]
    np.testing.assert_allclose(x, want[0], rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(P, want[1], rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(mu_new, want[2], rtol=1e-9, atol=1e-12)


def _gap(cost, a, active, zvalid, gate=11.34):
    """assoc_gap on one lane given dense (C, M) costs."""
    rows = np.flatnonzero(active)
    bad = bool((a[~active] >= 0).any()) or len(
        set(a[a >= 0])) < (a >= 0).sum()
    return reference.assoc_gap(cost[rows], np.zeros(len(rows), int),
                               a[rows], zvalid[None], gate,
                               np.array([bad]))[0]


def test_assoc_gap_is_zero_exactly_for_the_greedy_assignment():
    import jax.numpy as jnp

    from repro.core.tracker import greedy_assign

    rng = np.random.default_rng(2)
    for trial in range(20):
        C, M = 12, 9
        cost = rng.uniform(0, 20, (C, M))
        active = rng.random(C) < 0.8
        zvalid = rng.random(M) < 0.8
        valid = active[:, None] & zvalid[None, :]
        a = np.asarray(greedy_assign(jnp.asarray(cost, jnp.float32),
                                     jnp.asarray(valid), 11.34, min(C, M)))
        cost32 = cost.astype(np.float32).astype(np.float64)
        assert _gap(cost32, a, active, zvalid) == 0.0, trial
        # move one chosen pair to another gated measurement: a violation
        ch = np.flatnonzero(a >= 0)
        if len(ch) and (~np.isin(np.arange(M), a) & zvalid).any():
            b = a.copy()
            free = np.flatnonzero(~np.isin(np.arange(M), a) & zvalid)
            b[ch[0]] = free[0]
            assert _gap(cost32, b, active, zvalid) > 0.0
        # an impossible choice: a measurement used twice
        if len(ch) > 1:
            b = a.copy()
            b[ch[1]] = b[ch[0]]
            assert np.isinf(_gap(cost32, b, active, zvalid))
