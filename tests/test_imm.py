"""IMM multi-model bank: kernel vs oracles, degenerate cases, tracker
integration, and the accuracy claim on the maneuvering-target scene."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ref as oref
from repro.core.filters import (as_imm, get_filter, make_ca9_lkf,
                                make_ct9_lkf, make_cv9_lkf, make_imm)
from repro.core.rewrites import imm_combine, imm_mix, run_sequence, small_det
from repro.core.tracker import TrackerConfig, make_jitted_imm_tracker
from repro.data.trajectories import maneuvering_batch, maneuvering_target
from repro.kernels.katana_bank.kernel import plan_imm_tables
from repro.kernels.katana_bank.ops import (imm_bank_sequence, katana_bank_imm,
                                           katana_bank_sequence)
from repro.kernels.katana_bank.ref import katana_imm_ref


def _random_states(imm, N, seed=0):
    rng = np.random.default_rng(seed)
    K, n, m = imm.K, imm.n, imm.m
    x = jnp.asarray(rng.normal(size=(K, N, n)), jnp.float32)
    A = rng.normal(size=(K, N, n, n)) * 0.3
    P = jnp.asarray(A @ A.transpose(0, 1, 3, 2) + np.eye(n), jnp.float32)
    z = jnp.asarray(rng.normal(size=(N, m)), jnp.float32)
    return x, P, z


# ------------------------------------------------------------ kernel step
@pytest.mark.parametrize("N", [1, 7, 64, 130])  # incl. non-tile multiples
def test_imm_kernel_matches_jnp_ref(N):
    """Stacked-lane multi-model kernel == per-model einsum oracle,
    states, covariances AND log-likelihoods (the kernel's Sinv/det reuse
    is exact)."""
    imm = make_imm()
    x, P, z = _random_states(imm, N, seed=N)
    xk, Pk, llk = katana_bank_imm(imm, x, P, z, lane_tile=128)
    xr, Pr, llr = katana_imm_ref(imm, x, P, z)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(Pk), np.asarray(Pr),
                               atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(llk), np.asarray(llr),
                               atol=5e-5, rtol=2e-4)


def test_plan_imm_tables_folds_shared_entries():
    """Entries identical across models stay trace-time floats; only the
    genuinely differing entries consume table rows."""
    imm = make_imm()
    entries, V = plan_imm_tables(imm.models)
    # R is identical for every member model -> fully folded
    assert all(isinstance(c, float) for row in entries["R"] for c in row)
    # F differs (CV/CA/CT dynamics) -> some varying entries exist
    f_vars = [c for row in entries["F"] for c in row if not
              isinstance(c, float)]
    assert f_vars, "expected varying F entries across CV/CA/CT"
    # every varying reference resolves into V
    for tag, e in f_vars:
        assert tag == "var" and 0 <= e < V.shape[0]
    # shared diagonal example: F[5][5] == 1.0 in all four models
    assert entries["F"][5][5] == 1.0


# ----------------------------------------------------- sequence vs oracle
def test_imm_sequence_matches_float64_oracle():
    """imm_bank (mix -> fused kernel -> mode posterior) tracks the
    textbook float64 IMM recursion at fused-scan tolerances."""
    imm = make_imm()
    rng = np.random.default_rng(3)
    T, N = 60, 5
    zs = rng.normal(size=(T, N, imm.m)) * 0.5
    x0 = np.tile(imm.x0, (N, 1))
    P0 = np.tile(imm.P0, (N, 1, 1))
    want, _ = oref.run_imm_batched(imm, zs, x0, P0)
    got = np.asarray(imm_bank_sequence(
        imm, jnp.asarray(zs, jnp.float32), jnp.asarray(x0, jnp.float32),
        jnp.asarray(P0, jnp.float32), lane_tile=128))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kind", ["cv9", "ekf"])
def test_imm_k1_reduces_to_plain_bank(kind):
    """K=1 IMM == the existing single-model fused bank (mixing with one
    mode is the identity; mu stays 1) — including the nonlinear EKF
    member via the K=1 kernel delegation."""
    model = get_filter(kind)
    rng = np.random.default_rng(7)
    T, N = 40, 6
    zs = jnp.asarray(rng.normal(size=(T, N, model.m)) * 0.5, jnp.float32)
    x0 = jnp.asarray(np.tile(model.x0, (N, 1)), jnp.float32)
    P0 = jnp.asarray(np.tile(model.P0, (N, 1, 1)), jnp.float32)
    got = np.asarray(imm_bank_sequence(as_imm(model), zs, x0, P0,
                                       lane_tile=128))
    plain = np.asarray(katana_bank_sequence(model, zs, x0, P0,
                                            lane_tile=128))
    np.testing.assert_allclose(got, plain, atol=1e-6, rtol=1e-6)


def test_imm_stage_in_run_sequence():
    """The 'imm_bank' rewrites stage is driveable through the uniform
    run_sequence entry point with an IMMModel."""
    imm = make_imm()
    rng = np.random.default_rng(11)
    T, N = 30, 4
    zs = rng.normal(size=(T, N, imm.m)) * 0.5
    x0 = np.tile(imm.x0, (N, 1))
    P0 = np.tile(imm.P0, (N, 1, 1))
    got = np.asarray(run_sequence(imm, "imm_bank", zs, x0, P0))
    want, _ = oref.run_imm_batched(imm, zs, x0, P0)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------- IMM algebra
def test_imm_mix_preserves_normalization_and_psd():
    """Mixing keeps mode probabilities normalized and mixed covariances
    PSD (the spread term does its job)."""
    imm = make_imm()
    K, n = imm.K, imm.n
    rng = np.random.default_rng(5)
    B = 6
    x = jnp.asarray(rng.normal(size=(K, B, n)), jnp.float32)
    A = rng.normal(size=(K, B, n, n)) * 0.3
    P = jnp.asarray(A @ A.transpose(0, 1, 3, 2) + np.eye(n), jnp.float32)
    mu = rng.random((B, K)) + 0.1
    mu = jnp.asarray(mu / mu.sum(1, keepdims=True), jnp.float32)
    x_mix, P_mix, cbar = imm_mix(x, P, mu, jnp.asarray(imm.trans, jnp.float32))
    np.testing.assert_allclose(np.asarray(cbar).sum(1), 1.0, atol=1e-6)
    Pm = np.asarray(P_mix)
    for k in range(K):
        for b in range(B):
            np.testing.assert_allclose(Pm[k, b], Pm[k, b].T, atol=1e-5)
            assert np.linalg.eigvalsh(Pm[k, b]).min() > -1e-4


def test_imm_mix_survives_unreachable_mode():
    """A mode the chain cannot reach (identity transition + zero mode
    probability) must not divide 0/0 into NaN: mixing stays finite and
    the dead mode's posterior weight stays exactly 0."""
    import numpy as _np

    from repro.core.filters import IMMModel
    from repro.core.rewrites import imm_mode_posterior

    cv = make_cv9_lkf()
    ca = make_ca9_lkf()
    imm = IMMModel(name="frozen", models=(cv, ca), trans=_np.eye(2),
                   mu0=_np.array([1.0, 0.0]))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 9)),
                    jnp.float32)
    P = jnp.broadcast_to(jnp.eye(9), (2, 3, 9, 9)).astype(jnp.float32)
    mu = jnp.asarray(np.tile(imm.mu0, (3, 1)), jnp.float32)
    x_mix, P_mix, cbar = imm_mix(x, P, mu, jnp.asarray(imm.trans,
                                                       jnp.float32))
    assert np.isfinite(np.asarray(x_mix)).all()
    assert np.isfinite(np.asarray(P_mix)).all()
    mu2 = imm_mode_posterior(cbar, jnp.zeros((2, 3), jnp.float32))
    np.testing.assert_allclose(np.asarray(mu2), np.tile([1.0, 0.0], (3, 1)),
                               atol=0)


def test_small_det_matches_numpy():
    rng = np.random.default_rng(2)
    for dim in (1, 2, 3, 4):
        A = rng.normal(size=(16, dim, dim))
        A = A @ np.swapaxes(A, -1, -2) + 3 * np.eye(dim)
        got = np.asarray(small_det(jnp.asarray(A, jnp.float32), dim))
        np.testing.assert_allclose(got, np.linalg.det(A), rtol=1e-4)


# ------------------------------------------------------------ accuracy win
def test_imm_beats_single_cv_on_maneuvering_scene():
    """The headline claim: on the CV/CT/CA switching scene the IMM bank
    has materially lower position RMSE than the single-model CV LKF."""
    T, N = 96, 8
    truth, zs = maneuvering_batch(T, N, seed=1)
    cv = get_filter("lkf")
    imm = make_imm()
    zsf = jnp.asarray(zs, jnp.float32)
    xc = jnp.asarray(np.tile(cv.x0, (N, 1)), jnp.float32)
    Pc = jnp.asarray(np.tile(cv.P0, (N, 1, 1)), jnp.float32)
    xi = jnp.asarray(np.tile(imm.x0, (N, 1)), jnp.float32)
    Pi = jnp.asarray(np.tile(imm.P0, (N, 1, 1)), jnp.float32)
    est_cv = np.asarray(katana_bank_sequence(cv, zsf, xc, Pc, lane_tile=128))
    est_imm = np.asarray(imm_bank_sequence(imm, zsf, xi, Pi, lane_tile=128))
    warm = 20

    def rmse(est):
        return np.sqrt(np.mean((est[warm:, :, :3] - truth[warm:, :, :3]) ** 2))

    assert rmse(est_imm) < 0.75 * rmse(est_cv), \
        (rmse(est_imm), rmse(est_cv))


def test_imm_mode_probs_follow_the_maneuver():
    """On a long coordinated-turn segment the CT hypotheses dominate the
    CV hypothesis (the mode chain identifies the maneuver)."""
    imm = make_imm(omega=0.7)
    T = 120
    rng = np.random.default_rng(0)
    # pure CT+ truth at exactly the model's turn rate
    p = np.zeros(3)
    v = np.array([3.0, 0.0, 0.0])
    dt, w = imm.dt, 0.7
    zs = np.zeros((T, 3))
    for t in range(T):
        c, s = np.cos(w * dt), np.sin(w * dt)
        v = np.array([c * v[0] - s * v[1], s * v[0] + c * v[1], v[2]])
        p = p + v * dt
        zs[t] = p + 0.05 * rng.normal(size=3)
    _, mus = oref.run_imm(imm, zs)
    # modes: 0=CV, 1=CA, 2=CT(+w), 3=CT(-w)
    assert mus[-1, 2] > mus[-1, 0]
    assert mus[-1, 2] > mus[-1, 3]


# ---------------------------------------------------------------- tracker
def test_imm_tracker_confirms_maneuvering_targets():
    imm = make_imm()
    cfg = TrackerConfig(capacity=16, max_meas=8)
    T, N = 60, 3
    truth, zs = maneuvering_batch(T, N, seed=5)
    init, step = make_jitted_imm_tracker(imm, cfg)
    bank = init()
    for t in range(T):
        z = np.zeros((cfg.max_meas, 3), np.float32)
        v = np.zeros(cfg.max_meas, bool)
        z[:N] = zs[t]
        v[:N] = True
        res = step(bank, jnp.asarray(z), jnp.asarray(v))
        bank = res.bank
    assert int(res.confirmed.sum()) == N
    # combined estimate lands near the truth for each confirmed track
    est = np.asarray(res.x_est)[np.asarray(res.confirmed)]
    err = np.abs(est[:, None, :3] - truth[-1][None, :, :3]).sum(-1).min(1)
    assert (err < 1.0).all(), err
    # mode probabilities are a distribution per track
    mu = np.asarray(res.mode_probs)[np.asarray(res.confirmed)]
    np.testing.assert_allclose(mu.sum(1), 1.0, atol=1e-5)


def test_imm_tracker_mode_probs_stay_normalized_under_coasting():
    """With no measurements at all (pure coasting) the mode probability
    update is the Markov prediction cbar — rows keep summing to 1 and
    never go NaN, until the tracks prune away."""
    imm = make_imm()
    cfg = TrackerConfig(capacity=8, max_meas=4, max_misses=20)
    init, step = make_jitted_imm_tracker(imm, cfg)
    bank = init()
    # spawn two tracks
    z = np.zeros((4, 3), np.float32)
    z[:2] = [[1.0, 2.0, 0.0], [-3.0, 0.5, 1.0]]
    v = np.array([True, True, False, False])
    res = step(bank, jnp.asarray(z), jnp.asarray(v))
    bank = res.bank
    # coast for 10 frames
    for _ in range(10):
        res = step(bank, jnp.zeros((4, 3), jnp.float32), jnp.zeros(4, bool))
        bank = res.bank
        mu = np.asarray(bank.mu)
        assert np.isfinite(mu).all()
        act = np.asarray(bank.active)
        assert act[:2].all()  # max_misses=20: still alive
        np.testing.assert_allclose(mu[act].sum(1), 1.0, atol=1e-5)


def test_imm_engine_snapshots_carry_mode_probs():
    from repro.serving.engine import TrackingEngine

    imm = make_imm()
    eng = TrackingEngine(imm, TrackerConfig(capacity=8, max_meas=4,
                                            min_hits=2))
    _, zs = maneuvering_target(30, seed=9)
    snaps = []
    for t in range(30):
        snaps = eng.submit(zs[t][None, :])
    assert len(snaps) == 1
    assert snaps[0].mode_probs is not None
    np.testing.assert_allclose(snaps[0].mode_probs.sum(), 1.0, atol=1e-5)
    assert snaps[0].state.shape == (imm.n,)
    # replay goes through imm_bank_sequence
    out = eng.replay(zs[:10][:, None, :])
    assert out.shape == (10, 1, imm.n)


def test_update_imm_bank_recompute_fallback_matches_passthrough():
    """``update_imm_bank``'s standalone path (z_pred/PHt/Sinv/S/cbar =
    None) rebuilds the innovation quantities from the predicted bank
    with the same expressions ``predict_imm_bank`` uses — updates must
    come out bit-identical to the pass-through, every combination of
    missing tensors."""
    from repro.core import bank as bank_lib

    imm = make_imm()
    rng = np.random.default_rng(5)
    C, M = 10, 5
    bank = bank_lib.init_imm_bank(imm, C)
    bank = bank._replace(
        active=jnp.asarray(rng.random(C) < 0.7),
        x=jnp.asarray(rng.normal(size=(imm.K, C, imm.n)) * 0.4, jnp.float32),
        mu=jnp.asarray(rng.dirichlet(np.ones(imm.K), C), jnp.float32))
    bank_p, z_pred, S, Sinv, PHt, cbar = bank_lib.predict_imm_bank(imm, bank)
    z = jnp.asarray(rng.normal(size=(M, imm.m)) * 0.4, jnp.float32)
    assoc = jnp.asarray(rng.integers(-1, M, size=C), jnp.int32)
    ref = bank_lib.update_imm_bank(imm, bank_p, z, assoc, z_pred, PHt, Sinv,
                                   S, cbar)
    cases = (
        dict(),                                              # all recomputed
        dict(z_pred=z_pred, PHt=PHt),                        # partial
        dict(z_pred=z_pred, PHt=PHt, Sinv=Sinv, S=S),        # only cbar
    )
    for kw in cases:
        got = bank_lib.update_imm_bank(imm, bank_p, z, assoc, **kw)
        np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))
        np.testing.assert_array_equal(np.asarray(got.P), np.asarray(ref.P))
        np.testing.assert_array_equal(np.asarray(got.mu), np.asarray(ref.mu))
        np.testing.assert_array_equal(np.asarray(got.hits),
                                      np.asarray(ref.hits))
