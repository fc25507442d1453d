"""Sharded multi-sensor IMM engine (serving/engine.ShardedBankEngine).

The serving tentpole: ``imm_frame_step`` vmapped over the sensor axis,
the (K, S, C, n) IMM bank shard_mapped over the mesh data axes, and a
sharded fused replay. Everything here is equivalence against the
unsharded per-sensor oracles:

  * the vmapped fleet == a python loop of single-sensor frame steps
    (runs on any device count — the always-on tier-1 leg);
  * the shard_mapped fleet == the vmapped fleet, bitwise (needs >= 4
    local devices — CI runs this under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
  * K=1 reduces to the single-model sharded path;
  * ``replay`` == per-sensor ``replay_imm_bank`` on coasting-masked
    streams, one fused dispatch per track batch per shard;
  * multi-sensor lifecycle: sensors that disagree (one spawns while
    another coasts/prunes) keep their shared-across-hypotheses track
    ids exactly in lockstep with the unsharded oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.core import bank as bank_lib
from repro.core.bank import IMMBankState, init_imm_bank, replay_imm_bank
from repro.core.filters import as_imm, make_cv9_lkf, make_imm
from repro.core.tracker import TrackerConfig, frame_step, imm_frame_step
from repro.serving.engine import ShardedBankEngine

CFG = TrackerConfig(capacity=8, max_meas=4)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 local devices (run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return make_mesh((4,), ("data",))


def _fleet_scene(S, T, cfg=CFG, seed=0, targets=2, drop=()):
    """(T, S, max_meas, m) measurement streams: `targets` slow walkers
    per sensor; ``drop`` lists (sensor, first_frame) pairs after which
    that sensor goes dark (its tracks coast, then prune)."""
    rng = np.random.default_rng(seed)
    pos = rng.normal(size=(S, targets, 3)) * 3
    z = np.zeros((T, S, cfg.max_meas, 3), np.float32)
    v = np.zeros((T, S, cfg.max_meas), bool)
    for t in range(T):
        pos = pos + 0.05
        z[t, :, :targets] = pos + rng.normal(size=pos.shape) * 0.05
        v[t, :, :targets] = True
        for s, t0 in drop:
            if t >= t0:
                v[t, s] = False
    return z, v


def _per_sensor_oracle(model, z, v, cfg=CFG):
    """Unsharded reference: one imm_frame_step / frame_step per sensor
    per frame, banks never stacked. Yields the per-frame results."""
    is_imm = hasattr(model, "models")
    S = z.shape[1]
    init = bank_lib.init_imm_bank if is_imm else bank_lib.init_bank
    step = imm_frame_step if is_imm else frame_step
    banks = [init(model, cfg.capacity) for _ in range(S)]
    for t in range(z.shape[0]):
        res = []
        for s in range(S):
            r = step(model, cfg, banks[s], jnp.asarray(z[t, s]),
                     jnp.asarray(v[t, s]))
            banks[s] = r.bank
            res.append(r)
        yield res


def _check_fleet_matches_oracle(engine, model, z, v):
    for t, oracle in enumerate(_per_sensor_oracle(model, z, v, engine.cfg)):
        res = engine.frame(z[t], v[t])
        for s, r in enumerate(oracle):
            np.testing.assert_array_equal(np.asarray(res.assoc)[s],
                                          np.asarray(r.assoc))
            np.testing.assert_array_equal(np.asarray(res.confirmed)[s],
                                          np.asarray(r.confirmed))
            np.testing.assert_array_equal(np.asarray(res.bank.track_id)[s],
                                          np.asarray(r.bank.track_id))
            if engine.is_imm:
                np.testing.assert_allclose(np.asarray(res.x_est)[s],
                                           np.asarray(r.x_est),
                                           atol=1e-5, rtol=1e-5)
                np.testing.assert_allclose(np.asarray(res.mode_probs)[s],
                                           np.asarray(r.bank.mu),
                                           atol=1e-5)
            else:
                np.testing.assert_allclose(np.asarray(res.bank.x)[s],
                                           np.asarray(r.bank.x),
                                           atol=1e-5, rtol=1e-5)


# ------------------------------------------------- vmapped fleet (any host)
def test_vmapped_imm_fleet_matches_per_sensor_oracle():
    """No mesh: the vmapped multi-sensor IMM step is frame-by-frame
    identical to S independent single-sensor imm_frame_step loops."""
    imm = make_imm()
    z, v = _fleet_scene(S=3, T=10, seed=0)
    eng = ShardedBankEngine(imm, 3, CFG)
    assert eng.is_imm
    # track ids are per-SLOT (shared across the K hypotheses): (S, C)
    assert np.asarray(eng.banks.track_id).shape == (3, CFG.capacity)
    assert np.asarray(eng.banks.x).shape == (imm.K, 3, CFG.capacity, imm.n)
    _check_fleet_matches_oracle(eng, imm, z, v)


def test_vmapped_fleet_snapshots_carry_mode_probs():
    imm = make_imm()
    z, v = _fleet_scene(S=2, T=8, seed=3)
    eng = ShardedBankEngine(imm, 2, CFG)
    for t in range(z.shape[0]):
        res = eng.frame(z[t], v[t])
    snaps = eng.snapshots(res)
    assert len(snaps) == 2 and all(len(s) == 2 for s in snaps)
    for s in snaps:
        for snap in s:
            assert snap.state.shape == (imm.n,)
            np.testing.assert_allclose(snap.mode_probs.sum(), 1.0, atol=1e-5)


# ------------------------------------------------ sharded fleet (>=4 devs)
def test_sharded_imm_engine_matches_unsharded(mesh):
    """shard_map over the mesh data axis changes NOTHING: every frame's
    bank state, associations, ids and combined estimates are bitwise
    equal to the unsharded vmapped fleet (sensors are independent, each
    shard runs the identical per-sensor program)."""
    imm = make_imm()
    S, T = 8, 10
    z, v = _fleet_scene(S=S, T=T, seed=1)
    sharded = ShardedBankEngine(imm, S, CFG, mesh=mesh)
    local = ShardedBankEngine(imm, S, CFG)
    for t in range(T):
        rs = sharded.frame(z[t], v[t])
        rl = local.frame(z[t], v[t])
        np.testing.assert_array_equal(np.asarray(rs.bank.x),
                                      np.asarray(rl.bank.x))
        np.testing.assert_array_equal(np.asarray(rs.bank.mu),
                                      np.asarray(rl.bank.mu))
        np.testing.assert_array_equal(np.asarray(rs.bank.track_id),
                                      np.asarray(rl.bank.track_id))
        np.testing.assert_array_equal(np.asarray(rs.x_est),
                                      np.asarray(rl.x_est))


def test_sharded_imm_engine_matches_per_sensor_oracle(mesh):
    """End-to-end acceptance: the sharded fleet against the unsharded
    per-sensor imm_frame_step oracle (allclose at fp32)."""
    imm = make_imm()
    z, v = _fleet_scene(S=8, T=8, seed=2)
    eng = ShardedBankEngine(imm, 8, CFG, mesh=mesh)
    _check_fleet_matches_oracle(eng, imm, z, v)


def test_sharded_k1_reduces_to_single_model_path(mesh):
    """as_imm(cv9) with K=1 on the sharded engine == the plain
    single-model sharded path: same ids, same states (the IMM mixing /
    combination collapse to identities at K=1)."""
    cv9 = make_cv9_lkf()
    S, T = 4, 8
    z, v = _fleet_scene(S=S, T=T, seed=4)
    plain = ShardedBankEngine(cv9, S, CFG, mesh=mesh)
    k1 = ShardedBankEngine(as_imm(cv9), S, CFG, mesh=mesh)
    assert not plain.is_imm and k1.is_imm
    for t in range(T):
        rp = plain.frame(z[t], v[t])
        rk = k1.frame(z[t], v[t])
        np.testing.assert_array_equal(np.asarray(rp.bank.track_id),
                                      np.asarray(rk.bank.track_id))
        np.testing.assert_array_equal(np.asarray(rp.confirmed),
                                      np.asarray(rk.confirmed))
        np.testing.assert_allclose(np.asarray(rk.x_est),
                                   np.asarray(rp.bank.x),
                                   atol=1e-6, rtol=1e-6)
    assert rp.mode_probs is None
    np.testing.assert_array_equal(np.asarray(rk.mode_probs),
                                  np.ones((S, CFG.capacity, 1), np.float32))


# ----------------------------------------------------------- fused replay
def _slice_bank(banks, s):
    """Sensor s's single-sensor IMMBankState out of the stacked fleet."""
    return IMMBankState(
        x=jnp.asarray(np.asarray(banks.x)[:, s]),
        P=jnp.asarray(np.asarray(banks.P)[:, s]),
        mu=jnp.asarray(np.asarray(banks.mu)[s]),
        active=jnp.asarray(np.asarray(banks.active)[s]),
        hits=jnp.asarray(np.asarray(banks.hits)[s]),
        misses=jnp.asarray(np.asarray(banks.misses)[s]),
        age=jnp.asarray(np.asarray(banks.age)[s]),
        track_id=jnp.asarray(np.asarray(banks.track_id)[s]),
        next_id=jnp.asarray(np.asarray(banks.next_id)[s]))


def test_sharded_replay_matches_replay_imm_bank(mesh):
    """engine.replay routes through katana_imm_sequence (one dispatch
    per shard, local sensors flattened onto the track axis) and matches
    per-sensor replay_imm_bank frame-by-frame on a coasting-masked
    stream, seeded from the live mode-conditioned banks."""
    imm = make_imm()
    S, T, T2 = 8, 6, 12
    z, v = _fleet_scene(S=S, T=T, seed=5)
    eng = ShardedBankEngine(imm, S, CFG, mesh=mesh)
    for t in range(T):
        eng.frame(z[t], v[t])
    rng = np.random.default_rng(7)
    zs = (rng.normal(size=(T2, S, CFG.capacity, imm.m)) * 0.5
          ).astype(np.float32)
    valid = rng.random((T2, S, CFG.capacity)) > 0.3
    valid[3] = False  # a whole coasted frame, fleet-wide
    out = eng.replay(zs, valid)
    assert out.shape == (T2, S, CFG.capacity, imm.n)
    assert np.isfinite(out).all()
    for s in range(S):
        want = np.asarray(replay_imm_bank(
            imm, _slice_bank(eng.banks, s), jnp.asarray(zs[:, s]),
            valid=jnp.asarray(valid[:, s])))
        np.testing.assert_allclose(out[:, s], want, atol=1e-6, rtol=1e-6)
    assert eng.stats.replay_frames == T2
    assert eng.stats.frames == T  # replay never dilutes serving fps


def test_vmapped_replay_matches_replay_imm_bank():
    """Same replay contract without a mesh (the always-on leg)."""
    imm = make_imm()
    S, T2 = 2, 10
    z, v = _fleet_scene(S=S, T=4, seed=6)
    eng = ShardedBankEngine(imm, S, CFG)
    for t in range(4):
        eng.frame(z[t], v[t])
    rng = np.random.default_rng(8)
    zs = (rng.normal(size=(T2, S, CFG.capacity, imm.m)) * 0.5
          ).astype(np.float32)
    valid = rng.random((T2, S, CFG.capacity)) > 0.4
    out = eng.replay(zs, valid)
    for s in range(S):
        want = np.asarray(replay_imm_bank(
            imm, _slice_bank(eng.banks, s), jnp.asarray(zs[:, s]),
            valid=jnp.asarray(valid[:, s])))
        np.testing.assert_allclose(out[:, s], want, atol=1e-6, rtol=1e-6)


# ----------------------------------------------- multi-sensor lifecycle
def _disagreeing_scene(S=4, T=14):
    """Sensor 1 goes dark at frame 4 (coast -> prune), sensor 2 starts
    dark and first detects at frame 6 (late spawn); the rest track
    normally — maximal lifecycle disagreement across the fleet."""
    z, v = _fleet_scene(S=S, T=T, seed=9, drop=((1, 4),))
    v[:6, 2] = False
    return z, v


@pytest.mark.parametrize("use_mesh", [False, True])
def test_multi_sensor_lifecycle_disagreement(use_mesh, request):
    """Spawn/prune interplay when sensors disagree: one sensor spawns
    while another coasts. Per-sensor id counters stay independent,
    pruned slots free up only on the dark sensor, and the
    shared-across-hypotheses track ids never diverge from the unsharded
    oracle on any shard, any frame."""
    mesh = request.getfixturevalue("mesh") if use_mesh else None
    imm = make_imm()
    cfg = TrackerConfig(capacity=8, max_meas=4, max_misses=3)
    S, T = 4, 14
    z, v = _disagreeing_scene(S=S, T=T)
    eng = ShardedBankEngine(imm, S, cfg, mesh=mesh)
    oracle = _per_sensor_oracle(imm, z, v, cfg)
    for t, per_sensor in enumerate(oracle):
        res = eng.frame(z[t], v[t])
        ids = np.asarray(res.bank.track_id)
        for s, r in enumerate(per_sensor):
            np.testing.assert_array_equal(ids[s], np.asarray(r.bank.track_id))
            np.testing.assert_array_equal(np.asarray(res.bank.active)[s],
                                          np.asarray(r.bank.active))
        # active ids stay unique per sensor (never reused while live)
        act = np.asarray(res.bank.active)
        for s in range(S):
            live = ids[s][act[s]].tolist()
            assert len(live) == len(set(live))
    bank = eng.banks
    active = np.asarray(bank.active)
    # sensor 1 coasted past max_misses: everything pruned
    assert not active[1].any()
    # sensor 2 spawned late but did spawn; sensors 0/3 tracked through
    assert active[2].sum() == 2
    assert active[0].sum() == 2 and active[3].sum() == 2
    # per-sensor id counters advanced independently (no cross-sensor
    # coupling through the stacked next_id)
    next_ids = np.asarray(bank.next_id)
    assert next_ids.shape == (S,)
    assert next_ids[0] == 2 and next_ids[2] == 2
    # mode probabilities on live tracks remain distributions
    mu = np.asarray(bank.mu)
    np.testing.assert_allclose(mu[active].sum(-1), 1.0, atol=1e-5)
