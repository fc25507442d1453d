"""Smoke test: the tracker's main path, compiled on a TPU, gives the
right answers.

    python chip_smoke.py             # one chip: phases a-c
    python chip_smoke.py --chips 4   # four chips: phases d-e only

a. LKF front end. ``StreamFrontEnd`` with the paper's cv-6 LKF,
   ``TrackerConfig(capacity=256, max_meas=64)``, one shard of 4 lanes,
   4 tenants, each streaming a seeded 90-frame MOT scene (3 s at
   30 fps, default clutter). The same front end on the einsum path
   (``fused_frame=False``, compiled XLA on the same chip) is the
   reference: per-frame assoc and track ids identical, states within
   float32 tolerance, no dispatch error, no lost shard, every accepted
   frame applied.
b. IMM front end. The same with the K=4 CV9/CA9/CT9± IMM.
c. Replay. ``TrackingEngine.replay`` for LKF and EKF ctra-8 at N=1024,
   T=300 against the float64 ``core/ref.run_batched``; IMM K=4 at
   N=256, T=300 against ``ref.run_imm_batched``. The IMM replay's
   limit comes from a witness on the same chip: the same IMM recursion
   in plain XLA (einsum mixing and model steps, no Pallas) against the
   same float64 reference.

``--chips 4`` runs only what exists across chips:

d. ``ShardedBankEngine``, IMM, 8 sensors on a 4-device mesh: bitwise
   equal to the unsharded fleet, and per sensor to ``imm_frame_step``
   (assoc and ids exact). The banks must sit on 4 distinct devices.
e. A 4-shard ``StreamFrontEnd``, one shard per device, one shard
   killed mid-run: the failed-over tenants' streams are bitwise equal
   to an uninterrupted run.

Every phase checks that its program ran the Pallas kernels compiled:
exec mode ``compiled`` and ``tpu_custom_call`` in the compiled HLO.
Each phase prints one JSON line (wall time, backend compile time kept
apart, max deviation, frames served). The last line of standard output
is ``{"ok": true, "device": {...}}``, printed only when every phase
passed. Without a TPU the script exits non-zero and prints no result.
It runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
FRAMES = 90          # 3 s of a 30 fps sensor
TENANTS = 4
REPLAY_T = 300


def log(**row) -> None:
    print(json.dumps(row), flush=True)


class CompileClock:
    """Seconds the backend spent compiling (XLA + Mosaic), from JAX's
    monitoring events, so a phase's wall time can be split."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs


class Phase:
    """Times one phase and prints its JSON line on exit."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock, self.row = name, clock, {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            wall = time.perf_counter() - self.t0
            comp = self.clock.total - self.c0
            log(phase=self.name, wall_s=wall, compile_s=comp,
                wall_minus_compile_s=wall - comp, **self.row)
        return False


def check_compiled(fn, *args) -> None:
    """The program ran its kernels compiled: exec mode and HLO."""
    from repro.execmode import active_mode

    assert active_mode().mode == "compiled", active_mode()
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def capture_results(front) -> list:
    """Record the FrameResult of every dispatch ``front`` makes (the
    per-lane assoc is not part of a TenantUpdate)."""
    seen = []
    step_for = front._step_for

    def recording(tier):
        step = step_for(tier)

        def run(*args):
            res = step(*args)
            seen.append(res)
            return res

        return run

    front._step_for = recording
    return seen


def tenant_scenes(scene_model, seed: int, tenants: int, frames: int,
                  max_meas: int):
    from repro.data.trajectories import SceneConfig, mot_scene

    cfg = SceneConfig(T=frames, max_meas=max_meas)
    out = []
    for i in range(tenants):
        z, valid, _ = mot_scene(scene_model, cfg, seed=seed * 1000 + i)
        out.append([z[t][valid[t]].astype(np.float32)
                    for t in range(frames)])
    return out


def front_end_phase(name, model, scene_model, tracker, seed, clock,
                    atol, tenants=TENANTS, frames=FRAMES, check=True):
    """Phases a/b: the fused front end against the einsum one."""
    from repro.serving.stream import (Admission, ServiceTier, StreamConfig,
                                      StreamFrontEnd)

    scenes = tenant_scenes(scene_model, seed, tenants, frames,
                           tracker.max_meas)
    cfg = StreamConfig(n_shards=1, lanes_per_shard=tenants)
    names = [f"t{i}" for i in range(tenants)]
    with Phase(name, clock) as ph, tempfile.TemporaryDirectory() as tmp:
        fronts, results, streams, pump_s = {}, {}, {}, {}
        for path, fused in (("fused", True), ("einsum", False)):
            trk = dataclasses.replace(tracker, fused_frame=fused)
            front = StreamFrontEnd(model, cfg, trk,
                                   ckpt_dir=f"{tmp}/{path}")
            results[path] = capture_results(front)
            for t in names:
                assert front.attach(t) == Admission.ACCEPTED
            streams[path] = {t: [] for t in names}
            pump_s[path] = []
            for f in range(frames):
                for i, t in enumerate(names):
                    assert front.submit(t, scenes[i][f]) == \
                        Admission.ACCEPTED
                t0 = time.perf_counter()
                ups = front.pump()
                pump_s[path].append(time.perf_counter() - t0)
                for t, up in ups.items():
                    streams[path][t].append(up)
            fronts[path] = front
        assert len(results["fused"]) == len(results["einsum"]) == frames
        dev = 0.0
        for rf, re in zip(results["fused"], results["einsum"]):
            np.testing.assert_array_equal(np.asarray(rf.assoc),
                                          np.asarray(re.assoc))
            np.testing.assert_array_equal(np.asarray(rf.bank.track_id),
                                          np.asarray(re.bank.track_id))
            xf = np.asarray(rf.x_est if rf.x_est is not None else rf.bank.x)
            xe = np.asarray(re.x_est if re.x_est is not None else re.bank.x)
            np.testing.assert_allclose(xf, xe, atol=atol)
            dev = max(dev, float(np.abs(xf - xe).max()))
            if rf.mode_probs is not None:
                np.testing.assert_allclose(np.asarray(rf.mode_probs),
                                           np.asarray(re.mode_probs),
                                           atol=atol)
        for t in names:
            uf, ue = streams["fused"][t], streams["einsum"][t]
            assert len(uf) == len(ue) == frames, (t, len(uf), len(ue))
            for a, b in zip(uf, ue):
                assert (a.frame, a.seq, a.kind) == (b.frame, b.seq, b.kind)
                assert [s.track_id for s in a.snapshots] == \
                    [s.track_id for s in b.snapshots]
        for path, front in fronts.items():
            st = front.stats
            assert st.dispatch_errors == 0 and st.shards_lost == 0, st
            assert st.shed == st.expired == 0, st
            assert st.served + st.coasted == st.accepted == \
                tenants * frames, st
        front = fronts["fused"]
        sh = front.shards[0]
        L, M = cfg.lanes_per_shard, tracker.max_meas
        if check:
            check_compiled(StreamFrontEnd._step_for(front, ServiceTier.FULL),
                           sh.banks,
                           np.zeros((L, M, model.m), np.float32),
                           np.zeros((L, M), bool))
        # host clock around pump(): one fused dispatch for all tenants,
        # snapshots and checkpoints included; the first pump compiles
        ph.row.update(first_pump_s=pump_s["fused"][0],
                      median_pump_s=float(np.median(pump_s["fused"][1:])),
                      median_einsum_pump_s=float(
                          np.median(pump_s["einsum"][1:])),
                      frames_served=front.stats.served,
                      frames_coasted=front.stats.coasted,
                      dispatches=front.stats.dispatches,
                      max_state_dev_vs_einsum=dev,
                      confirmed_tracks_last_frame=sum(
                          len(streams["fused"][t][-1].snapshots)
                          for t in names))


def xla_imm_replay(imm, zs, x0, P0):
    """The IMM recursion of ``katana_imm_sequence`` in plain XLA: einsum
    mixing, the einsum model steps of ``katana_imm_ref``, mode posterior
    and combined estimate under one ``lax.scan``. Returns (program,
    combined estimates (T, N, n))."""
    import jax
    import jax.numpy as jnp

    from repro.core.rewrites import imm_combine, imm_mix, imm_mode_posterior
    from repro.kernels.katana_bank.ref import katana_imm_ref

    K, N = imm.K, x0.shape[0]
    Pi = jnp.asarray(imm.trans, jnp.float32)

    @jax.jit
    def run(zs, x0, P0):
        def body(carry, z_t):
            x, P, mu = carry
            x_mix, P_mix, cbar = imm_mix(x, P, mu, Pi)
            x_new, P_new, loglik = katana_imm_ref(imm, x_mix, P_mix, z_t)
            mu_new = imm_mode_posterior(cbar, loglik)
            return (x_new, P_new, mu_new), imm_combine(x_new, P_new,
                                                       mu_new)[0]

        mu0 = jnp.broadcast_to(jnp.asarray(imm.mu0, jnp.float32), (N, K))
        carry = (jnp.broadcast_to(x0, (K,) + x0.shape),
                 jnp.broadcast_to(P0, (K,) + P0.shape), mu0)
        return jax.lax.scan(body, carry, zs)[1]

    args = (jnp.asarray(zs), jnp.asarray(x0), jnp.asarray(P0))
    return run, args, np.asarray(run(*args))


def replay_phase(name, model, N, T, seed, clock, atol, check=True):
    """Phase c: TrackingEngine.replay against the float64 reference.
    An IMM replay is held to ``atol`` or to twice the deviation of the
    plain-XLA IMM (``xla_imm_replay``) from the same reference, the
    larger."""
    import jax
    import jax.numpy as jnp

    from repro.core import ref
    from repro.core.filters import IMMModel
    from repro.core.tracker import TrackerConfig
    from repro.kernels.katana_bank.ops import (katana_bank_sequence,
                                               katana_imm_sequence)
    from repro.serving.engine import TrackingEngine

    rng = np.random.default_rng(seed)
    zs = (rng.normal(size=(T, N, model.m)) * 0.5).astype(np.float32)
    x0 = (np.tile(model.x0, (N, 1))
          + rng.normal(size=(N, model.n)) * 0.1).astype(np.float32)
    P0 = np.tile(model.P0, (N, 1, 1)).astype(np.float32)
    is_imm = isinstance(model, IMMModel)
    with Phase(name, clock) as ph:
        eng = TrackingEngine(model, TrackerConfig(capacity=256,
                                                  max_meas=64))
        t0 = time.perf_counter()
        got = eng.replay(zs, x0, P0)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        got2 = eng.replay(zs, x0, P0)
        warm = time.perf_counter() - t0
        np.testing.assert_array_equal(got, got2)
        t0 = time.perf_counter()
        if is_imm:
            want, _ = ref.run_imm_batched(model, zs.astype(np.float64),
                                          x0.astype(np.float64),
                                          P0.astype(np.float64))
        else:
            want, _, _ = ref.run_batched(model, zs.astype(np.float64),
                                         x0.astype(np.float64),
                                         P0.astype(np.float64))
        ph.row.update(float64_reference_s=time.perf_counter() - t0)
        assert got.shape == (T, N, model.n) and np.isfinite(got).all()
        if is_imm:
            xla, xla_args, xla_got = xla_imm_replay(model, zs, x0, P0)
            if check:
                assert "tpu_custom_call" not in \
                    xla.lower(*xla_args).compile().as_text()
            xla_dev = float(np.abs(xla_got - want).max())
            atol = max(atol, 2 * xla_dev)
            ph.row.update(xla_imm_max_abs_dev_vs_float64=xla_dev,
                          max_abs_dev_vs_xla_imm=float(
                              np.abs(got - xla_got).max()),
                          limit=atol)
        np.testing.assert_allclose(got, want, atol=atol, rtol=atol)
        if check:
            seq = katana_imm_sequence if is_imm else katana_bank_sequence
            check_compiled(jax.jit(lambda z, x, p: seq(model, z, x, p)),
                           jnp.asarray(zs), jnp.asarray(x0),
                           jnp.asarray(P0))
        ph.row.update(N=N, T=T, frames=T * N,
                      first_replay_s=first, warm_replay_s=warm,
                      max_abs_dev_vs_float64=float(np.abs(got - want).max()))


def sharded_phase(clock, sensors=8, frames=30, seed=0, capacity=256,
                  max_meas=64, check=True):
    """Phase d: the sharded IMM fleet on a 4-device mesh."""
    import jax
    import jax.numpy as jnp

    from repro.core import bank as bank_lib
    from repro.core.filters import make_cv9_lkf, make_imm
    from repro.core.tracker import TrackerConfig, imm_frame_step
    from repro.data.trajectories import SceneConfig, mot_scene
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import ShardedBankEngine

    imm = make_imm()
    cfg = TrackerConfig(capacity=capacity, max_meas=max_meas)
    scene = SceneConfig(T=frames, max_meas=max_meas)
    zv = [mot_scene(make_cv9_lkf(), scene, seed=seed * 1000 + s)[:2]
          for s in range(sensors)]
    z = np.stack([a for a, _ in zv], 1).astype(np.float32)  # (T, S, M, m)
    v = np.stack([b for _, b in zv], 1)
    with Phase("d_sharded_imm_fleet", clock) as ph:
        mesh = make_mesh((4,), ("data",))
        sharded = ShardedBankEngine(imm, sensors, cfg, mesh=mesh)
        local = ShardedBankEngine(imm, sensors, cfg)
        for leaf in jax.tree.leaves(sharded.banks):
            assert len(leaf.sharding.device_set) == 4, leaf.sharding
        x_devs = {s.device for s in sharded.banks.x.addressable_shards}
        assert len(x_devs) == 4, x_devs
        one = jax.jit(lambda b, zz, vv: imm_frame_step(imm, cfg, b, zz, vv))
        banks = [bank_lib.init_imm_bank(imm, capacity)
                 for _ in range(sensors)]
        for t in range(frames):
            rs = sharded.frame(z[t], v[t])
            rl = local.frame(z[t], v[t])
            for a, b in ((rs.bank.x, rl.bank.x), (rs.bank.mu, rl.bank.mu),
                         (rs.bank.track_id, rl.bank.track_id),
                         (rs.x_est, rl.x_est), (rs.assoc, rl.assoc)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for s in range(sensors):
                r = one(banks[s], jnp.asarray(z[t, s]), jnp.asarray(v[t, s]))
                banks[s] = r.bank
                np.testing.assert_array_equal(np.asarray(rs.assoc)[s],
                                              np.asarray(r.assoc))
                np.testing.assert_array_equal(
                    np.asarray(rs.bank.track_id)[s],
                    np.asarray(r.bank.track_id))
                np.testing.assert_allclose(np.asarray(rs.x_est)[s],
                                           np.asarray(r.x_est),
                                           atol=1e-5, rtol=1e-5)
        if check:
            check_compiled(sharded._step, sharded.banks, jnp.asarray(z[0]),
                           jnp.asarray(v[0]))
        ph.row.update(sensors=sensors, frames=frames,
                      devices=sorted(str(d) for d in x_devs),
                      confirmed_tracks_last_frame=int(
                          np.asarray(rs.confirmed).sum()))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def failover_phase(clock, tenants=8, cycles=24, kill_at=8, seed=0,
                   capacity=256, max_meas=64, check=True):
    """Phase e: a 4-shard front end, one shard per device, one shard
    killed mid-run, compared bitwise with an uninterrupted run."""
    import jax

    from repro.core.filters import make_cv9_lkf, make_imm
    from repro.core.tracker import TrackerConfig
    from repro.serving.faults import ChaosDriver, FaultPlan
    from repro.serving.stream import (Admission, ServiceTier, StreamConfig,
                                      StreamFrontEnd)

    imm = make_imm()
    tracker = TrackerConfig(capacity=capacity, max_meas=max_meas)
    frames = cycles + 40
    scenes = tenant_scenes(make_cv9_lkf(), seed, tenants, frames, max_meas)
    names = [f"t{i}" for i in range(tenants)]
    # a dead shard's queues back up: keep the ladder at FULL so the
    # resumed streams can be compared bitwise
    cfg = StreamConfig(n_shards=4, lanes_per_shard=4, queue_depth=8,
                       checkpoint_every=4, degrade_at=5.0, coast_at=6.0,
                       reject_at=7.0)
    devs = jax.devices()[:4]
    with Phase("e_failover_4_shards", clock) as ph, \
            tempfile.TemporaryDirectory() as tmp:
        reports, fronts = {}, {}
        for tag, plan in (("ref", FaultPlan()),
                          ("chaos", FaultPlan(kill_shards={kill_at: 0}))):
            clk = FakeClock()
            front = StreamFrontEnd(imm, cfg, tracker, ckpt_dir=f"{tmp}/{tag}",
                                   clock=clk, devices=devs)
            placed = {sh.device for sh in front.shards}
            assert len(placed) == 4, placed
            for sh in front.shards:
                assert {d for leaf in jax.tree.leaves(sh.banks)
                        for d in leaf.devices()} == {sh.device}
            for t in names:
                assert front.attach(t) == Admission.ACCEPTED
            drv = ChaosDriver(front, plan,
                              {t: (lambda i, s=scenes[k]: s[i])
                               for k, t in enumerate(names)},
                              clk.advance, dt_s=0.5)
            rep = drv.run(cycles)
            for _ in range(40):  # drain the dead period's backlog
                ups = front.pump()
                if not ups:
                    break
                for t, u in ups.items():
                    rep.updates[t].append(u)
                clk.advance(0.5)
            assert rep.exceptions == [], rep.exceptions
            reports[tag], fronts[tag] = rep, front
        chaos = fronts["chaos"]
        assert chaos.stats.shards_lost == 1 and chaos.stats.failovers > 0
        moved = [t for t in names
                 if reports["chaos"].updates[t][-1].shard != "shard0"
                 and reports["chaos"].updates[t][0].shard == "shard0"]
        assert moved, "no tenant failed over"
        for t in moved:
            assert chaos.shards[chaos.tenants[t].shard].device != \
                chaos.shards[0].device
        applied = 0
        for t in names:
            ru, gu = reports["ref"].updates[t], reports["chaos"].updates[t]
            assert len(ru) == len(gu), (t, len(ru), len(gu))
            for r, g in zip(ru, gu):
                assert (r.frame, r.seq, r.kind) == (g.frame, g.seq, g.kind)
                assert len(r.snapshots) == len(g.snapshots)
                for rs, gs in zip(r.snapshots, g.snapshots):
                    assert rs.track_id == gs.track_id
                    assert (rs.hits, rs.age) == (gs.hits, gs.age)
                    np.testing.assert_array_equal(rs.state, gs.state)
                    np.testing.assert_array_equal(rs.mode_probs,
                                                  gs.mode_probs)
            applied += len(gu)
        if check:
            sh = next(s for s in chaos.shards if s.alive)
            L, M = cfg.lanes_per_shard, max_meas
            check_compiled(chaos._step_for(ServiceTier.FULL), sh.banks,
                           np.zeros((L, M, imm.m), np.float32),
                           np.zeros((L, M), bool))
        ph.row.update(tenants=tenants, frames_applied=applied,
                      failed_over=moved,
                      devices=sorted(str(d) for d in placed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not __debug__:
        print("chip_smoke: its checks are assert statements; run it "
              "without -O", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"the smoke runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.filters import (make_ctra_ekf, make_cv9_lkf,
                                    make_cv_lkf, make_imm)
    from repro.core.tracker import TrackerConfig

    log(compile_cache=enable_compile_cache(), jax=jax.__version__,
        devices=[str(d) for d in devices])
    clock = CompileClock()
    if args.chips == 1:
        tracker = TrackerConfig(capacity=256, max_meas=64)
        lkf = make_cv_lkf()
        # tolerances of tests/test_frame_kernel.py (fused vs einsum) and
        # tests/test_kernels.py / test_imm_scan.py (replay vs float64)
        front_end_phase("a_lkf_front_end", lkf, lkf, tracker, args.seed,
                        clock, atol=1e-4)
        front_end_phase("b_imm_front_end", make_imm(), make_cv9_lkf(),
                        tracker, args.seed, clock, atol=5e-4)
        replay_phase("c_replay_lkf", lkf, 1024, REPLAY_T, args.seed, clock,
                     atol=1e-5)
        replay_phase("c_replay_ekf", make_ctra_ekf(), 1024, REPLAY_T,
                     args.seed, clock, atol=1e-5)
        # tests/test_imm_scan.py's 1e-5, unless plain XLA on this chip
        # strays further from float64 (f32 exp/log in the mode
        # posterior): then twice XLA's own deviation
        replay_phase("c_replay_imm", make_imm(), 256, REPLAY_T, args.seed,
                     clock, atol=1e-5)
    else:
        sharded_phase(clock, seed=args.seed)
        failover_phase(clock, seed=args.seed)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
