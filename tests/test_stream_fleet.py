"""The camera fleet: a ``StreamFrontEnd`` of four shards, one per device.

The front end pins each shard's stacked bank to its own device and fans
every pump out over the shards. These cases check, on four virtual CPU
devices with one seeded camera per shard:

  (a) every shard's bank and every step result stay on that shard's
      device, and the ``z`` / ``valid`` batch each step call receives is
      already there when the call is made (no hop through device 0);
  (b) each tenant's confirmed ids, hits, ages and states over 20 frames
      equal those of the same tenants on a one-shard front end;
  (c) one tenant's track follows the float64 filter of ``core/ref.py``
      within the chip benchmark's ``state_dev`` limit;
  (d) ``StreamStats.fanout_pumps`` counts a pump that called two
      shards' steps and not one that called one.

JAX fixes its device count when it starts, so the cases share one child
process started with ``--xla_force_host_platform_device_count=4``: it
runs this file as a script, drives both front ends once and prints what
the cases check as one JSON line.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SHARDS = 4
FRAMES = 20
# the chip benchmark's limit on a confirmed track's deviation from the
# float64 reference (benchmarks/chip/configs/mot17-lkf-cv6*.json)
STATE_DEV_LIMIT = 0.02


def _scenes(model, max_meas, seed=20261018):
    """(tenants, frames) lists of (k, 3) float32 detections: camera 0
    sees one walker (its track is the one ``core/ref.py`` follows), the
    others three walkers 10 m apart; no clutter, no missed detections,
    and a noise of 0.05 m, well inside the filter's gate (r = 0.1 m^2),
    so every detection lands on its walker's track."""
    rng = np.random.default_rng(seed)
    out = []
    for cam in range(SHARDS):
        k = 1 if cam == 0 else 3
        pos = rng.uniform(-5, 5, (k, 3)) + 10.0 * np.arange(k)[:, None]
        vel = rng.normal(0.0, 0.5, (k, 3))
        frames = []
        for f in range(FRAMES):
            truth = pos + vel * model.dt * f
            z = truth + rng.normal(0.0, 0.05, truth.shape)
            frames.append(z[:max_meas].astype(np.float32))
        out.append(frames)
    return out


def _child() -> dict:
    import tempfile

    import jax

    from repro.core.filters import make_cv_lkf
    from repro.core.tracker import TrackerConfig
    from repro.serving.stream import StreamConfig, StreamFrontEnd

    devs = jax.devices()
    assert len(devs) == SHARDS, devs
    model = make_cv_lkf()
    tracker = TrackerConfig(capacity=8, max_meas=4)
    scenes = _scenes(model, tracker.max_meas)
    names = [f"cam{i}" for i in range(SHARDS)]

    def dev_ids(x) -> list:
        return sorted(d.id for d in x.devices())

    def fleet(n_shards, devices, tmp):
        front = StreamFrontEnd(
            model, StreamConfig(n_shards=n_shards,
                                lanes_per_shard=SHARDS // n_shards,
                                queue_depth=4, checkpoint_every=8),
            tracker, ckpt_dir=tmp, devices=devices)
        calls = []
        step_for = front._step_for

        def watched(tier):
            step = step_for(tier)

            def run(banks, z, valid):
                seen = dict(banks=dev_ids(banks.x), z=dev_ids(z),
                            valid=dev_ids(valid))
                res = step(banks, z, valid)
                calls.append(dict(seen, out=dev_ids(res.bank.x)))
                return res
            return run

        front._step_for = watched
        for name in names:
            front.attach(name)
        tracks = {n: [] for n in names}
        for f in range(FRAMES):
            for i, name in enumerate(names):
                front.submit(name, scenes[i][f], seq=f)
            for name, u in front.pump().items():
                tracks[name].append(dict(
                    ids=[s.track_id for s in u.snapshots],
                    hits=[s.hits for s in u.snapshots],
                    age=[s.age for s in u.snapshots],
                    states=[s.state.tolist() for s in u.snapshots]))
        out = dict(calls=list(calls), tracks=tracks,
                   shard_devices=[sorted({d for leaf in sh.banks
                                          for d in dev_ids(leaf)})
                                  for sh in front.shards],
                   fanout_after_frames=front.stats.fanout_pumps)
        return front, out

    with tempfile.TemporaryDirectory() as a, \
            tempfile.TemporaryDirectory() as b:
        front, four = fleet(SHARDS, devs, a)
        _, one = fleet(1, devs[:1], b)
        # (d): a pump over one shard, then one over two
        fan = [front.stats.fanout_pumps]
        front.submit(names[0], scenes[0][0], seq=FRAMES)
        front.pump()
        fan.append(front.stats.fanout_pumps)
        front.submit(names[0], scenes[0][0], seq=FRAMES + 1)
        front.submit(names[1], scenes[1][0], seq=FRAMES)
        front.pump()
        fan.append(front.stats.fanout_pumps)
    return dict(four=four, one=one, fanout=fan,
                cam0=[z.tolist() for z in scenes[0]])


@pytest.fixture(scope="module")
def fleet():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src")]
                   + [p for p in os.environ.get("PYTHONPATH", "").split(
                       os.pathsep) if p]))
    p = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_batch_and_banks_stay_on_their_shards_device(fleet):
    four = fleet["four"]
    assert four["shard_devices"] == [[s] for s in range(SHARDS)]
    calls = four["calls"]
    assert len(calls) == SHARDS * FRAMES
    assert sorted({c["banks"][0] for c in calls}) == list(range(SHARDS))
    for c in calls:
        assert len(c["banks"]) == 1, c
        # the batch arrives on the shard's chip: no copy to device 0
        # and on from there
        assert c["z"] == c["valid"] == c["out"] == c["banks"], c


def test_four_shards_serve_what_one_shard_serves(fleet):
    four, one = fleet["four"]["tracks"], fleet["one"]["tracks"]
    assert four.keys() == one.keys()
    confirmed = 0
    for name in four:
        assert len(four[name]) == len(one[name]) == FRAMES
        for a, b in zip(four[name], one[name]):
            assert (a["ids"], a["hits"], a["age"]) == \
                   (b["ids"], b["hits"], b["age"]), name
            # the two fleets run different compiled programs (a one-lane
            # and a four-lane step), which XLA may fuse and order
            # differently: float32 rounding of states of order 10 m,
            # compounded over 20 frames, stays far below 1e-4 m
            np.testing.assert_allclose(a["states"], b["states"],
                                       rtol=0, atol=1e-4)
            confirmed += len(a["ids"])
    assert confirmed > 0


def test_one_tenants_track_follows_the_float64_filter(fleet):
    from repro.core import ref
    from repro.core.filters import make_cv_lkf

    model = make_cv_lkf()
    zs = np.asarray(fleet["cam0"], np.float64)[:, 0]
    x0 = np.concatenate([zs[0], np.zeros(3)])  # the spawn: z through H^T
    want, _ = ref.run(model, zs[1:], x0=x0, P0=model.P0)
    got = fleet["four"]["tracks"]["cam0"]
    checked = 0
    for f, u in enumerate(got):
        if f == 0:
            continue
        assert len(u["ids"]) <= 1
        if u["ids"]:
            dev = np.abs(np.asarray(u["states"][0]) - want[f - 1]).max()
            assert dev < STATE_DEV_LIMIT, (f, dev)
            checked += 1
    # confirmed from its third hit on
    assert checked == FRAMES - 2


def test_fanout_pumps_counts_pumps_over_several_shards(fleet):
    # every frame's pump called all four shards' steps
    assert fleet["four"]["fanout_after_frames"] == FRAMES
    assert fleet["one"]["fanout_after_frames"] == 0
    before, after_one, after_two = fleet["fanout"]
    assert after_one == before
    assert after_two == before + 1


if __name__ == "__main__":
    print(json.dumps(_child()))
