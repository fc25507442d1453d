import numpy as np

from scenes import CLUTTER_SLOTS, SceneSpec, simulate

SPEC = SceneSpec(targets=20, p_detect=0.95, clutter_rate=1.0, extent=20.0,
                 death_rate=0.02, dt=1 / 30, q=0.01, r=0.1, speed=0.5)


def test_same_seed_same_scene_and_large_seeds():
    seed = 2 ** 31 + 12345
    a = simulate(SPEC, 3, 50, seed, 32)
    b = simulate(SPEC, 3, 50, seed, 32)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = simulate(SPEC, 3, 50, seed + 1, 32)
    assert not np.array_equal(a[0], c[0])


def test_population_held_and_detections_bounded():
    dets, counts = simulate(SPEC, 4, 400, 7, 64)
    # every frame sees ~p_detect of the stated population plus clutter
    mean = counts.mean()
    assert abs(mean - (SPEC.targets * SPEC.p_detect + SPEC.clutter_rate)) < 1.0
    assert counts.max() <= SPEC.targets + CLUTTER_SLOTS
    # padding rows are zero, real rows are inside the scene (plus drift)
    for i in range(4):
        for t in (0, 399):
            assert not dets[i, t, counts[i, t]:].any()
            assert np.abs(dets[i, t, :counts[i, t]]).max() < 2 * SPEC.extent


def test_truncates_at_max_meas():
    _, counts = simulate(SPEC, 2, 10, 3, 8)
    assert counts.max() == 8
