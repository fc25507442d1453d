"""Seeded, vectorised multi-target scenes: what each camera tenant sees.

Every tenant watches its own scene. Targets move as 3-D constant-velocity
points driven by white-noise acceleration, are detected with probability
``p_detect`` under Gaussian position noise, and Poisson clutter falls
uniformly in the scene's box. The scene starts at its stated population,
and deaths are balanced by births (each target leaves with probability
``death_rate`` per frame; Poisson(targets · death_rate) enter), so the
population stays at the stated size for the whole run.

All tenants are simulated together as numpy arrays of shape
(tenants, slots, ...): no Python loop per target, one per frame.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class SceneSpec:
    targets: int          # stated population per scene
    p_detect: float
    clutter_rate: float   # Poisson mean false alarms per frame
    extent: float         # half-width of the scene's cube, metres
    death_rate: float     # per-target, per-frame probability of leaving
    dt: float             # seconds per frame
    q: float              # white-noise acceleration PSD of the truth
    r: float              # measurement noise variance per axis
    speed: float          # per-axis std of a new target's velocity, m/s

    @classmethod
    def from_config(cls, cfg: dict) -> "SceneSpec":
        s = cfg["scene"]
        return cls(targets=s["targets"], p_detect=s["p_detect"],
                   clutter_rate=s["clutter_rate"], extent=s["extent"],
                   death_rate=s["death_rate"], dt=1.0 / cfg["fps"],
                   q=cfg["filter"]["q"], r=cfg["filter"]["r"],
                   speed=s["speed"])


def simulate(spec: SceneSpec, tenants: int, frames: int, seed: int,
             max_meas: int):
    """Simulate ``tenants`` scenes for ``frames`` frames from ``seed``
    (any integer >= 0).

    Returns ``(dets, counts)``: dets (tenants, frames, max_meas, 3)
    float32 measurements, of which the first ``counts[i, t]`` of frame t
    of tenant i are real: detections and clutter in random order,
    truncated at ``max_meas``. A target that leaves is replaced in the
    same frame by one that enters, so every frame holds exactly
    ``spec.targets`` targets.
    """
    rng = np.random.default_rng(int(seed))
    shape = (tenants, spec.targets, 3)
    pos = rng.uniform(-spec.extent, spec.extent, shape)
    vel = rng.normal(0.0, spec.speed, shape)
    dt, sr = spec.dt, np.sqrt(spec.r)
    # exact discretisation of white-noise acceleration, per axis
    Lq = np.linalg.cholesky(
        spec.q * np.array([[dt ** 3 / 3, dt ** 2 / 2], [dt ** 2 / 2, dt]]))
    n_cl = CLUTTER_SLOTS
    rows = np.arange(tenants)[:, None]
    dets = np.zeros((tenants, frames, max_meas, 3), np.float32)
    counts = np.zeros((tenants, frames), np.int32)
    for t in range(frames):
        leave = rng.random(shape[:2]) < spec.death_rate
        pos = np.where(leave[..., None],
                       rng.uniform(-spec.extent, spec.extent, shape), pos)
        vel = np.where(leave[..., None], rng.normal(0.0, spec.speed, shape),
                       vel)
        w = rng.normal(size=shape + (2,)) @ Lq.T
        pos = pos + vel * dt + w[..., 0]
        vel = vel + w[..., 1]
        seen = rng.random(shape[:2]) < spec.p_detect
        z = pos + sr * rng.normal(size=shape)
        clutter = rng.uniform(-spec.extent, spec.extent,
                              (tenants, n_cl, 3))
        n_clutter = np.minimum(rng.poisson(spec.clutter_rate, tenants), n_cl)
        real = np.concatenate(
            [seen, np.arange(n_cl)[None, :] < n_clutter[:, None]], axis=1)
        cand = np.concatenate([z, clutter], axis=1)
        # real rows first, in random order; padding rows after them
        order = np.argsort(np.where(real, rng.random(real.shape), 2.0),
                           axis=1)[:, :max_meas]
        k = np.minimum(real.sum(axis=1), max_meas)
        dets[:, t, :order.shape[1]] = np.where(
            (np.arange(order.shape[1])[None, :] < k[:, None])[..., None],
            cand[rows, order], 0.0)
        counts[:, t] = k
    return dets, counts


# clutter draws above this many per frame are dropped (Poisson(1) exceeds
# it with probability below 1e-13)
CLUTTER_SLOTS = 16
