"""§IV-D analysis: useful-FLOP fraction of the batching strategies,
plus the serving-scale follow-on — sharded multi-sensor IMM frames/sec.

The paper expands N filters into an (N·n)x(N·n) block-diagonal system
so the NPU's MAC array sees big GEMMs; on a TPU that expansion costs
O(N^2-N^3) redundant FLOPs. This bench measures compiled HLO FLOPs for
the paper-faithful expansion vs the TPU-native lane batching, against
the analytic useful-work floor.

The ``sharded_imm`` rows scale the OTHER batching axis: S independent
sensors, each a full IMM MOT frame (gating + assignment + lifecycle),
shard_mapped over a 1/2/4/8-device host-platform mesh
(``serving.engine.ShardedBankEngine``). Run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get every
row; device counts that exceed the host (or don't divide S) are
skipped. Interpret-mode CPU numbers measure dispatch scaling, not TPU
silicon."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import hlo_flops
from repro.core.filters import get_filter
from repro.core.rewrites import build_stage, canonical_to_stage


def useful_flops(n: int, m: int) -> float:
    """Per-filter predict+update mul/adds (dense F, selector H)."""
    return 2.0 * (2 * n ** 3 + 2 * n * n * m + n * m * m + m ** 3 + n * m)


def run(csv: List[str], N: int = 200, imm_sensors: int = 8,
        imm_frames: int = 32) -> None:
    rng = np.random.default_rng(0)
    for kind in ("lkf", "ekf"):
        model = get_filter(kind)
        floor = useful_flops(model.n, model.m) * N
        for stage in ("batched_blockdiag", "batched_lanes"):
            step, _ = build_stage(model, stage, N=N)
            x0 = np.tile(model.x0, (N, 1)).astype(np.float32)
            P0 = np.tile(model.P0, (N, 1, 1)).astype(np.float32)
            z0 = rng.normal(size=(N, model.m)).astype(np.float32)
            x, P, z = canonical_to_stage(stage, jnp.asarray(x0),
                                         jnp.asarray(P0), jnp.asarray(z0),
                                         model.n, model.m)
            fl = hlo_flops(step, x, P, z)
            csv.append(f"batching/{kind}/{stage}/N={N},{fl:.0f},"
                       f"useful_floor={floor:.0f};"
                       f"useful_fraction={min(1.0, floor / fl):.4f}")
    _run_sharded_imm(csv, imm_sensors, imm_frames)


def _run_sharded_imm(csv: List[str], S: int, T: int) -> None:
    """Sharded multi-sensor IMM serving throughput: S sensors, each a
    full K=4 IMM MOT frame, shard_mapped over 1/2/4/8 host devices.
    Times the live ``ShardedBankEngine.frame`` loop (compile excluded
    by the engine's warmup), reporting fleet frames/sec — one frame =
    all S sensors serviced."""
    from repro.launch.mesh import make_mesh
    from repro.core.filters import make_imm
    from repro.core.tracker import TrackerConfig
    from repro.serving.engine import ShardedBankEngine

    imm = make_imm()
    cfg = TrackerConfig(capacity=16, max_meas=8)
    n_dev = len(jax.devices())
    rng = np.random.default_rng(3)
    pos = rng.normal(size=(S, 2, 3)) * 3
    z = np.zeros((T, S, cfg.max_meas, imm.m), np.float32)
    v = np.zeros((T, S, cfg.max_meas), bool)
    for t in range(T):
        pos = pos + 0.05
        z[t, :, :2] = pos + rng.normal(size=pos.shape) * 0.05
        v[t, :, :2] = True
    base_fps = None
    for d in (1, 2, 4, 8):
        if d > n_dev or S % d:
            csv.append(f"batching/sharded_imm/devices={d}/S={S},0,"
                       f"skipped=need {d} devices dividing S={S}")
            continue
        eng = ShardedBankEngine(imm, S, cfg, mesh=make_mesh((d,), ("data",)))
        for t in range(T):
            eng.frame(z[t], v[t])
        fps = eng.stats.fps
        base_fps = base_fps or fps
        csv.append(f"batching/sharded_imm/devices={d}/S={S},"
                   f"{1e6 / fps:.1f},frames_per_sec={fps:.1f};"
                   f"scaling_vs_1dev={fps / base_fps:.2f}")
