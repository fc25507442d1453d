"""Live-frame serving bench: the fused one-dispatch frame vs the einsum
chain.

This is the closed-loop number the paper reports (408.73 FPS LKF /
223.35 FPS EKF on Series 2 are per-frame measurement-in to
fused-estimate-out figures): one ``frame_step`` — predict + gate +
greedy assignment + update + lifecycle — per measurement frame. Rows
compare the two routes through the SAME ``tracker.frame_step`` /
``imm_frame_step``:

  * ``einsum``  — ``fused_frame=False``: the XLA chain predict_bank ->
    mahalanobis_cost -> greedy_assign -> update_bank (the PR-1 hot
    path, kept as the equivalence oracle);
  * ``fused``   — ``fused_frame=True``: ONE ``katana_frame`` /
    ``katana_imm_frame`` Pallas dispatch for the whole measurement
    cycle, with only spawn/prune left in XLA.

Single-sensor rows sweep the bank capacity C at a fixed measurement
budget M; the ``sharded`` rows run the 8-sensor ``ShardedBankEngine``
fleet (fused vs einsum) over however many host devices exist — run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for the
8-device row (the bench-smoke CI job does; missing device counts emit
explicit ``skipped=`` rows, never silence).

Every timed configuration first asserts fused/einsum equivalence on
the timed frame (identical assoc, float32-tolerance states) — the CI
smoke run keeps that assertion at tiny shapes, where the timings
themselves are meaningless. Results land in BENCH_frame.json, every
row stamped with how it actually executed (mode / lowering / backend):
the ``einsum`` route is real compiled XLA on every backend, while the
``fused`` route's Pallas dispatch is interpret-stamped on CPU — those
numbers overweight dispatch/op overhead vs TPU silicon. Never read a
fused-vs-einsum speedup without reading the stamps first;
docs/benchmarks.md maps these FPS to the paper's reporting.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_meta, row_mode, row_tag, time_fn
from repro.core import bank as bank_lib
from repro.core.filters import get_filter, make_imm
from repro.core.tracker import TrackerConfig, frame_step, imm_frame_step

BENCH_JSON = pathlib.Path(__file__).resolve().parents[1] / "BENCH_frame.json"

WARM_FRAMES = 6  # spawn + confirm tracks before the timed frame


def _scene_frames(m: int, M: int, T: int, n_targets: int, seed: int):
    """(T, M, m) measurement stream + validity: n_targets slow random
    walks in the first slots, the rest of the M budget empty — the
    static-shape serving frame shape."""
    rng = np.random.default_rng(seed)
    pos = rng.normal(size=(n_targets, m)).astype(np.float64) * 3.0
    z = np.zeros((T, M, m), np.float32)
    v = np.zeros((T, M), bool)
    for t in range(T):
        pos = pos + rng.normal(size=pos.shape) * 0.05
        z[t, :n_targets] = pos + rng.normal(size=pos.shape) * 0.05
        v[t, :n_targets] = True
    return z, v


def _steps(model, cfg: TrackerConfig):
    base = imm_frame_step if hasattr(model, "models") else frame_step
    return jax.jit(lambda b, z, v: base(model, cfg, b, z, v))


def _init(model, cfg: TrackerConfig):
    if hasattr(model, "models"):
        return bank_lib.init_imm_bank(model, cfg.capacity)
    return bank_lib.init_bank(model, cfg.capacity)


def _bench_single(csv: List[str], rows: list, kind: str, model, C: int,
                  M: int) -> None:
    cfg_f = TrackerConfig(capacity=C, max_meas=M)
    cfg_e = dataclasses.replace(cfg_f, fused_frame=False)
    step_f, step_e = _steps(model, cfg_f), _steps(model, cfg_e)
    n_targets = max(2, min(M - 2, C // 4, 24))
    z, v = _scene_frames(model.m, M, WARM_FRAMES + 1, n_targets, seed=5)
    bank = _init(model, cfg_f)
    for t in range(WARM_FRAMES):
        bank = step_f(bank, jnp.asarray(z[t]), jnp.asarray(v[t])).bank
    zt, vt = jnp.asarray(z[WARM_FRAMES]), jnp.asarray(v[WARM_FRAMES])
    # equivalence gate before anything is timed: identical association,
    # float32-tolerance states (the CI smoke run keeps only this part)
    rf, re = step_f(bank, zt, vt), step_e(bank, zt, vt)
    np.testing.assert_array_equal(np.asarray(rf.assoc), np.asarray(re.assoc))
    np.testing.assert_allclose(np.asarray(rf.bank.x), np.asarray(re.bank.x),
                               atol=5e-4, rtol=5e-4)
    row = dict(kind=kind, C=C, M=M, active=int(np.asarray(bank.active).sum()))
    for name, step in (("fused", step_f), ("einsum", step_e)):
        fn = lambda s=step: s(bank, zt, vt).bank.x
        # best-of-rounds: min is robust to the container's noisy
        # scheduler (the protocol every other bench here uses; 5 rounds
        # because the frame's sequential assignment loop is the most
        # stall-sensitive thing in the repo)
        sec = min(time_fn(fn, iters=5, warmup=1) for _ in range(5))
        pallas = name == "fused"  # einsum route is XLA on every backend
        row[name] = dict(us_per_frame=sec * 1e6, steps_per_sec=1.0 / sec,
                         **row_mode(pallas))
        csv.append(f"frame/{kind}/{name}/C={C},{sec * 1e6:.1f},"
                   f"steps_per_sec={1.0 / sec:.1f};{row_tag(pallas)}")
    row["speedup_fused_vs_einsum"] = (row["fused"]["steps_per_sec"]
                                      / row["einsum"]["steps_per_sec"])
    csv.append(f"frame/{kind}/speedup_fused_vs_einsum/C={C},0,"
               f"x{row['speedup_fused_vs_einsum']:.2f}")
    rows.append(row)


def _bench_sharded(csv: List[str], out: list, S: int, T: int) -> None:
    """8-sensor IMM fleet frames/sec, fused vs einsum frame route,
    over 1/8 host devices (``ShardedBankEngine``; one frame = all S
    sensors serviced)."""
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import ShardedBankEngine

    imm = make_imm()
    n_dev = len(jax.devices())
    rng = np.random.default_rng(7)
    cfg_f = TrackerConfig(capacity=16, max_meas=8)
    cfg_e = dataclasses.replace(cfg_f, fused_frame=False)
    z = np.zeros((T, S, cfg_f.max_meas, imm.m), np.float32)
    v = np.zeros((T, S, cfg_f.max_meas), bool)
    pos = rng.normal(size=(S, 2, imm.m)) * 3
    for t in range(T):
        pos = pos + 0.05
        z[t, :, :2] = pos + rng.normal(size=pos.shape) * 0.05
        v[t, :, :2] = True
    for d in (1, 8):
        if d > n_dev or S % d:
            csv.append(f"frame/sharded/devices={d}/S={S},0,"
                       f"skipped=need {d} devices dividing S={S}")
            out.append(dict(devices=d, S=S, skipped=True))
            continue
        mesh = make_mesh((d,), ("data",))
        row = dict(devices=d, S=S)
        results = {}
        for name, cfg in (("fused", cfg_f), ("einsum", cfg_e)):
            eng = ShardedBankEngine(imm, S, cfg, mesh=mesh)
            results[name] = res = []
            # the engine warms its compile in __init__; dropping frame 0
            # from the stats anyway makes the steady-state methodology
            # explicit (matches the single-sensor rows' warmup)
            res.append(eng.frame(z[0], v[0]))
            eng.stats = type(eng.stats)()
            for t in range(1, T):
                res.append(eng.frame(z[t], v[t]))
            fps = eng.stats.fps
            pallas = name == "fused"
            row[name] = dict(frames_per_sec=fps, **row_mode(pallas))
            csv.append(f"frame/sharded/{name}/devices={d}/S={S},"
                       f"{1e6 / fps:.1f},frames_per_sec={fps:.1f};"
                       f"{row_tag(pallas)}")
        # the same equivalence gate as the single-sensor rows, under the
        # mesh: identical association + ids, close combined states,
        # every frame (comparisons happen outside eng.frame, so the
        # timed stats are untouched)
        for rf, re in zip(results["fused"], results["einsum"]):
            np.testing.assert_array_equal(np.asarray(rf.assoc),
                                          np.asarray(re.assoc))
            np.testing.assert_array_equal(np.asarray(rf.bank.track_id),
                                          np.asarray(re.bank.track_id))
            np.testing.assert_allclose(np.asarray(rf.x_est),
                                       np.asarray(re.x_est),
                                       atol=5e-4, rtol=5e-4)
        row["speedup_fused_vs_einsum"] = (row["fused"]["frames_per_sec"]
                                          / row["einsum"]["frames_per_sec"])
        out.append(row)


def run(csv: List[str], Cs=(64, 256, 1024), M: int = 64,
        sensors: int = 8, sensor_frames: int = 24) -> None:
    rows: list = []
    models = (("lkf", get_filter("lkf")), ("imm", make_imm()))
    for kind, model in models:
        for C in Cs:
            _bench_single(csv, rows, kind, model, C, M)
    sharded: list = []
    _bench_sharded(csv, sharded, sensors, sensor_frames)
    headline = next((r["speedup_fused_vs_einsum"] for r in rows
                     if r["kind"] == "lkf" and r["C"] == 256), None)
    BENCH_JSON.write_text(json.dumps(dict(
        bench="frame", meta=bench_meta(), M=M,
        rows=rows, sharded=sharded,
        speedup_lkf_c256=headline,
        notes=("fused = one katana_frame/katana_imm_frame Pallas "
               "dispatch per frame (TrackerConfig.fused_frame, the "
               "serving default); einsum = the predict_bank -> "
               "mahalanobis_cost -> greedy_assign -> update_bank XLA "
               "chain (equivalence oracle). Every row asserts identical "
               "assoc + float32-tolerance states before timing. "
               "sharded rows: 8-sensor IMM ShardedBankEngine fleet "
               "frames/sec. Read each row's mode/lowering stamp: "
               "einsum rows are compiled XLA everywhere, fused rows "
               "are interpret-stamped on CPU (overweighting per-op "
               "dispatch overhead vs TPU silicon); see "
               "docs/benchmarks.md for the paper-FPS mapping."),
    ), indent=2) + "\n")
