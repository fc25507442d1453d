"""Katana-kernel roofline: achieved FLOPs/bytes of the COMPILED
programs vs the three-term roofline model.

For each stage of the serving path — the fused multi-frame scan
(``katana_bank_sequence``), its XLA-native twin (the batched_lanes
einsum stage under ``lax.scan``), the fused IMM scan
(``katana_imm_sequence``) and the live frame (``tracker.frame_step``,
fused and einsum routes) — this bench:

  * compiles the program (``jit(...).lower(...).compile()``) and reads
    XLA's ``cost_analysis()`` FLOPs + bytes-accessed, plus an
    optimized-HLO op census (``repro.roofline.hlo.op_census``);
  * computes the ANALYTIC useful-work floor (the paper's §IV-D
    mul/add count per filter step, ``benchmarks.batching.useful_flops``,
    extended to IMM mixing) and the minimal HBM crossings (measurement
    stream in, estimates out, bank once per chunk);
  * evaluates the three-term roofline on the backend's ``Machine``
    (``repro.roofline.analysis``) and times the real call —
    ``roofline_fraction`` = analytic bound / measured wall-clock is the
    honest "how far from the roofline" number, ``useful_fraction`` =
    useful / compiled FLOPs the arithmetic-overhead number (the axis
    Cerati et al. and Tithi et al. show small-matrix tracking lives or
    dies on).

Rows land in BENCH_roofline.json with the execution mode stamped per
row — a Pallas program that ran through the interpreter is labelled
``mode=interpret`` and its cost_analysis reflects the EMULATED op
stream, which is exactly the conflation this file exists to make
visible (the XLA rows are compiled code on every backend, CPU
included). Variants a backend can't run emit explicit ``skip=`` rows
(batching.py's convention), never silence.

The legacy dry-run table reader (``load_cells`` / ``table``, consumed
by benchmarks/make_tables.py) is kept below; its ``results/dryrun/``
artifacts don't exist in this repo, and ``run`` now says so with an
explicit skip row instead of silently emitting nothing.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.batching import useful_flops
from benchmarks.common import bench_meta, compiled_of, row_mode, time_fn
from repro.core.filters import get_filter, make_imm
from repro.core.rewrites import build_stage
from repro.execmode import active_mode
from repro.kernels.katana_bank.ops import (katana_bank_sequence,
                                           katana_imm_sequence)
from repro.roofline.analysis import machine_for, terms_on
from repro.roofline.hlo import op_census

BENCH_JSON = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_roofline.json"
RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "dryrun"

F32 = 4  # bytes


def imm_useful_flops(n: int, m: int, K: int) -> float:
    """Per-track IMM frame mul/adds: K model-conditioned KF steps plus
    the mixing moment spread (K^2 weighted (P + x x^T) accumulations)
    and the moment-matched combination."""
    mix = K * K * (2 * n * n + 2 * n) + K * (2 * n * n + 2 * n)
    return K * useful_flops(n, m) + mix


def _cost_of(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)))


def _row(csv: List[str], rows: list, name: str, fn, args, pallas: bool,
         model_flops: float, model_bytes: float, machine,
         cost_probe=None) -> None:
    """Compile + census + time one program; append the csv/json row.

    ``cost_probe=(probe_fn, probe_args, scale)`` overrides the
    flops/bytes source: XLA's ``cost_analysis()`` counts a ``lax.scan``
    body ONCE (analysis.py's documented caveat), so scan-over-time
    programs cost the per-frame body and scale by T instead of trusting
    the scan program's own (T-independent) counters.
    """
    jfn = jax.jit(fn)
    compiled = jfn.lower(*args).compile()
    census = op_census(compiled.as_text())
    if cost_probe is not None:
        probe_fn, probe_args, scale = cost_probe
        cost = _cost_of(compiled_of(probe_fn, *probe_args))
        cost = dict(flops=cost["flops"] * scale, bytes=cost["bytes"] * scale)
    else:
        cost = _cost_of(compiled)
    sec = min(time_fn(jfn, *args, iters=3, warmup=1) for _ in range(3))
    terms = terms_on(machine, cost["flops"], cost["bytes"],
                     model_flops_dev=model_flops)
    model_terms = terms_on(machine, model_flops, model_bytes,
                           model_flops_dev=model_flops)
    row = dict(
        name=name, **row_mode(pallas),
        measured_us=sec * 1e6,
        hlo_flops=cost["flops"], hlo_bytes=cost["bytes"],
        model_flops=model_flops, model_bytes=model_bytes,
        useful_fraction=(model_flops / cost["flops"]
                         if cost["flops"] else 0.0),
        intensity_hlo=(cost["flops"] / cost["bytes"]
                       if cost["bytes"] else 0.0),
        intensity_model=(model_flops / model_bytes
                         if model_bytes else 0.0),
        t_compute_us=terms.t_compute * 1e6,
        t_memory_us=terms.t_memory * 1e6,
        dominant=terms.dominant,
        bound_us=model_terms.bound * 1e6,
        roofline_fraction=(model_terms.bound / sec if sec else 0.0),
        achieved_gflops=cost["flops"] / sec / 1e9 if sec else 0.0,
        cost_probe=("per-step-x-T" if cost_probe is not None
                    else "whole-program"),
        op_census=census,
    )
    rows.append(row)
    csv.append(
        f"roofline/{name},{sec * 1e6:.1f},"
        f"mode={row['mode']};lowering={row['lowering']};"
        f"useful={row['useful_fraction']:.4f};dom={row['dominant']};"
        f"roofline_frac={row['roofline_fraction']:.4f}")


def run(csv: List[str], Ns=(256,), T: int = 32, C: int = 256,
        M: int = 64) -> None:
    mode = active_mode()
    machine = machine_for(jax.devices()[0].device_kind)
    rows: list = []
    lkf = get_filter("lkf")
    imm = make_imm()
    rng = np.random.default_rng(11)

    for N in Ns:
        zs = jnp.asarray(rng.normal(size=(T, N, lkf.m)) * 0.5, jnp.float32)
        x0 = jnp.asarray(np.tile(lkf.x0, (N, 1)), jnp.float32)
        P0 = jnp.asarray(np.tile(lkf.P0, (N, 1, 1)), jnp.float32)
        kf_flops = useful_flops(lkf.n, lkf.m) * N * T
        scan_bytes = (T * N * (lkf.m + lkf.n) * F32
                      + 2 * N * (lkf.n + lkf.n * lkf.n) * F32)

        # the fused Pallas scan — the kernel whose compiled-mode truth
        # this whole file exists to report
        _row(csv, rows, f"fused_scan/N={N}",
             lambda zs, x0, P0: katana_bank_sequence(
                 lkf, zs, x0, P0, interpret=mode.interpret),
             (zs, x0, P0), True, kf_flops, scan_bytes, machine)

        # the XLA-native twin: compiled code on every backend
        lanes_step, _ = build_stage(lkf, "batched_lanes", N=N)

        def lanes_scan(zs, x0, P0):
            def body(carry, z_t):
                x, P = lanes_step(*carry, z_t)
                return (x, P), x
            _, xs = jax.lax.scan(body, (x0, P0), zs)
            return xs

        _row(csv, rows, f"lanes_scan/N={N}", lanes_scan, (zs, x0, P0),
             False, kf_flops, scan_bytes, machine,
             cost_probe=(lanes_step, (x0, P0, zs[0]), T))

        # the fused IMM scan (mixing + mode posterior in-kernel)
        zs9 = jnp.asarray(rng.normal(size=(T, N, imm.m)) * 0.5, jnp.float32)
        x9 = jnp.asarray(np.tile(imm.models[0].x0, (N, 1)), jnp.float32)
        P9 = jnp.asarray(np.tile(imm.models[0].P0, (N, 1, 1)), jnp.float32)
        imm_flops = imm_useful_flops(imm.n, imm.m, imm.K) * N * T
        imm_bytes = (T * N * (imm.m + imm.n) * F32
                     + 2 * imm.K * N * (imm.n + imm.n * imm.n) * F32
                     + 2 * imm.K * N * F32)
        _row(csv, rows, f"imm_scan/N={N}",
             lambda zs, x0, P0: katana_imm_sequence(
                 imm, zs, x0, P0, interpret=mode.interpret),
             (zs9, x9, P9), True, imm_flops, imm_bytes, machine)

    # the live frame, both routes through tracker.frame_step — one
    # frame's measurement cycle incl. gating + assignment + lifecycle
    from benchmarks.frame import _init, _scene_frames, _steps
    from repro.core.tracker import TrackerConfig

    cfg_f = TrackerConfig(capacity=C, max_meas=M)
    cfg_e = dataclasses.replace(cfg_f, fused_frame=False)
    n_targets = max(2, min(M - 2, C // 4, 24))
    z, v = _scene_frames(lkf.m, M, 4, n_targets, seed=13)
    frame_flops = (useful_flops(lkf.n, lkf.m) * C
                   + C * M * (2 * lkf.m * lkf.m + 2 * lkf.m))
    frame_bytes = (2 * C * (lkf.n + lkf.n * lkf.n) * F32
                   + M * lkf.m * F32 + 2 * C * F32)
    for name, cfg, pallas in (("frame_fused", cfg_f, True),
                              ("frame_einsum", cfg_e, False)):
        step = _steps(lkf, cfg)
        bank = _init(lkf, cfg)
        for t in range(3):
            bank = step(bank, jnp.asarray(z[t]), jnp.asarray(v[t])).bank
        zt, vt = jnp.asarray(z[3]), jnp.asarray(v[3])
        _row(csv, rows, f"{name}/C={C}",
             lambda b, zz, vv: step(b, zz, vv).bank.x, (bank, zt, vt),
             pallas, frame_flops, frame_bytes, machine)

    # a natively-compiled Pallas variant is a different program than the
    # interpreter emulation — say so explicitly instead of pretending
    # the interpreted census covers it
    if mode.interpret:
        for name in ("fused_scan", "imm_scan", "frame_fused"):
            csv.append(f"roofline/{name}/pallas-compiled,0,"
                       f"skip=pallas-lowering-unsupported:{mode.backend}")

    dryrun_note = _legacy_dryrun(csv)

    BENCH_JSON.write_text(json.dumps(dict(
        bench="roofline", meta=bench_meta(),
        machine=dict(name=machine.name, peak_flops=machine.peak_flops,
                     mem_bw=machine.mem_bw),
        T=T, C=C, M=M, rows=rows, dryrun=dryrun_note,
        notes=("useful_fraction = analytic mul/add floor / compiled HLO "
               "flops (cost_analysis). mode=interpret rows census the "
               "Pallas interpreter's EMULATED op stream — the number "
               "that makes interpret-vs-compiled conflation visible; "
               "mode=compiled rows (xla lowering on CPU, pallas on "
               "TPU/GPU) are real compiled code. bound_us is the "
               "three-term roofline on the backend Machine from the "
               "analytic floor; roofline_fraction = bound/measured."),
    ), indent=2) + "\n")


def _legacy_dryrun(csv: List[str]) -> str:
    """The old results/dryrun reader: explicit skip row when absent
    (always, in this repo) instead of silently contributing nothing."""
    cells = load_cells("single") + load_cells("multi")
    if not cells:
        csv.append("roofline/dryrun,0,skip=no results/dryrun artifacts "
                   "(repro.launch.dryrun writes them)")
        return "skipped: no results/dryrun artifacts"
    for rec in cells:
        tag = f"roofline/{rec['mesh']}/{rec['arch']}/{rec['shape']}"
        if not rec.get("supported", True):
            csv.append(f"{tag},0,skip={rec['skip_reason']}")
            continue
        r = rec.get("roofline")
        if not r:
            csv.append(f"{tag},0,no-probe")
            continue
        bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        csv.append(
            f"{tag},{bound * 1e6:.0f},"
            f"tc={r['t_compute_s']:.4f};tm={r['t_memory_s']:.4f};"
            f"tcoll={r['t_collective_s']:.4f};dom={r['dominant']};"
            f"useful={r['useful_fraction']:.3f};"
            f"roofline_frac={r['roofline_fraction']:.4f}")
    return f"{len(cells)} dryrun cells"


def load_cells(mesh: str):
    cells = []
    root = RESULTS / mesh
    if not root.exists():
        return cells
    for f in sorted(root.glob("*/*.json")):
        cells.append(json.loads(f.read_text()))
    return cells


def table(mesh: str = "single") -> str:
    """Markdown table for EXPERIMENTS.md (dry-run cells)."""
    rows = [
        "| arch | shape | T_comp (s) | T_mem (s) | T_coll (s) | dominant "
        "| useful | roofline frac | fits 16G (tpu-est) |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in load_cells(mesh):
        if not rec.get("supported", True):
            rows.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | "
                        f"skipped: {rec['skip_reason']} | — | — | — |")
            continue
        r = rec.get("roofline", {})
        f = rec.get("full", {})
        if not r:
            continue
        rows.append(
            f"| {rec['arch']} | {rec['shape']} | {r['t_compute_s']:.4g} "
            f"| {r['t_memory_s']:.4g} | {r['t_collective_s']:.4g} "
            f"| {r['dominant']} | {r['useful_fraction']:.3f} "
            f"| {r['roofline_fraction']:.4f} "
            f"| {f.get('fits_16g_tpu_est', '—')} |")
    return "\n".join(rows)
