import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
"""Multi-pod dry-run (deliverable e) + roofline measurement artifacts.

For every (arch x shape x mesh) cell:
  1. FULL compile (scan-over-groups): .lower().compile() must succeed;
     memory_analysis() proves per-device residency; wall compile time
     recorded. This is the compile-proof on the production mesh.
  2. COST PROBES (unrolled, depth p and 2p, microbatches=1): FLOPs /
     bytes / collective wire-bytes extrapolated to full depth
     (cost_analysis counts scan bodies once — DESIGN.md §4).
Artifacts land in results/dryrun/<mesh>/<arch>/<shape>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen3-moe-235b-a22b \
      --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ALL_SHAPES, RunConfig, cell_supported, get_config,
                           get_shape, list_archs)
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_decode_step, make_encode_step,
                                make_prefill_step, make_train_step)
from repro.models import model as model_lib
from repro.models.counting import model_flops
from repro.optim import adamw
from repro.roofline import hlo as hlo_lib
from repro.roofline.analysis import HBM_BW, extrapolate, terms_from
from repro.roofline.memmodel import analytic_bytes_dev
from repro.sharding.rules import make_context

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _default_run(shape, cfg=None) -> RunConfig:
    mb = 8 if shape.kind == "train" else 1
    # big residual streams can't afford selective-remat activation
    # residency at mb=8 (e.g. qwen3: 21 GB/dev of saved qkv/moe hiddens)
    remat = "full" if (cfg is not None and cfg.d_model >= 4096) else "selective"
    return RunConfig(microbatches=mb, remat=remat)


def _lower_cell(cfg, shape, run, ctx):
    """Build (fn, example args with shardings applied via in_shardings)."""
    mesh = ctx.mesh
    bspecs = specs_lib.batch_specs(cfg, shape, run)
    bshard = specs_lib.batch_shardings(cfg, shape, run, ctx)
    if shape.kind == "train":
        astate = adamw.abstract_train_state(
            model_lib.abstract_params(cfg), run.grad_compression)
        sshard = specs_lib.state_shardings(cfg, run, ctx)
        fn = make_train_step(cfg, run, ctx)
        jit = jax.jit(fn, in_shardings=(sshard, bshard),
                      out_shardings=(sshard, None), donate_argnums=(0,))
        return jit, (astate, bspecs)
    aparams = model_lib.abstract_params(cfg)
    pshard = specs_lib.param_shardings(cfg, ctx)
    if shape.kind == "prefill":
        if cfg.is_encoder_only:
            fn = make_encode_step(cfg, ctx)
            jit = jax.jit(fn, in_shardings=(pshard, bshard))
            return jit, (aparams, bspecs)
        fn = make_prefill_step(cfg, ctx)
        cshard = specs_lib.cache_shardings(cfg, shape, ctx)
        jit = jax.jit(fn, in_shardings=(pshard, bshard),
                      out_shardings=(None, cshard))
        return jit, (aparams, bspecs)
    # decode
    acache = specs_lib.cache_specs(cfg, shape)
    cshard = specs_lib.cache_shardings(cfg, shape, ctx)
    fn = make_decode_step(cfg, ctx)
    jit = jax.jit(fn, in_shardings=(pshard, bshard, cshard),
                  out_shardings=(None, cshard), donate_argnums=(2,))
    return jit, (aparams, bspecs, acache)


def _compile_cell(cfg, shape, run, ctx):
    jit, args = _lower_cell(cfg, shape, run, ctx)
    t0 = time.time()
    lowered = jit.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return lowered, compiled, t_lower, t_compile


def _cost_dict(compiled):
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    cc = hlo_lib.collective_census(txt)
    tot = hlo_lib.totals(cc)
    return {
        "flops_dev": float(ca.get("flops", 0.0)),
        "bytes_dev": float(ca.get("bytes accessed", 0.0)),
        "coll_wire_bytes_dev": tot["wire_bytes"],
        "coll_wire_bytes_bf16eq_dev": tot["wire_bytes_bf16eq"],
        "coll_operand_bytes_dev": tot["operand_bytes"],
        "coll_count": tot["count"],
    }, cc


def probe_depths(cfg):
    p = cfg.interleave_period()
    return p, 2 * p


def run_cell(arch: str, shape_name: str, mesh_name: str, probes: bool = True,
             run: RunConfig = None, out_root: Path = RESULTS,
             full_compile: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cell_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "supported": ok}
    out_dir = out_root / mesh_name / arch
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{shape_name}.json"
    if out_path.exists() and not full_compile:
        # probe-only refresh: keep the existing full-compile record
        old = json.loads(out_path.read_text())
        if "full" in old:
            rec["full"] = old["full"]
    if not ok:
        rec["skip_reason"] = why
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    run = run or _default_run(shape, cfg)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size
    ctx = make_context(
        mesh, fsdp=run.fsdp,
        attn_impl="flash" if run.attn_kernel == "flash" else "auto",
        moe_weight_mode=run.moe_weight_mode)

    if full_compile:
        _, compiled, t_lower, t_compile = _compile_cell(cfg, shape, run, ctx)
        ma = compiled.memory_analysis()
        upcast = hlo_lib.cpu_upcast_bytes(compiled.as_text())
        total_dev = ma.argument_size_in_bytes + ma.temp_size_in_bytes
        corrected = max(ma.argument_size_in_bytes,
                        total_dev - upcast)
        rec["full"] = {
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "arg_bytes_dev": int(ma.argument_size_in_bytes),
            "out_bytes_dev": int(ma.output_size_in_bytes),
            "temp_bytes_dev": int(ma.temp_size_in_bytes),
            "total_bytes_dev": int(total_dev),
            # XLA:CPU legalizes bf16 dots via hoisted f32 converts; TPU
            # runs bf16 natively, so those buffers vanish on the target.
            "cpu_upcast_bytes_dev": int(upcast),
            "total_bytes_dev_tpu_est": int(corrected),
            "fits_16g": total_dev < 16e9,
            "fits_16g_tpu_est": corrected < 16e9,
        }
        cost_full, cc_full = _cost_dict(compiled)
        rec["full"]["cost_scanned"] = cost_full  # NB: scan bodies counted 1x
        del compiled

    if probes:
        p, p2 = probe_depths(cfg)
        prun = dataclasses.replace(run, microbatches=1)
        # probe context: full-einsum attention + unrolled SSD chunk scan
        # so cost_analysis sees every FLOP (inner lax.scan bodies are
        # costed once — DESIGN.md §4); AOT lowering never allocates, so
        # the S^2 score tensor is free here.
        pctx = dataclasses.replace(ctx, attn_impl="full", probe_unroll=True)
        costs = {}
        for L in (p, p2):
            pcfg = dataclasses.replace(cfg, n_layers=L)
            _, compiled, _, tc = _compile_cell(pcfg, shape, prun, pctx)
            costs[L], _ = _cost_dict(compiled)
            costs[L]["compile_s"] = tc
            del compiled
        cost = extrapolate(costs[p], costs[p2], p, cfg.n_layers)
        rec["probe"] = {"p": p, "c_p": costs[p], "c_2p": costs[p2],
                        "extrapolated": cost}
        mf = model_flops(cfg, shape)
        rec["model_flops_total"] = mf
        rec["n_chips"] = n_chips
        ab = analytic_bytes_dev(cfg, shape, run, n_chips,
                                model_size=ctx.model_size)
        rec["analytic_bytes_dev"] = ab
        t = terms_from(cost["flops_dev"], ab, cost["coll_wire_bytes_dev"],
                       model_flops_dev=mf / n_chips)
        rec["roofline"] = {
            "t_compute_s": t.t_compute, "t_memory_s": t.t_memory,
            "t_memory_hlo_upper_s": cost["bytes_dev"] / HBM_BW,
            "t_collective_s": t.t_collective, "dominant": t.dominant,
            "useful_fraction": t.useful_fraction,
            "roofline_fraction": t.roofline_fraction,
        }
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--no-full", action="store_true",
                    help="skip the full-depth compile (probes only)")
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--moe-mode", default=None, choices=["gather", "tp2d"],
                    help="override the MoE weight strategy (hillclimb)")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default=None,
                    choices=["none", "selective", "full"])
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate non-MoE weights over the data axes "
                         "(decode serving mode)")
    ap.add_argument("--attn-kernel", default=None, choices=["xla", "flash"])
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = ([s.name for s in ALL_SHAPES]
              if (args.all or not args.shape) else [args.shape])

    failures = []
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                tag = f"{mesh_name}/{arch}/{shape_name}"
                t0 = time.time()
                run_override = None
                if (args.moe_mode or args.microbatches or args.remat
                        or args.no_fsdp or args.attn_kernel):
                    base = _default_run(get_shape(shape_name),
                                        get_config(arch))
                    run_override = dataclasses.replace(
                        base,
                        moe_weight_mode=args.moe_mode or base.moe_weight_mode,
                        microbatches=args.microbatches or base.microbatches,
                        remat=args.remat or base.remat,
                        fsdp=not args.no_fsdp,
                        attn_kernel=args.attn_kernel or base.attn_kernel)
                try:
                    rec = run_cell(arch, shape_name, mesh_name,
                                   probes=not args.no_probes,
                                   run=run_override,
                                   out_root=Path(args.out),
                                   full_compile=not args.no_full)
                    if not rec.get("supported", True):
                        print(f"[skip] {tag}: {rec['skip_reason']}")
                        continue
                    dom = rec.get("roofline", {}).get("dominant", "-")
                    fits = rec.get("full", {}).get("fits_16g", "-")
                    print(f"[ok]   {tag}  {time.time()-t0:6.1f}s  "
                          f"dominant={dom} fits16G={fits}", flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled")


if __name__ == "__main__":
    main()
