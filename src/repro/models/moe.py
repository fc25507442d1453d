"""Mixture-of-Experts with static-capacity scatter dispatch + expert
parallelism over the mesh ``model`` axis.

Design notes (DESIGN.md §5):
  * Static shapes everywhere (KATANA Opt-2): capacity-bounded buffers,
    token drops instead of dynamic shapes. ``capacity_mode='full'``
    (decode/prefill) sets capacity = local token count — zero drops.
  * Dispatch is a scatter-add into an (E_local, C, d) buffer and a
    gather back — O(T·k·d) bytes, *not* the O(T·E·C·d) one-hot einsum
    dispatch whose FLOPs would rival the expert GEMMs themselves.
  * Expert parallelism via shard_map: each model-shard owns E/TP
    experts; tokens are data-sharded and replicated over `model`; the
    only collective is one psum of the (T_local, d) output over `model`
    (same traffic class as a TP all-reduce).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig


def moe_init(key, cfg: MoEConfig, d: int, act: str, dtype) -> Dict:
    E, f = cfg.num_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, E)) * s_in).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (E, d, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ks[2], (E, f, d)) * s_out).astype(dtype),
    }
    if act == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[3], (E, d, f)) * s_in).astype(dtype)
    return p


def moe_spec(act: str) -> Dict:
    # "moe_d"/"moe_f" resolve per ShardingContext.moe_weight_mode:
    #   gather: moe_d -> FSDP data axes, moe_f -> replicated
    #   tp2d:   moe_d -> replicated,     moe_f -> data axes
    p = {
        "router": (None, None),
        "w_in": ("experts", "moe_d", "moe_f"),
        "w_out": ("experts", "moe_f", "moe_d"),
    }
    if act == "swiglu":
        p["w_gate"] = ("experts", "moe_d", "moe_f")
    return p


def _capacity(cfg: MoEConfig, t_local: int, mode: str) -> int:
    if mode == "full":
        return t_local
    c = int(np.ceil(t_local * cfg.top_k * cfg.capacity_factor / cfg.num_experts))
    return max(8, min(t_local, -(-c // 8) * 8))  # 8-aligned, bounded


def _moe_shard(x, p, cfg: MoEConfig, act: str, e_first, e_local: int,
               capacity: int, model_axis: Optional[str]):
    """Per-device MoE: x (T, d) local tokens; expert weights local slices."""
    T, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    logits = (x.astype(jnp.float32) @ p["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)  # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert queue, computed over
    # the flattened (T*k,) routing stream (deterministic, static shapes)
    flat_e = topi.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # position before self
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < capacity

    local_slot = flat_e - e_first
    mine = keep & (local_slot >= 0) & (local_slot < e_local)
    slot_c = jnp.clip(local_slot, 0, e_local - 1)
    pos_c = jnp.clip(flat_pos, 0, capacity - 1)

    tok_idx = jnp.repeat(jnp.arange(T), k)  # (T*k,)
    updates = x[tok_idx] * mine[:, None].astype(x.dtype)
    buf = jnp.zeros((e_local, capacity, d), x.dtype)
    buf = buf.at[slot_c, pos_c].add(updates, mode="drop")

    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    if act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        h = jax.nn.silu(g) * h
    elif act == "squared_relu":
        r = jax.nn.relu(h)
        h = r * r
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])  # (E_loc, C, d)

    gathered = y[slot_c, pos_c]  # (T*k, d)
    w = (topw.reshape(-1) * mine.astype(jnp.float32)).astype(x.dtype)
    out = (gathered * w[:, None]).reshape(T, k, d).sum(axis=1)

    # load-balance auxiliary (Switch-style), local shard estimate
    frac = onehot.astype(jnp.float32).mean(axis=0) * k  # fraction routed
    mean_p = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_p) / k

    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out, aux


def apply_moe(p: Dict, x: jnp.ndarray, cfg: MoEConfig, act: str,
              ctx=None, capacity_mode: str = "factor") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (B, S, d), aux-loss scalar.

    ctx: repro.sharding.ShardingContext or None (single-device path).
    """
    B, S, d = x.shape
    if (ctx is None or ctx.mesh is None or ctx.model_size == 1
            or cfg.num_experts % ctx.model_size != 0):
        t_loc = B * S
        cap = _capacity(cfg, t_loc, capacity_mode)
        out, aux = _moe_shard(x.reshape(t_loc, d), p, cfg, act, 0,
                              cfg.num_experts, cap, None)
        return out.reshape(B, S, d), aux

    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    # tokens replicated over model; data-sharded only when divisible
    # (long-context decode runs B=1: tokens replicated everywhere, the
    # parallelism lives in the experts/cache instead)
    dp = ctx.data_axes if B % ctx.data_size == 0 else ()
    tp = ctx.model_axis  # 'model'
    e_local = cfg.num_experts // ctx.model_size
    t_loc = (B // ctx.data_size if dp else B) * S
    cap = _capacity(cfg, t_loc, capacity_mode)

    tp2d = (ctx.moe_weight_mode == "tp2d"
            and cfg.d_ff_expert % ctx.data_size == 0 and ctx.data_size > 1)
    if tp2d:
        return _apply_moe_tp2d(p, x, cfg, act, ctx, capacity_mode)

    # "gather" mode: expert weights are 2D-sharded — experts over
    # `model` AND the embed dim FSDP'd over the data axes (a 398B Jamba
    # or 235B Qwen cannot hold even one expert-shard replicated per data
    # rank). The gather back to full-d happens HERE, explicitly, in bf16
    # — without it the partitioner un-FSDPs outside the shard_map in f32
    # (2x wire + full temps; see EXPERIMENTS.md §Perf log).
    fsdp_moe = ctx.fsdp and d % ctx.data_size == 0 and ctx.data_size > 1
    wspec_in = P(tp, ctx.data_axes if fsdp_moe else None, None)
    wspec_out = P(tp, None, ctx.data_axes if fsdp_moe else None)

    def shard_fn(x_l, router, w_in, w_out, *rest):
        if fsdp_moe:
            w_in = jax.lax.all_gather(w_in, ctx.data_axes, axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, ctx.data_axes, axis=2,
                                       tiled=True)
        pl = {"router": router, "w_in": w_in, "w_out": w_out}
        if rest:
            wg = rest[0]
            if fsdp_moe:
                wg = jax.lax.all_gather(wg, ctx.data_axes, axis=1, tiled=True)
            pl["w_gate"] = wg
        b_l, s_l, _ = x_l.shape
        e_first = jax.lax.axis_index(tp) * e_local
        out, aux = _moe_shard(x_l.reshape(b_l * s_l, d), pl, cfg, act,
                              e_first, e_local, cap, tp)
        # average the aux estimate over data shards
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out.reshape(b_l, s_l, d), aux

    args = [x, p["router"], p["w_in"], p["w_out"]]
    in_specs = [P(dp if dp else None, None, None), P(None, None),
                wspec_in, wspec_out]
    if "w_gate" in p:
        args.append(p["w_gate"])
        in_specs.append(wspec_in)
    out, aux = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(dp if dp else None, None, None), P()),
        check_vma=False,  # all_gather over the FSDP axes un-varies the
        # weights; the static VMA checker can't see that.
    )(*args)
    return out, aux


def _apply_moe_tp2d(p: Dict, x: jnp.ndarray, cfg: MoEConfig, act: str,
                    ctx, capacity_mode: str):
    """Decode-optimized MoE: experts over `model` x FFN dim over the
    data axes. ZERO weight movement per step — tokens are replicated
    over the data axes (a few MB at decode batch sizes) and the single
    collective is one psum of the (T, d) output over the whole mesh.
    The win vs "gather" at decode: GB-scale per-layer weight all-gathers
    become MB-scale activation reductions (EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    mesh = ctx.mesh
    tp = ctx.model_axis
    dpx = ctx.data_axes
    e_local = cfg.num_experts // ctx.model_size
    t_all = B * S
    cap = _capacity(cfg, t_all, capacity_mode)

    def shard_fn(x_l, router, w_in, w_out, *rest):
        # x_l: full tokens (replicated over the mesh); weights:
        # (E_loc, d, f_loc) / (E_loc, f_loc, d)
        pl = {"router": router, "w_in": w_in, "w_out": w_out}
        if rest:
            pl["w_gate"] = rest[0]
        e_first = jax.lax.axis_index(tp) * e_local
        out, aux = _moe_shard(x_l.reshape(t_all, d), pl, cfg, act,
                              e_first, e_local, cap, None)
        # out is partial over BOTH the expert dim (tp) and the FFN-dim
        # contraction (dp): one fused all-reduce completes it.
        out = jax.lax.psum(out, dpx + (tp,))
        return out.reshape(B, S, d), aux

    args = [x, p["router"], p["w_in"], p["w_out"]]
    in_specs = [P(None, None, None), P(None, None),
                P(tp, None, dpx), P(tp, dpx, None)]
    if "w_gate" in p:
        args.append(p["w_gate"])
        in_specs.append(P(tp, None, dpx))
    out, aux = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(P(None, None, None), P()),
        check_vma=False,
    )(*args)
    return out, aux
