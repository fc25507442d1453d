"""Shared benchmark utilities: timing + HLO inspection + execution-mode
stamping (every BENCH row records how its code actually executed)."""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.execmode import active_mode


def bench_meta() -> Dict:
    """Top-level BENCH_*.json metadata: the execution mode, backend and
    jax version — so interpret-mode dispatch-count wins can never be
    conflated with compiled-mode wall-clock wins after the fact."""
    return active_mode().as_meta()


def row_mode(pallas: bool = True) -> Dict:
    """Per-row stamp: ``mode`` is "compiled" only for code that really
    compiled for this backend — XLA-native (einsum/lanes) formulations
    always, Pallas kernel dispatches only when the backend lowered them
    natively. ``lowering`` names the path ("xla" / "pallas" /
    "pallas-interpret"); ``backend`` the jax backend."""
    m = active_mode()
    return dict(mode=m.row_mode(pallas), lowering=m.lowering(pallas),
                backend=m.backend)


def row_tag(pallas: bool = True) -> str:
    """CSV-suffix form of ``row_mode`` for the harness's derived column."""
    r = row_mode(pallas)
    return f"mode={r['mode']};lowering={r['lowering']}"


def time_fn(fn: Callable, *args, iters: int = 50, warmup: int = 5,
            min_time_s: float = 0.0) -> float:
    """Mean wall seconds per call of a jitted fn (blocks on output)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    n = 0
    while True:
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        n += iters
        dt = time.perf_counter() - t0
        if dt >= min_time_s or n >= iters:
            return dt / n


def compiled_of(fn: Callable, *args):
    return jax.jit(fn).lower(*args).compile()


def hlo_op_counts(fn: Callable, *args, ops=("transpose", "reshape",
                                            "gather", "subtract", "dot",
                                            "add", "scatter")) -> Dict[str, int]:
    from repro.roofline.hlo import op_census

    return op_census(compiled_of(fn, *args).as_text(), ops)


def hlo_cost(fn: Callable, *args) -> Dict[str, float]:
    """XLA ``cost_analysis()`` of the compiled program: at least
    ``flops`` and ``bytes`` (the ``bytes accessed`` counter), 0.0 when
    the backend doesn't report a counter."""
    ca = compiled_of(fn, *args).cost_analysis()
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)))


def hlo_flops(fn: Callable, *args) -> float:
    return hlo_cost(fn, *args)["flops"]
