"""The main path's kernels compile for a TPU v5e — without a chip.

Each test lowers one program for a described ``v5e:2x2`` topology with
the Pallas kernels compiled (``interpret=False``) and compiles it with
the TPU compiler: the Mosaic layout rules and the 16 MiB scoped-VMEM
limit refuse here what the interpreter on CPU would happily run. Each
asserts that the compiled program holds the kernel (``tpu_custom_call``).
Nothing runs; a passing compile is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import execmode
from repro.core import bank as bank_lib
from repro.core.filters import make_ctra_ekf, make_cv_lkf, make_imm
from repro.core.tracker import TrackerConfig, make_multi_sensor_step
from repro.kernels.katana_bank.kernel import (LANE_TILE,
                                              katana_bank_imm_scan_step,
                                              katana_bank_scan_step,
                                              katana_frame_step,
                                              katana_imm_frame_step,
                                              scan_time_chunk)
from repro.kernels.katana_bank.ops import imm_track_tile

M = 64  # measurement slots per frame, the serving cells' width


@pytest.fixture(scope="module")
def one_chip():
    # without the TPU library there is no TPU compiler to rehearse
    # with; any other failure to describe the chip is a fault and raises
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("C", [256, 1024])
def test_lkf_frame_kernel_compiles(one_chip, C):
    model = make_cv_lkf()
    n, m = model.n, model.m
    _assert_kernel_compiles(
        lambda x, P, z, zv, a: katana_frame_step(
            model, x, P, z, zv, a, gate=11.34, rounds=min(C, M),
            interpret=False),
        _spec(one_chip, (n, C)), _spec(one_chip, (n, n, C)),
        _spec(one_chip, (m, M)), _spec(one_chip, (1, M)),
        _spec(one_chip, (1, C)))


@pytest.mark.parametrize("C", [128, 256, 1024])
def test_imm_frame_kernel_compiles(one_chip, C):
    imm = make_imm()
    K, n, m = imm.K, imm.n, imm.m
    _assert_kernel_compiles(
        lambda x, P, mu, z, zv, a: katana_imm_frame_step(
            imm, x, P, mu, z, zv, a, gate=11.34, rounds=min(C, M),
            interpret=False),
        _spec(one_chip, (K, n, C)), _spec(one_chip, (K, n, n, C)),
        _spec(one_chip, (K, C)), _spec(one_chip, (m, M)),
        _spec(one_chip, (1, M)), _spec(one_chip, (1, C)))


@pytest.mark.parametrize("kind", ["lkf", "ekf"])
def test_scan_kernel_compiles_at_default_time_chunk(one_chip, kind):
    """The static TPU time chunk fits the scoped VMEM at N=1024."""
    model = make_cv_lkf() if kind == "lkf" else make_ctra_ekf()
    n, m, N = model.n, model.m, 1024
    T = scan_time_chunk(n, m, LANE_TILE)
    assert T >= 300  # a 10 s, 30 fps replay is one dispatch
    _assert_kernel_compiles(
        lambda x, P, zs: katana_bank_scan_step(
            model, x, P, zs, lane_tile=LANE_TILE, interpret=False),
        _spec(one_chip, (n, N)), _spec(one_chip, (n, n, N)),
        _spec(one_chip, (T, m, N)))


def test_imm_scan_kernel_compiles_at_default_track_tile(one_chip):
    imm = make_imm()
    K, n, m, N, T = imm.K, imm.n, imm.m, 256, 64
    tile = imm_track_tile(K)
    assert tile % 128 == 0
    for with_valid in (False, True):  # replay, and replay with coasting
        _assert_kernel_compiles(
            lambda x, P, mu, zs: katana_bank_imm_scan_step(
                imm, x, P, mu, zs, lane_tile=tile, with_valid=with_valid,
                interpret=False),
            _spec(one_chip, (K, n, N)), _spec(one_chip, (K, n, n, N)),
            _spec(one_chip, (K, N)), _spec(one_chip, (T, m + with_valid, N)))


@pytest.mark.parametrize("kind", ["lkf", "imm"])
def test_front_end_lane_step_compiles(one_chip, kind, monkeypatch):
    """The stream front end's 4-lane fused step (tracker frame step
    vmapped over the lanes, spawn/prune in XLA) at C=256."""
    compiled = execmode.ExecMode("compiled", "tpu", jax.__version__)
    monkeypatch.setattr(execmode, "active_mode", lambda: compiled)
    assert execmode.resolve_interpret(None) is False
    model = make_cv_lkf() if kind == "lkf" else make_imm()
    cfg = TrackerConfig(capacity=256, max_meas=M)
    one, _, step = make_multi_sensor_step(model, cfg)
    lanes = 4
    banks = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        bank_lib.stack_sensor_banks(one, lanes))
    _assert_kernel_compiles(
        step, banks, _spec(one_chip, (lanes, M, model.m)),
        _spec(one_chip, (lanes, M), jnp.bool_))
    # the einsum reference path compiles for the chip too (no kernel)
    ref = dataclasses.replace(cfg, fused_frame=False)
    text = jax.jit(make_multi_sensor_step(model, ref)[2]).lower(
        banks, _spec(one_chip, (lanes, M, model.m)),
        _spec(one_chip, (lanes, M), jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" not in text
