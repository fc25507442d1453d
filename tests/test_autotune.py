"""Autotuned tile table: lookup rules, advisory-only fallback, and the
bench-regression gate that rides the same BENCH artifacts.

The table contract (src/repro/kernels/katana_bank/autotune.py): exact
``backend/mode`` key match, nearest-N in log-space, and NO semantics —
a missing/garbage table must leave every op on its static defaults.
The regression gate contract (benchmarks/check_regression.py): ratio
floors keyed mode+shape, red on injected slowdown and on silently
dropped rows, green within tolerance.
"""
import json

import pytest

from repro.execmode import ExecMode
from repro.kernels.katana_bank import autotune

CPU_INTERP = ExecMode("interpret", "cpu", "x")
TPU_COMPILED = ExecMode("compiled", "tpu", "x")


@pytest.fixture
def table(tmp_path):
    path = tmp_path / "tuned.json"
    autotune.write_table({
        "katana_bank_sequence": {
            "cpu/interpret": [
                dict(N=64, lane_tile=128, time_chunk=1024, us_per_frame=1.0),
                dict(N=1024, lane_tile=512, time_chunk=4096,
                     us_per_frame=2.0),
            ],
        },
    }, path)
    yield path
    autotune.clear_cache()


def test_nearest_n_in_log_space(table):
    # N=100 is nearer 64 than 1024 in log space
    cfg = autotune.best_config("katana_bank_sequence", 100, CPU_INTERP,
                               path=table)
    assert cfg["lane_tile"] == 128
    # N=500: log(500/64)=2.06 vs log(1024/500)=0.72 -> 1024 wins
    cfg = autotune.best_config("katana_bank_sequence", 500, CPU_INTERP,
                               path=table)
    assert cfg["lane_tile"] == 512


def test_mode_key_is_exact(table):
    """A CPU/interpret entry never drives a TPU/compiled run."""
    assert autotune.best_config("katana_bank_sequence", 64, TPU_COMPILED,
                                path=table) == {}


def test_unknown_kernel_and_missing_table(tmp_path, table):
    assert autotune.best_config("nope", 64, CPU_INTERP, path=table) == {}
    missing = tmp_path / "absent.json"
    assert autotune.best_config("katana_bank_sequence", 64, CPU_INTERP,
                                path=missing) == {}


def test_tuned_helpers_fall_back_to_default(tmp_path):
    autotune.clear_cache()
    missing = tmp_path / "absent.json"
    # helpers consult the module TUNED_PATH; drive best_config directly
    assert autotune.best_config("katana_bank", 64, CPU_INTERP,
                                path=missing) == {}
    # a zero/absent field in a hit falls back too
    path = tmp_path / "t.json"
    autotune.write_table({"katana_bank": {"cpu/interpret": [
        dict(N=64, lane_tile=0, us_per_frame=1.0)]}}, path)
    cfg = autotune.best_config("katana_bank", 64, CPU_INTERP, path=path)
    assert (int(cfg.get("lane_tile", 0)) or 256) == 256
    autotune.clear_cache()


def test_bad_format_table_is_ignored(tmp_path):
    autotune.clear_cache()
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(dict(format=999, entries={
        "katana_bank": {"cpu/interpret": [dict(N=1, lane_tile=8)]}})))
    assert autotune.best_config("katana_bank", 1, CPU_INTERP,
                                path=path) == {}
    path.write_text("{not json")
    autotune.clear_cache()
    assert autotune.best_config("katana_bank", 1, CPU_INTERP,
                                path=path) == {}
    autotune.clear_cache()


def test_checked_in_table_is_well_formed():
    """The committed tuned.json must parse under the current format and
    only contain known kernels with positive tile values."""
    doc = json.loads(autotune.TUNED_PATH.read_text())
    assert doc["format"] == autotune.TABLE_FORMAT
    for kernel, by_key in doc["entries"].items():
        assert kernel in autotune.STATIC_DEFAULTS, kernel
        for key, rows in by_key.items():
            backend, mode = key.split("/")
            assert mode in ("interpret", "compiled")
            for r in rows:
                assert r["N"] > 0
                assert r.get("lane_tile", 0) >= 0
                assert r.get("time_chunk", 1) > 0
                assert r["us_per_frame"] > 0


def test_ops_defaults_consult_table(tmp_path, monkeypatch):
    """lane_tile=0 at the ops layer resolves through the table: point
    TUNED_PATH at a table pinning a non-default tile and check the op
    still produces correct output (the tile is a layout knob, never a
    semantics knob)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.filters import get_filter
    from repro.kernels.katana_bank.ops import katana_bank

    path = tmp_path / "tuned.json"
    autotune.write_table({"katana_bank": {"cpu/interpret": [
        dict(N=8, lane_tile=64, us_per_frame=1.0)]}}, path)
    monkeypatch.setattr(autotune, "TUNED_PATH", path)
    autotune.clear_cache()
    try:
        model = get_filter("lkf")
        N = 8
        rng = np.random.default_rng(2)
        x = jnp.asarray(np.tile(model.x0, (N, 1)), jnp.float32)
        P = jnp.asarray(np.tile(model.P0, (N, 1, 1)), jnp.float32)
        z = jnp.asarray(rng.normal(size=(N, model.m)), jnp.float32)
        x_tuned, P_tuned = katana_bank(model, x, P, z, interpret=True)
        x_pinned, P_pinned = katana_bank(model, x, P, z, lane_tile=256,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(x_tuned),
                                   np.asarray(x_pinned),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(P_tuned),
                                   np.asarray(P_pinned),
                                   atol=1e-6, rtol=1e-6)
    finally:
        autotune.clear_cache()


# ---------------------------------------------------------------------------
# bench-regression gate
# ---------------------------------------------------------------------------

def _bench_fixture(root, speedup_scan=4.0, speedup_frame=1.5,
                   imm_ratio=2.0, drop_frame=False):
    meta = dict(mode="interpret", backend="cpu", jax="x")
    (root / "BENCH_scan.json").write_text(json.dumps(dict(
        bench="scan_fusion", meta=meta,
        rows=[dict(kind="lkf", N=8, speedup_fused_vs_loop=speedup_scan)])))
    (root / "BENCH_imm.json").write_text(json.dumps(dict(
        bench="imm", meta=meta, N=4,
        ratio_kernel_imm_vs_cv9=0.5,
        speedup_imm_scan_vs_per_frame=imm_ratio,
        ratio_imm_scan_vs_ref=0.6)))
    if not drop_frame:
        (root / "BENCH_frame.json").write_text(json.dumps(dict(
            bench="frame", meta=meta,
            rows=[dict(kind="lkf", C=16,
                       speedup_fused_vs_einsum=speedup_frame)],
            sharded=[dict(devices=8, S=8, skipped=True)])))


def test_gate_green_within_tolerance(tmp_path):
    from benchmarks.check_regression import check, collect

    _bench_fixture(tmp_path)
    baseline = collect(tmp_path)
    assert baseline  # the fixture produced pinnable ratios
    # 10% slower is inside the 25% band
    _bench_fixture(tmp_path, speedup_scan=3.6, speedup_frame=1.4)
    failures, _ = check(baseline, collect(tmp_path), tol=0.25)
    assert failures == []


def test_gate_red_on_injected_slowdown(tmp_path):
    """The acceptance demo: a de-fused scan (speedup collapses toward
    1x) must turn the gate red."""
    from benchmarks.check_regression import check, collect

    _bench_fixture(tmp_path, speedup_scan=4.0)
    baseline = collect(tmp_path)
    _bench_fixture(tmp_path, speedup_scan=1.1)  # injected slowdown
    failures, _ = check(baseline, collect(tmp_path), tol=0.25)
    assert any("REGRESSED" in f and "fused_vs_loop" in f for f in failures)


def test_gate_red_on_dropped_row(tmp_path):
    """A bench row that silently disappears must not pass."""
    from benchmarks.check_regression import check, collect

    _bench_fixture(tmp_path)
    baseline = collect(tmp_path)
    _bench_fixture(tmp_path, drop_frame=True)
    (tmp_path / "BENCH_frame.json").unlink()
    failures, _ = check(baseline, collect(tmp_path), tol=0.25)
    assert any("MISSING" in f and "fused_vs_einsum" in f for f in failures)


def test_gate_keys_are_mode_scoped(tmp_path):
    """An interpret-mode baseline never judges a compiled run: the key
    prefix separates them, so the compiled run shows up as MISSING (pin
    it separately), not as a bogus pass/fail against interpret floors."""
    from benchmarks.check_regression import check, collect

    _bench_fixture(tmp_path)
    baseline = collect(tmp_path)
    assert all(k.startswith("cpu/interpret/") for k in baseline)
    compiled_meta_doc = json.loads((tmp_path / "BENCH_scan.json").read_text())
    compiled_meta_doc["meta"]["mode"] = "compiled"
    (tmp_path / "BENCH_scan.json").write_text(json.dumps(compiled_meta_doc))
    failures, _ = check(baseline, collect(tmp_path), tol=0.25)
    assert any("MISSING" in f and "scan_fusion" in f for f in failures)


def test_committed_baseline_parses():
    from benchmarks.check_regression import BASELINE_PATH

    doc = json.loads(BASELINE_PATH.read_text())
    assert doc["ratios"], "committed baseline must pin at least one ratio"
    for key, val in doc["ratios"].items():
        backend, mode = key.split("/")[:2]
        assert mode in ("interpret", "compiled")
        assert val > 0
