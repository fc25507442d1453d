"""CPU tests of the chip benchmark's own code, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

The program's Pallas kernels run through the interpreter here, so every
size is cut to a few slots and tenants; nothing here times anything.
"""
import copy
import json
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (ROOT / "src", CHIP):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(scope="session")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny(bench, name):
    """A cell's files, cut to a size the interpreter runs in seconds."""
    import harness

    cell, cfg, traffic = harness.load_cell(bench, name)
    cfg, traffic = copy.deepcopy(cfg), dict(traffic)
    cfg["tracker"].update(capacity=32, max_meas=16)
    cfg["scene"]["targets"] = 8
    traffic.update(tenants=3, preroll_s=0.5)
    return cell, cfg, traffic


@pytest.fixture
def run_tiny(bench, monkeypatch):
    """Run a cut-down cell through the whole harness on the CPU."""
    import time

    import jax

    import harness
    import peaks

    # the CPU has no published peaks; the roofline readers need some
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])

    def run(name, seed=20250101, seconds=1.0, trace=False, **kw):
        cell, cfg, traffic = tiny(bench, name)
        return harness.run_loaded(bench, cell, cfg, traffic, seed, seconds,
                                  trace, time.monotonic(), jax.devices(),
                                  **kw)

    return run
