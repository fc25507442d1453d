"""The streaming front end's lane select (serving/stream.py).

``_select_lanes`` merges a dispatch's result back into a shard's stacked
bank: lane i takes the new state where the mask is set and keeps its
exact pre-pump state where it is not. It runs as one compiled program
per bank structure, with the mask a traced argument:

  * the compiled merge equals a per-leaf NumPy ``where`` bit for bit,
    for LKF stacks (sensor axis 0 on every leaf) and IMM stacks
    (sensor axis 1 on the model-conditioned x and P);
  * changing lane patterns never retrace it (``select_traces``), and a
    second front end of the same structure reuses the program;
  * it stays a plain Python function that opens ``katana.select``, so
    callers that unwrap ``__wrapped__`` still reach the compiled path.
"""
import contextlib
import inspect

import numpy as np
import pytest

from repro.core import bank as bank_lib
from repro.core.filters import IMMModel, make_cv_lkf, make_imm
from repro.core.tracker import TrackerConfig
from repro.serving import stream
from repro.serving.stream import StreamConfig, StreamFrontEnd

MODELS = {"lkf-cv6": make_cv_lkf(), "imm": make_imm()}
MASKS = {
    "all-true": lambda L: np.ones(L, bool),
    "all-false": lambda L: np.zeros(L, bool),
    "mixed": lambda L: np.arange(L) % 2 == 1,
}


class FakeClock:
    def __call__(self) -> float:
        return 0.0


def random_stack(model, lanes: int, seed: int):
    """A stacked bank of ``lanes`` lanes with every leaf filled with
    seeded values of its own dtype, so no two stacks agree anywhere."""
    init = (bank_lib.init_imm_bank if isinstance(model, IMMModel)
            else bank_lib.init_bank)
    one = init(model, 8, np.dtype(np.float32))
    banks = bank_lib.stack_sensor_banks(one, lanes)
    rng = np.random.default_rng(seed)

    def fill(x):
        x = np.asarray(x)
        if x.dtype == bool:
            return rng.random(x.shape) < 0.5
        if np.issubdtype(x.dtype, np.integer):
            return rng.integers(0, 1000, x.shape).astype(x.dtype)
        return rng.standard_normal(x.shape).astype(x.dtype)

    axes = bank_lib.bank_sensor_axes(one)
    return type(banks)(*(fill(x) for x in banks)), axes


def reference_select(mask, new, old, axes):
    """The per-leaf ``where`` with the mask along each sensor axis."""
    def sel(n, o, a):
        shape = [1] * n.ndim
        shape[a] = len(mask)
        return np.where(mask.reshape(shape), n, o)

    return type(new)(*(sel(n, o, a) for n, o, a in zip(new, old, axes)))


@pytest.mark.parametrize("mask_kind", list(MASKS))
@pytest.mark.parametrize("lanes", [2, 4])
@pytest.mark.parametrize("model_name", list(MODELS))
def test_compiled_select_is_the_per_leaf_where(model_name, lanes, mask_kind):
    model = MODELS[model_name]
    new, axes = random_stack(model, lanes, seed=1)
    old, _ = random_stack(model, lanes, seed=2)
    if model_name == "imm":
        assert axes.x == axes.P == 1 and axes.mu == 0
    mask = MASKS[mask_kind](lanes)
    got = stream._select_lanes(mask, new, old, axes)
    want = reference_select(mask, new, old, axes)
    assert type(got) is type(want)
    for name, g, w in zip(want._fields, got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name


def pump_patterns(fe, patterns):
    rng = np.random.default_rng(0)
    for who in patterns:
        for name in who:
            fe.submit(name, rng.normal(size=(2, 3)).astype(np.float32))
        fe.pump()


@pytest.mark.parametrize("model_name", list(MODELS))
def test_select_traces_once_per_bank_structure(model_name, tmp_path):
    model = MODELS[model_name]
    # a shape no other test of this file selects over
    tracker = TrackerConfig(capacity=6, max_meas=4)
    scfg = StreamConfig(n_shards=1, lanes_per_shard=3, queue_depth=8)
    patterns = [("a", "b"), ("a",), ("b",), ("a", "b"), ("b",), ("a",)]
    fe = StreamFrontEnd(model, scfg, tracker, ckpt_dir=str(tmp_path / "1"),
                        clock=FakeClock())
    fe.attach("a")
    fe.attach("b")
    assert fe.stats.select_traces == 0
    pump_patterns(fe, patterns)
    assert fe.stats.dispatches == len(patterns)
    # four lane patterns, one program
    assert fe.stats.select_traces <= 1
    # a second front end of the same structure reuses it
    fe2 = StreamFrontEnd(model, scfg, tracker, ckpt_dir=str(tmp_path / "2"),
                         clock=FakeClock())
    fe2.attach("a")
    fe2.attach("b")
    pump_patterns(fe2, patterns)
    assert fe2.stats.dispatches == len(patterns)
    assert fe2.stats.select_traces == 0


def test_select_lanes_is_a_plain_function_in_its_span(monkeypatch):
    fn = stream._select_lanes
    # a jitted or functools.wraps'd select would carry __wrapped__, and
    # a caller that unwraps it would run the body eagerly
    assert not hasattr(fn, "__wrapped__")
    assert inspect.isfunction(fn)
    assert list(inspect.signature(fn).parameters) == \
        ["mask", "new", "old", "axes"]
    opened = []

    @contextlib.contextmanager
    def annotation(name):
        opened.append(name)
        yield

    monkeypatch.setattr(stream, "TraceAnnotation", annotation)
    new, axes = random_stack(MODELS["lkf-cv6"], 2, seed=3)
    old, _ = random_stack(MODELS["lkf-cv6"], 2, seed=4)
    fn(np.array([True, False]), new, old, axes)
    assert opened == [stream.SELECT_SPAN]
