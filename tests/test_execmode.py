"""Execution-mode resolution: the backend decides, no fallback, row labels.

The contract under test (src/repro/execmode.py): the backend alone
decides interpret-vs-compiled for every kernel op — compiled on TPU,
the Pallas interpreter elsewhere — with no capability probe and no
fallback: a kernel that cannot lower raises, and the interpreter never
runs on a TPU backend. Per-BENCH-row labels call XLA-native paths
compiled everywhere but Pallas paths compiled only on TPU.
"""
import jax
import numpy as np
import pytest

from repro.execmode import (ExecMode, active_mode, backend_mode,
                            resolve_interpret)


def _lkf_bank(N=4):
    import jax.numpy as jnp

    from repro.core.filters import get_filter

    model = get_filter("lkf")
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.tile(model.x0, (N, 1)), jnp.float32)
    P = jnp.asarray(np.tile(model.P0, (N, 1, 1)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(N, model.m)), jnp.float32)
    return model, x, P, z


def test_auto_resolves_to_backend_capability():
    m = active_mode()
    assert m.backend == jax.default_backend()
    assert m.jax_version == jax.__version__
    assert m.mode == backend_mode(m.backend)
    assert m.mode == ("compiled" if m.backend == "tpu" else "interpret")


@pytest.mark.parametrize("backend,mode", [("tpu", "compiled"),
                                          ("cpu", "interpret"),
                                          ("gpu", "interpret")])
def test_active_mode_follows_backend(monkeypatch, backend, mode):
    """The backend is the only input: a TPU resolves to compiled with
    no probe at all, every other backend to the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    m = active_mode()
    assert (m.backend, m.mode) == (backend, mode)
    assert resolve_interpret(None) is (mode == "interpret")


def test_interpret_request_is_always_honored():
    """Where the interpreter exists (every non-TPU backend) an explicit
    ``interpret=True`` is honoured."""
    if jax.default_backend() == "tpu":
        with pytest.raises(ValueError, match="CPU only"):
            resolve_interpret(True)
        return
    assert resolve_interpret(True) is True
    assert active_mode().interpret is True


def test_compiled_request_is_never_silent():
    """``interpret=False`` is always honoured as asked (compile
    rehearsals on CPU need it); it is never turned into the
    interpreter."""
    assert resolve_interpret(False) is False


def test_interpreter_refused_on_tpu_backend(monkeypatch):
    """An explicit ``interpret=True`` can never reach a kernel on a TPU
    backend; ``interpret=False`` (compile for the chip) is always
    allowed, as compile rehearsals on CPU need it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="CPU only"):
        resolve_interpret(True)
    assert resolve_interpret(False) is False


def test_lowering_failure_raises_instead_of_interpreting(monkeypatch):
    """A backend that resolves to compiled but cannot lower the kernel
    raises; nothing falls back to the interpreter."""
    from repro.kernels.katana_bank.ops import katana_bank

    if jax.default_backend() == "tpu":
        pytest.skip("the TPU lowers the kernel")
    model, x, P, z = _lkf_bank()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret"):
        katana_bank(model, x, P, z)


def test_explicit_interpret_beats_mode():
    """Tests pin the interpreter with interpret=True on CPU, and compile
    rehearsals pin interpret=False — the ops-level shim honours both."""
    if jax.default_backend() != "tpu":
        assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) is active_mode().interpret


def test_row_labels_are_honest():
    """XLA rows are compiled everywhere; Pallas rows are compiled only
    on a TPU backend."""
    native = ExecMode("compiled", "tpu", "x")
    interp = ExecMode("interpret", "cpu", "x")
    assert native.lowering(pallas=True) == "pallas"
    assert native.row_mode(pallas=True) == "compiled"
    assert interp.lowering(pallas=True) == "pallas-interpret"
    assert interp.row_mode(pallas=True) == "interpret"
    for m in (native, interp):
        assert m.lowering(pallas=False) == "xla"
        assert m.row_mode(pallas=False) == "compiled"


def test_as_meta_round_trips_the_facts():
    m = active_mode()
    meta = m.as_meta()
    assert meta == dict(mode=m.mode, backend=m.backend, jax=jax.__version__)


def test_ops_honor_resolved_mode():
    """End-to-end: backend -> resolver -> ops wrapper -> pallas_call.
    An op left to the resolver gives exactly what the op pinned to the
    backend's own mode gives."""
    from repro.kernels.katana_bank.ops import katana_bank

    model, x, P, z = _lkf_bank()
    own = backend_mode(jax.default_backend())
    x_pinned, P_pinned = katana_bank(model, x, P, z,
                                     interpret=own == "interpret")
    x_auto, P_auto = katana_bank(model, x, P, z)
    np.testing.assert_array_equal(np.asarray(x_auto), np.asarray(x_pinned))
    np.testing.assert_array_equal(np.asarray(P_auto), np.asarray(P_pinned))


def test_engine_records_the_backend_mode():
    from repro.core.filters import get_filter
    from repro.serving.engine import TrackingEngine

    eng = TrackingEngine(get_filter("lkf"))
    assert eng.exec_mode == active_mode()
