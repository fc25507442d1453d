"""Execution-mode resolution for the katana Pallas kernels.

The backend decides how a kernel runs, with no probe and no fallback:

  * on a TPU backend every kernel runs compiled (Mosaic). A kernel the
    TPU compiler refuses raises; nothing turns it into the interpreter,
    and asking for the interpreter there is an error;
  * on any other backend (the CPU the test suite runs on) Pallas TPU
    kernels cannot compile, so they run through the Pallas interpreter
    — slow, but the same op stream. ``interpret=False`` there lowers a
    kernel for a described TPU (the compile rehearsals); run on the
    CPU, it raises.

Nothing else chooses the mode: no env var, no config field. The
resolved ``ExecMode`` names the backend and jax version so every
BENCH_*.json row records how its code actually executed:
``lowering="pallas"`` (natively compiled kernel),
``"pallas-interpret"`` (kernel through the interpreter), or ``"xla"``
(the XLA-native einsum/lanes formulation — compiled code on every
backend, including CPU).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class ExecMode:
    mode: str             # what runs: "interpret" | "compiled"
    backend: str          # jax.default_backend()
    jax_version: str

    @property
    def interpret(self) -> bool:
        """What the kernel ops pass to ``pallas_call``."""
        return self.mode == "interpret"

    def lowering(self, pallas: bool = True) -> str:
        """How a code path executes under this mode: ``"xla"`` for the
        einsum/lanes formulations (native compiled code everywhere),
        ``"pallas"`` / ``"pallas-interpret"`` for kernel dispatches."""
        if not pallas:
            return "xla"
        return "pallas" if self.mode == "compiled" else "pallas-interpret"

    def row_mode(self, pallas: bool = True) -> str:
        """The honest per-BENCH-row mode label: XLA-native paths are
        compiled code on every backend; Pallas paths are compiled only
        on a TPU backend."""
        return "interpret" if self.lowering(pallas) == "pallas-interpret" \
            else "compiled"

    def as_meta(self) -> dict:
        """Top-of-file metadata for BENCH_*.json."""
        return dict(mode=self.mode, backend=self.backend,
                    jax=self.jax_version)


def backend_mode(backend: str) -> str:
    """The one mode a backend runs Pallas kernels in."""
    return "compiled" if backend == "tpu" else "interpret"


def active_mode() -> ExecMode:
    """The mode of the default backend (what ops use when no explicit
    ``interpret=`` is passed)."""
    import jax

    backend = jax.default_backend()
    return ExecMode(mode=backend_mode(backend), backend=backend,
                    jax_version=jax.__version__)


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The ops-level shim: an explicit ``interpret=`` wins (tests pin
    the interpreter on CPU; compile rehearsals pin ``False`` to lower
    for a described TPU), otherwise the backend decides. The
    interpreter never runs on a TPU backend."""
    mode = active_mode()
    if interpret is None:
        return mode.interpret
    if interpret and mode.mode == "compiled":
        raise ValueError("the Pallas interpreter is for CPU only: on "
                         "a TPU backend the kernels run compiled")
    return bool(interpret)
