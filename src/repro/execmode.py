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
resolved ``ExecMode`` names the mode, the backend and the jax version.
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
