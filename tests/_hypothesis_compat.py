"""Optional-hypothesis shim for the test suite.

When ``hypothesis`` is installed the real ``given``/``settings``/``st``/
``example`` are re-exported unchanged. When it is absent (the minimal
container image), the property tests degrade to fixed-seed parametrized cases:
``given`` samples ``max_examples`` tuples from the strategies with a
deterministic per-test rng and applies ``pytest.mark.parametrize``,
after the cases recorded with ``example``.
Coverage shrinks (no shrinking, no adaptive search) but every property
still runs — the suite never fails to *collect*.
"""
from __future__ import annotations

import inspect
import zlib

import numpy as np
import pytest

try:
    from hypothesis import (example, given, settings,  # noqa: F401
                            strategies as st)

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

    class _Integers:
        def __init__(self, min_value, max_value):
            self.lo = int(min_value)
            self.hi = int(max_value)

        def sample(self, rng: np.random.Generator) -> int:
            return int(rng.integers(self.lo, self.hi + 1))

    class st:  # noqa: N801 — mirrors `hypothesis.strategies as st`
        @staticmethod
        def integers(min_value, max_value):
            return _Integers(min_value, max_value)

    def settings(max_examples: int = 10, **_ignored):
        def deco(fn):
            fn._compat_max_examples = max_examples
            return fn

        return deco

    def example(**kwargs):
        def deco(fn):
            fn._compat_examples = [kwargs] + getattr(fn, "_compat_examples",
                                                     [])
            return fn

        return deco

    def given(*strategies):
        def deco(fn):
            n_ex = getattr(fn, "_compat_max_examples", 10)
            # deterministic per-test seed so failures reproduce
            rng = np.random.default_rng(zlib.crc32(fn.__name__.encode()))
            names = list(inspect.signature(fn).parameters)[: len(strategies)]
            cases = [tuple(ex[k] for k in names)
                     for ex in getattr(fn, "_compat_examples", [])]
            cases += [
                tuple(s.sample(rng) for s in strategies) for _ in range(n_ex)
            ]
            return pytest.mark.parametrize(",".join(names), cases)(fn)

        return deco
