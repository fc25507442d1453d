"""The bytes-from-shapes functions equal the nbytes of what each kernel
is handed and returns."""
import jax
import jax.numpy as jnp
import numpy as np

import kernel_bytes


def _nbytes(tree):
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _capture(monkeypatch, name):
    from repro.kernels.katana_bank import ops

    seen = []
    orig = getattr(ops, name)

    def spy(*args, **kw):
        out = orig(*args, **kw)
        arrays = [a for a in args if hasattr(a, "shape")]
        seen.append(_nbytes(arrays) + _nbytes(out))
        return out

    monkeypatch.setattr(ops, name, spy)
    return seen


def test_frame_kernel_bytes(monkeypatch):
    from repro.core.filters import make_cv_lkf
    from repro.kernels.katana_bank.ops import katana_frame

    seen = _capture(monkeypatch, "katana_frame_step")
    m = make_cv_lkf()
    C, M = 200, 24
    katana_frame(m, jnp.zeros((C, 6)), jnp.tile(jnp.eye(6), (C, 1, 1)),
                 jnp.zeros((M, 3)), jnp.zeros(M, bool), jnp.zeros(C, bool),
                 gate=11.34, rounds=M)
    assert seen == [kernel_bytes.frame_kernel_bytes(1, C, M, 6, 3)]


def test_imm_frame_kernel_bytes(monkeypatch):
    from repro.core.filters import make_imm
    from repro.kernels.katana_bank.ops import katana_imm_frame

    seen = _capture(monkeypatch, "katana_imm_frame_step")
    imm = make_imm()
    C, M = 130, 12
    katana_imm_frame(imm, jnp.zeros((4, C, 9)),
                     jnp.tile(jnp.eye(9), (4, C, 1, 1)),
                     jnp.full((C, 4), 0.25), jnp.zeros((M, 3)),
                     jnp.zeros(M, bool), jnp.zeros(C, bool), gate=11.34,
                     rounds=M)
    assert seen == [kernel_bytes.frame_kernel_bytes(1, C, M, 9, 3, K=4)]
