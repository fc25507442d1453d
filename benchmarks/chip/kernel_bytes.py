"""Bytes each katana kernel must move per dispatch, from shapes alone.

A kernel reads each input array once and writes each output once: that
is the least HBM traffic its algorithm allows, and the numerator of its
share of the HBM roofline. The shapes are the ones the ops layer hands
the ``pallas_call`` (lanes-minor, the track axis padded to a multiple
of 128), counted in float32 / int32 words.
"""
from __future__ import annotations

WORD = 4      # float32 and int32
LANE_PAD = 128


def _pad(n: int, to: int = LANE_PAD) -> int:
    return -(-n // to) * to


def frame_kernel_bytes(lanes: int, C: int, M: int, n: int, m: int,
                       K: int = 1) -> int:
    """One vmapped frame dispatch over ``lanes`` lanes of C slots and M
    measurement slots. In: x (K, n, C), P (K, n, n, C), [mu (K, C)],
    z (m, M), z_valid (1, M), active (1, C). Out: x, P, [mu,
    combined x (n, C)], assoc (1, C). K = 1 is the single-model kernel
    (no mu, no combined estimate)."""
    C = _pad(C)
    state = K * n * C + K * n * n * C
    inp = state + m * M + M + C + (K * C if K > 1 else 0)
    out = state + C + (K * C + n * C if K > 1 else 0)
    return WORD * lanes * (inp + out)
