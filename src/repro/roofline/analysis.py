"""Three-term roofline: machine peaks + the compute/memory/collective
time terms.

Originally built for the dry-run artifacts (TPU v5e targets); now also
the model behind ``benchmarks/roofline.py``'s katana-kernel rows, which
compare ``cost_analysis()``-measured FLOPs/bytes of the compiled
programs against the analytic useful-work floor on a per-backend
``Machine``.

Methodology (DESIGN.md §4, calibrated on this container):
  * ``cost_analysis()`` is per-device, post-SPMD.
  * ``lax.scan`` bodies are costed ONCE -> full-depth compiles are used
    for memory/compile-proof only; FLOPs/bytes/collective-bytes come
    from unrolled depth-extrapolation probes:
        per_period = c(2p) - c(p);  total(L) = c(p) + per_period*(L-p)/p
  * Collective bytes use the wire (ring) estimate per device.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

# --- TPU v5e per-chip constants (Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI) ---
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s per link (~)
ICI_LINKS = 4                 # 2D torus: 4 links/chip; effective injection
ICI_BW = ICI_BW_PER_LINK * ICI_LINKS


@dataclass(frozen=True)
class Machine:
    """Roofline peaks of one device kind, with where they come from."""
    name: str
    peak_flops: float   # FLOP/s
    mem_bw: float       # B/s
    ici_bw: float       # B/s (collective injection; ~0 disables the term)
    source: str = ""


# Keyed by ``jax.Device.device_kind`` (TPU v5e reports "TPU v5 lite").
MACHINES = {
    "TPU v5 lite": Machine(
        "TPU v5 lite", PEAK_FLOPS_BF16, HBM_BW, ICI_BW,
        source='Google Cloud documentation, "TPU v5e" (bf16 peak)'),
    "cpu": Machine(
        "cpu", 1.0e11, 2.0e10, 1.0e9,
        source="order-of-magnitude reference for a few AVX2 cores "
               "(classifies compute- vs memory-bound; not a calibrated "
               "model of any host)"),
}


def machine_for(device_kind: str) -> Machine:
    """The roofline peaks of a ``device_kind``. A device that is not in
    the table is an error, never a default."""
    try:
        return MACHINES[device_kind]
    except KeyError:
        raise KeyError(f"no roofline peaks for device_kind "
                       f"{device_kind!r}: add it to MACHINES with its "
                       f"source") from None


@dataclass
class RooflineTerms:
    t_compute: float
    t_memory: float
    t_collective: float
    flops_dev: float
    bytes_dev: float
    coll_bytes_dev: float
    model_flops_dev: float = 0.0
    peak_flops: float = PEAK_FLOPS_BF16  # the machine the terms used

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops_dev / self.flops_dev if self.flops_dev else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU at the roofline bound: useful FLOPs / (bound x
        peak). =useful_fraction when compute-bound; lower when memory/
        collective-bound."""
        if self.bound <= 0:
            return 0.0
        return self.model_flops_dev / (self.bound * self.peak_flops)


def terms_from(flops_dev: float, bytes_dev: float, coll_wire_bytes_dev: float,
               model_flops_dev: float = 0.0,
               ici_bw: float = ICI_BW) -> RooflineTerms:
    return RooflineTerms(
        t_compute=flops_dev / PEAK_FLOPS_BF16,
        t_memory=bytes_dev / HBM_BW,
        t_collective=coll_wire_bytes_dev / ici_bw,
        flops_dev=flops_dev, bytes_dev=bytes_dev,
        coll_bytes_dev=coll_wire_bytes_dev,
        model_flops_dev=model_flops_dev,
    )


def terms_on(machine: Machine, flops_dev: float, bytes_dev: float,
             coll_wire_bytes_dev: float = 0.0,
             model_flops_dev: float = 0.0) -> RooflineTerms:
    """``terms_from`` against an explicit ``Machine`` (the katana-kernel
    roofline path; ``terms_from`` keeps the TPU-v5e dry-run contract)."""
    return RooflineTerms(
        t_compute=flops_dev / machine.peak_flops,
        t_memory=bytes_dev / machine.mem_bw,
        t_collective=(coll_wire_bytes_dev / machine.ici_bw
                      if machine.ici_bw else 0.0),
        flops_dev=flops_dev, bytes_dev=bytes_dev,
        coll_bytes_dev=coll_wire_bytes_dev,
        model_flops_dev=model_flops_dev,
        peak_flops=machine.peak_flops,
    )


def extrapolate(c_p: Dict[str, float], c_2p: Dict[str, float], p: int,
                L: int) -> Dict[str, float]:
    """Linear depth extrapolation of a cost dict (keys -> floats)."""
    out = {}
    for k in c_p:
        per_period = c_2p.get(k, 0.0) - c_p[k]
        out[k] = c_p[k] + per_period * (L - p) / p
    return out


def model_flops_total(n_params_active: float, tokens: float,
                      kind: str) -> float:
    """6·N·D for train, 2·N·D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * tokens
