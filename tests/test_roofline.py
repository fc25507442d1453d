"""The dormant roofline package gets a test floor, plus the katana
wiring that now consumes it.

hlo.py's census parsers were written against dry-run artifacts this
repo never ships, so until now nothing executed them: every regex is
exercised here on hand-built HLO lines (explicit and iota
replica_groups, tuple results, dtype byte widths) AND on a real
compiled katana_bank program. analysis.py's three-term model is pinned
on dominance arithmetic and the per-backend Machine selection that
benchmarks/roofline.py uses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (HBM_BW, ICI_BW, MACHINES,
                                     PEAK_FLOPS_BF16, Machine,
                                     machine_for, terms_from,
                                     terms_on)
from repro.roofline.hlo import (collective_census, cpu_upcast_bytes,
                                op_census, totals)

# ---------------------------------------------------------------------------
# hlo.py census on synthetic HLO text
# ---------------------------------------------------------------------------

HLO = """\
HloModule m
  %x = f32[8,128]{1,0} parameter(0)
  %ar = f32[8,128]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ag = bf16[16,128]{1,0} all-gather(%x), replica_groups=[2,4]<=[8], dimensions={0}
  %tup = (f32[4,4]{1,0}, s32[4]{0}) all-to-all(%a, %b), replica_groups={{0,1}}
  %cp = f32[2,2]{1,0} collective-permute(%x), source_target_pairs={{0,1}}
  %d = f32[8,8]{1,0} dot(%x, %y), lhs_contracting_dims={1}
  %t = f32[128,8]{0,1} transpose(%x), dimensions={1,0}
  %add.1 = f32[8,128]{1,0} add(%x, %x)
"""


def test_collective_census_explicit_groups_all_reduce():
    c = collective_census(HLO)
    ar = c["all-reduce"]
    rb = 8 * 128 * 4
    assert ar["count"] == 1
    assert ar["result_bytes"] == rb
    assert ar["operand_bytes"] == rb
    # ring all-reduce: 2·B·(g-1)/g with g=4 from the explicit groups
    assert ar["wire_bytes"] == pytest.approx(2.0 * rb * 3 / 4)
    # f32 payload counts at half weight in the bf16-equivalent column
    assert ar["wire_bytes_bf16eq"] == pytest.approx(ar["wire_bytes"] * 0.5)


def test_collective_census_iota_groups_and_dtype_bytes():
    c = collective_census(HLO)
    ag = c["all-gather"]
    rb = 16 * 128 * 2  # bf16 = 2 bytes
    assert ag["result_bytes"] == rb
    # iota [2,4]<=[8]: group size 4
    assert ag["operand_bytes"] == pytest.approx(rb / 4)
    assert ag["wire_bytes"] == pytest.approx(rb * 3 / 4)
    # bf16 stays at full weight in the bf16-equivalent column
    assert ag["wire_bytes_bf16eq"] == pytest.approx(ag["wire_bytes"])


def test_collective_census_tuple_result():
    c = collective_census(HLO)
    a2a = c["all-to-all"]
    rb = 4 * 4 * 4 + 4 * 4  # f32[4,4] + s32[4]
    assert a2a["result_bytes"] == rb
    assert a2a["wire_bytes"] == pytest.approx(rb * 1 / 2)  # g=2


def test_collective_census_permute_and_totals():
    c = collective_census(HLO)
    cp = c["collective-permute"]
    assert cp["wire_bytes"] == cp["result_bytes"] == 2 * 2 * 4
    t = totals(c)
    assert t["count"] == 4
    assert t["wire_bytes"] == pytest.approx(
        sum(d["wire_bytes"] for d in c.values()))


def test_collective_census_start_done_counted_once():
    text = """\
  %s = f32[8]{0} all-reduce-start(%x), replica_groups={{0,1}}
  %d = f32[8]{0} all-reduce-done(%s)
"""
    c = collective_census(text)
    assert c["all-reduce"]["count"] == 1


def test_op_census_counts_kinds():
    c = op_census(HLO)
    assert c["dot"] == 1
    assert c["transpose"] == 1
    assert c["add"] == 1
    assert c["scatter"] == 0
    # collectives are not in the default op list
    assert "all-reduce" not in c


def test_cpu_upcast_bytes_thresholds():
    text = "  %c = f32[4096,4096]{1,0} convert(%w)\n" \
           "  %small = f32[4]{0} convert(%v)\n"
    big = 4096 * 4096 * 4
    assert cpu_upcast_bytes(text, min_bytes=1e6) == big
    assert cpu_upcast_bytes(text, min_bytes=big + 1) == 0.0


# ---------------------------------------------------------------------------
# analysis.py three-term model + Machine selection
# ---------------------------------------------------------------------------

def test_terms_from_dominance_and_bound():
    # memory-dominated: tiny flops, huge bytes
    t = terms_from(flops_dev=1e9, bytes_dev=1e12, coll_wire_bytes_dev=0.0)
    assert t.dominant == "memory"
    assert t.bound == pytest.approx(1e12 / HBM_BW)
    # compute-dominated
    t = terms_from(flops_dev=1e15, bytes_dev=1.0, coll_wire_bytes_dev=0.0)
    assert t.dominant == "compute"
    assert t.bound == pytest.approx(1e15 / PEAK_FLOPS_BF16)
    # collective-dominated
    t = terms_from(flops_dev=1.0, bytes_dev=1.0, coll_wire_bytes_dev=1e12)
    assert t.dominant == "collective"
    assert t.bound == pytest.approx(1e12 / ICI_BW)


def test_useful_and_roofline_fractions():
    t = terms_from(flops_dev=2e12, bytes_dev=1.0, coll_wire_bytes_dev=0.0,
                   model_flops_dev=1e12)
    assert t.useful_fraction == pytest.approx(0.5)
    # compute-bound: roofline fraction equals useful fraction
    assert t.roofline_fraction == pytest.approx(0.5)


def test_terms_on_uses_machine_peaks():
    m = Machine("toy", peak_flops=1e9, mem_bw=1e6, ici_bw=0.0)
    t = terms_on(m, flops_dev=1e9, bytes_dev=2e6, model_flops_dev=5e8)
    assert t.t_compute == pytest.approx(1.0)
    assert t.t_memory == pytest.approx(2.0)
    assert t.t_collective == 0.0  # ici_bw 0 disables the term
    assert t.dominant == "memory"
    # roofline_fraction must use the MACHINE's peak, not the TPU const
    assert t.roofline_fraction == pytest.approx(5e8 / (2.0 * 1e9))


def test_machine_for_backend_mapping():
    """Peaks are keyed by ``device_kind`` with a named source; a device
    the table does not know raises instead of borrowing a preset."""
    v5e = machine_for("TPU v5 lite")
    assert v5e is MACHINES["TPU v5 lite"]
    assert (v5e.peak_flops, v5e.mem_bw) == (PEAK_FLOPS_BF16, HBM_BW)
    assert machine_for("cpu") is MACHINES["cpu"]
    kind = jax.devices()[0].device_kind
    assert machine_for(kind).name == kind
    assert all(m.source for m in MACHINES.values())
    for unknown in ("tpu", "TPU v4", "unknown-thing"):
        with pytest.raises(KeyError, match="device_kind"):
            machine_for(unknown)


# ---------------------------------------------------------------------------
# census smoke on a REAL compiled katana program
# ---------------------------------------------------------------------------

def test_census_on_compiled_katana_bank():
    """The parsers must hold up against real optimized HLO, not just
    the synthetic lines above: compile the katana_bank op (interpret
    route — its jaxpr still lowers to a full XLA program) and check
    the census + cost_analysis wiring benchmarks/roofline.py relies
    on."""
    from benchmarks.common import compiled_of, hlo_cost
    from repro.core.filters import get_filter
    from repro.kernels.katana_bank.ops import katana_bank

    model = get_filter("lkf")
    N = 8
    rng = np.random.default_rng(1)
    x = jnp.asarray(np.tile(model.x0, (N, 1)), jnp.float32)
    P = jnp.asarray(np.tile(model.P0, (N, 1, 1)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(N, model.m)), jnp.float32)
    fn = lambda x, P, z: katana_bank(model, x, P, z, interpret=True)

    compiled = compiled_of(fn, x, P, z)
    census = op_census(compiled.as_text())
    assert all(isinstance(v, int) and v >= 0 for v in census.values())
    assert sum(census.values()) > 0  # a KF step is not op-free

    cost = hlo_cost(fn, x, P, z)
    assert cost["flops"] > 0
    assert cost["bytes"] > 0
    # a single-device program has no collectives
    assert totals(collective_census(compiled.as_text()))["count"] == 0
