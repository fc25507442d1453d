"""The lower-precision control, the program's own bfloat16 path, comes
out incorrect. (On the chip at each cell's own size the same readings
set the limits; see PERF.md.)"""
import pytest


@pytest.mark.parametrize("name", ["mot17-lkf.cams30", "mot20-imm.cams25"])
def test_control_fails(run_tiny, name):
    out = run_tiny(name, control=True)
    assert not out["correct"], out["compared"]
