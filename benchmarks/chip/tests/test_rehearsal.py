"""The harness runs end to end on the CPU (kernels interpreted) at tiny
sizes; the command itself refuses without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from conftest import CHIP, ROOT


@pytest.mark.parametrize("name", ["mot17-lkf.cams30", "mot20-imm.cams25"])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(run_tiny, bench, name, trace):
    out = run_tiny(name, trace=trace)
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[section]
            if name in m.get("workloads", [name])}
    if not trace:
        assert set(out["metrics"]) == want
        assert out["metrics"]["setup_s"]["value"] > 0
    else:
        # the CPU trace has no TPU plane: device readers stay silent
        assert set(out["metrics"]) <= want
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
    assert out["attempted"] > 0


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "bench.py"), "--workload",
         "mot17-lkf.cams30", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
