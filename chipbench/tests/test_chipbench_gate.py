"""The harness refuses to run without a TPU, and refuses a device kind it
has no peaks for: it exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "gpt2-medium.layup-r2d1.m1", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_gate_refuses_in_process():
    from chipbench import run as R
    with pytest.raises(SystemExit) as e:
        R.device_gate(4)
    assert e.value.code == 2


def test_unknown_device_kind_stops_the_run():
    from chipbench import run as R
    with pytest.raises(SystemExit) as e:
        R.peak_for("TPU v99")
    assert e.value.code == 2
    assert R.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_unknown_workload_stops_the_run():
    from chipbench import cells
    with pytest.raises(SystemExit):
        cells.find_cell(cells.load_benchmark(), "no-such-cell")
    json.dumps(cells.load_benchmark())
