"""The harness fails, and does not fall back, where JAX finds no TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from bench_tiny import BENCH, ROOT
from harness import device

ARGS = ["--workload", "fig1_cnn.grid", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(device.NoChip, match="no TPU"):
        device.require_tpu(1)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(device.NoChip, match="no peaks"):
        device.peaks("cpu")
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_on_the_cpu_exits_nonzero_with_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_without_the_program_exits_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())["paths"] \
        == ["bench"]
