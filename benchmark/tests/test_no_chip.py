"""No accelerator: the command exits nonzero and prints no result line. And
the table of peaks refuses a device it does not know."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from peaks import peaks_for


def test_run_on_the_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
