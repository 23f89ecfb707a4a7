"""Rehearsal of each driver on the CPU at 4^3x8, Pallas in interpret
mode, through the functions ``run.py`` calls (the command itself refuses
the CPU), from a benchmark defined in a temporary directory: a new cell
is new files only."""
import subprocess
import sys

import pytest

from chipbench import run as bench_run
from chipbench.bench import ROOT


@pytest.mark.parametrize("cell", ["tinyprop.tiny", "tinysolve.tiny"])
def test_driver_rehearsal(tiny_bench, cell):
    out = bench_run.execute(tiny_bench, cell, seed=2**33 + 5, seconds=2.0)
    checks = out["checks"]
    assert out["correct"], checks
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "rhs_per_s"}
    assert out["metrics"]["rhs_per_s"]["value"] > 0
    assert checks["worst_true_residual"]["value"] <= 1e-5
    assert list(out)[-1] == "checks"


def test_command_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "prop12.wilson-64x16x16x8", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_command_needs_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    gives no result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "prop12.wilson-64x16x16x8", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
