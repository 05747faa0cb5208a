"""run.py refuses to time anything without a chip, and the cells run end to
end at a size a test can hold."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_testlib import BENCH, ROOT, clean_env, drive


def _run_py(tmp_path, cwd, workload="sort.uniform.1chip"):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", "2147483649",
         "--seconds", "1", "--trace", "0"],
        env=clean_env(tmp_path), capture_output=True, text=True, timeout=300, cwd=cwd,
    )


@pytest.mark.parametrize("workload", ["sort.uniform.1chip", "sort.zipf.4chip"])
def test_exits_nonzero_on_cpu_without_timing(tmp_path, workload):
    out = _run_py(tmp_path, ROOT, workload)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_exits_nonzero_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path, tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


@pytest.mark.parametrize("workload", ["sort.uniform.1chip", "topk.decode.steps"])
def test_cell_runs_correct_at_a_small_size(tmp_path, workload):
    r = drive(tmp_path, workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) >= {"setup_s"}
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    json.dumps(r)


def test_four_chip_cell_runs_correct_at_a_small_size(tmp_path):
    r = drive(tmp_path, "sort.zipf.4chip", devices=4)
    assert r["correct"] and r["device"]["count"] == 4
    assert r["counters"]["peak_mean_ratio"] >= 1.0
