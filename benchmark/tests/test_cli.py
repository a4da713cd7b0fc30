"""The command itself, rehearsed on the CPU: without a TPU it fails,
prints no result and measures nothing; in a directory that holds only
BENCHMARK.json and the files under `paths` it fails too."""

import os
import shutil
import subprocess
import sys

import harness


def _run(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_is_a_failed_run_with_no_result():
    for cell in harness.load_benchmark()["workloads"]:
        got = _run(harness.REPO_ROOT, cell["name"])
        assert got.returncode == 3
        assert got.stdout.strip() == ""
        assert "no TPU" in got.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(harness.REPO_ROOT, "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    got = _run(str(tmp_path), "prove-transfer10")
    assert got.returncode != 0
    assert got.stdout.strip() == ""
