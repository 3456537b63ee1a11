"""The command refuses a host without the cell's TPUs, and a checkout that
holds only the benchmark, with a non-zero exit and no result."""
import os
import shutil
import subprocess
import sys

import bench_testlib


def _run(cwd, timeout=120):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo1b-chat",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_no_tpu_no_result():
    r = _run(bench_testlib.ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "{" not in r.stdout


def test_benchmark_alone_no_result(tmp_path):
    shutil.copytree(bench_testlib.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench_testlib.ROOT / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "{" not in r.stdout
