"""The command refuses to run where it cannot measure, and prints no
result."""
import os
import shutil
import subprocess
import sys

import bench_tiny


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_on_the_cpu():
    r = _run(bench_tiny.ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
