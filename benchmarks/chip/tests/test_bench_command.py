"""The command refuses to run without a TPU, and outside a checkout."""
import os
import shutil
import subprocess
import sys

import bench_tiny

RUN = os.path.join(bench_tiny.BENCH, "run.py")
ARGS = ["--workload", "ukb23k_fused.p20480", "--seed", "3000000007", "--seconds", "1",
        "--trace", "0"]


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    p = _run(RUN, bench_tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(os.path.join(bench_tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path / "benchmarks" / "chip" / "run.py"), str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
