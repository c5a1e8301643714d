"""Without a TPU, or without the program beside it, a run exits non-zero
and prints no result."""
import os
import shutil
import subprocess
import sys

from bench import run as bench_run

ARGS = ["--workload", "smollm-135m.train-2k", "--seed", "5000000000",
        "--seconds", "1", "--trace", "0"]


def run_in(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py")] + ARGS,
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_without_a_result():
    p = run_in(bench_run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_exit_without_a_result(tmp_path):
    shutil.copy(os.path.join(bench_run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(bench_run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = run_in(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
