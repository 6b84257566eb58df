"""Without a TPU the benchmark exits non-zero and prints no result line,
also from a directory that holds only BENCHMARK.json and bench/."""
import os
import shutil
import subprocess
import sys

from bench import common

ARGS = ["--workload", "unet32.serve.unique", "--seed", str(2 ** 31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_no_result():
    p = _run(common.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_only_the_benchmark_files_means_no_result(tmp_path):
    shutil.copy(common.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
