"""A run that finds no TPU, or finds nothing but the benchmark's own files,
exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import REPO

ARGS = ["--workload", "higgs-gbdt", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    return not any(ln.lstrip().startswith("{") for ln in proc.stdout.splitlines())


def test_no_tpu_exits_nonzero_with_no_result():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
