"""Without a TPU the benchmark exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT


def test_cpu_backend_exits_without_metrics():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_only_benchmark_files_exit_nonzero(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    files (no program) fails without a result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         cell["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
