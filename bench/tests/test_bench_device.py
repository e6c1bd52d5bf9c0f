"""The benchmark runs on a TPU or not at all."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "toolbench-2413-static.steady", "--seed", "4294967311", "--seconds", "1",
        "--trace", "0"]


def run_bench(cwd: Path, *extra: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS, *extra], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def printed_a_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_cpu_exits_non_zero_before_any_phase():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert not printed_a_result(p.stdout) and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0 and not printed_a_result(p.stdout)


def test_unknown_workload_exits_non_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope", "--seed", "1",
                        "--seconds", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and not printed_a_result(p.stdout)
