"""bench/run.py refuses to measure without a TPU, and without the
simulator beside it: a non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _run(cwd: Path, env: dict):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lotka77.kernel",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    _no_result(proc)
    assert "not tpu" in proc.stderr


def test_refuses_a_checkout_without_the_simulator(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run(tmp_path, env)
    _no_result(proc)
    assert "simulator" in proc.stderr
