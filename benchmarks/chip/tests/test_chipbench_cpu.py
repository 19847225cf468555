"""run.py and readings.py refuse to report without a TPU, and run.py
without the program beside it."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
ARGS = {"run.py": ["--workload", "qwen2-0.5b.s512.uniform.1chip", "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"],
        "readings.py": ["--workload", "qwen2-0.5b.s512.uniform.1chip", "--seeds", "1",
                        "--first-seed", "2147483659"]}


def _run(cwd: Path, script: str = "run.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, f"benchmarks/chip/{script}", *ARGS[script]],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300, check=False)


@pytest.mark.parametrize("script", sorted(ARGS))
def test_fails_without_tpu(script):
    r = _run(ROOT, script)
    assert r.returncode == 2
    assert '"metrics"' not in r.stdout and '"correct"' not in r.stdout
    assert '"program"' not in r.stdout
    assert "no TPU" in r.stderr


def test_run_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout
