"""chip_smoke.py refuses to report without a TPU: it exits non-zero and
prints no contract line when JAX finds only CPU devices."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=120, check=False)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "no TPU" in run.stderr
