"""``correct`` at a tiny size on the CPU: the harness, with its look for
a chip skipped, passes a sound run and fails each fault planted under the
timed path; the control (the float32 reference computed with float8
operands in the program's place) fails the cell's limits."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from benchmarks.chip import cells, check, harness
import chipbench_faults
from chipbench_tiny import tiny_cell

HERE = Path(__file__).resolve().parent
SEED = 2**31 + 123
ONE_CHIP = ["qwen2-0.5b.s512.uniform.1chip"]


def _run(cell, fault, tmp_path):
    peak = next(iter(cells.peaks().values()))
    return harness.run_cell(cell, jax.devices()[:1], peak, SEED, 0.5, False,
                            time.perf_counter(), tmp_path, step_fault=fault,
                            log=lambda m: None)


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", [None, *chipbench_faults.ONE_CHIP])
def test_one_chip_run_is_judged(name, fault, tmp_path):
    res = _run(tiny_cell(name), chipbench_faults.ONE_CHIP.get(fault), tmp_path)
    assert set(res["checks"]) == set(check.NUMBERS)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is (fault is None), res["checks"]


@pytest.fixture(scope="module")
def dense_weights():
    """The tiny dense configuration's weights from SEED (every dense cell
    shares them)."""
    cell = tiny_cell(ONE_CHIP[0])
    prog = harness.Program.build(cell, jax.devices()[:1])
    return check.flat(prog.weights_fn(cell)(check.seed_key(SEED)))


@pytest.mark.parametrize("traffic", ["s512.b8.dp1.uniform", "s512.b32.dp4.uniform"])
def test_control_fails(traffic, dense_weights):
    cell = tiny_cell(config="qwen2-0.5b", traffic_name=traffic)
    limits = cells.load_cell(ONE_CHIP[0]).limits
    tr = cell.traffic
    feed = harness.ZipfFeed(cell.config["vocab_size"], tr["seq_len"],
                            tr["global_batch"], tr["zipf"], SEED)
    batches = [(b["tokens"], b["labels"]) for b in map(feed.host_batch, range(3))]

    def ref(**kw):
        return check.reference_readings(cell.kind, cell.config, dense_weights,
                                        batches, tr["optimizer"], **kw)

    ok, checks = check.judge(check.compare(ref(precision="fp8"), ref()), limits)
    assert not ok, checks


def test_dp4_run_is_judged(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(HERE / "chipbench_dp4_worker.py"),
                        str(tmp_path)], env=env, capture_output=True, text=True,
                       timeout=900, check=False)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True, out["sound"]
    for case in chipbench_faults.MULTI_CHIP:
        assert out[case]["correct"] is False, (case, out[case])
