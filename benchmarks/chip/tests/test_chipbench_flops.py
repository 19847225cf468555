"""Model FLOPs (flops/<kind>.py) never exceed what the program executes,
as ``launch/hlo_cost.py`` counts it from the compiled step, and match the
hand count at the published sizes."""
import importlib
import json
from pathlib import Path

import jax
import pytest

from benchmarks.chip import harness
from benchmarks.chip.feed import ZipfFeed
from chipbench_tiny import tiny_cell

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SEQ, ROWS = 128, 4


@pytest.mark.parametrize("config,traffic", [("qwen2-0.5b", "s512.b8.dp1.zen"),
                                            ("mamba2-370m", "s4096.b2.dp1.zen")])
def test_model_flops_at_most_executed(config, traffic):
    from repro.launch import hlo_cost

    cell = tiny_cell(config=config, traffic_name=traffic, seq_len=SEQ,
                     rows_per_chip=ROWS)
    prog = harness.Program.build(cell, jax.devices()[:1])
    params = prog.weights_fn(cell)(jax.random.PRNGKey(0))
    opt = prog.prog.init_opt(params)
    batch = prog.put(ZipfFeed(512, SEQ, ROWS, 1.2, 0).host_batch(0))
    flops = importlib.import_module(f"benchmarks.chip.flops.{cell.kind}")
    tokens = SEQ * ROWS

    fwd = jax.jit(lambda p, b: prog.prog.model.train_loss(p, b)[0])
    executed_fwd = hlo_cost.analyze(fwd.lower(params, batch).compile().as_text())["flops"]
    assert flops.forward_flops(cell.config, SEQ) * tokens <= executed_fwd

    step = prog.prog.train_step.lower(params, opt, batch).compile().as_text()
    executed = hlo_cost.analyze(step)["flops"]
    assert flops.flops_per_token(cell.config, SEQ) * tokens <= executed


def test_published_sizes_hand_count():
    from benchmarks.chip.flops import dense, ssm

    q = json.loads((CONFIGS / "qwen2-0.5b.json").read_text())
    # 24 x (896*896*2 + 2*896*128 + 3*896*4864) + 896*151936 matmul
    # parameters, 4*512*896 per layer of attention
    matmul = 24 * (2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864) + 896 * 151936
    assert dense.forward_flops(q, 512) == 2 * matmul + 24 * 4 * 512 * 896
    assert dense.flops_per_token(q, 512) == pytest.approx(3.0959e9, rel=1e-4)

    m = json.loads((CONFIGS / "mamba2-370m.json").read_text())
    per_layer = 2 * 1024 * 2048 + 1024 * 32 + 1024 * 256 + 2048 * 1024
    ssd = 2 * 256 * 128 + 2 * 256 * 64 * 32 + 4 * 128 * 64 * 32
    assert ssm.forward_flops(m, 4096) == 2 * (48 * per_layer + 1024 * 50280) + 48 * ssd
