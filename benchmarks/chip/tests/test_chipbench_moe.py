"""The DeepSeek-V2-Lite cell: its files and metrics found by name, its
model FLOPs against what the compiled step executes, ``correct`` at a tiny
size on the CPU (sound, planted faults, the float8 control), and the
expert layer's readers on a hand-made trace."""
import dataclasses
import time

import jax
import pytest

from benchmarks.chip import blocks, cells, check, harness, scopes, tracing
from benchmarks.chip.feed import ZipfFeed
from benchmarks.chip.flops import moe as moe_flops
import chipbench_faults

CELL = "deepseek-v2-lite.s4096.uniform.1chip"
METRICS = ("moe.route_ms", "moe.experts_ms", "moe.experts_roofline")
# every width cut; 8 routed experts, 2 of them held
TINY = dict(num_hidden_layers=3, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=16, n_routed_experts=8,
            num_experts_per_tok=3, experts_held=2, vocab_size=512)
SEED = 2**31 + 4321
# The cell's limits hold 16,384 tokens at full width to bfloat16's
# rounding; 256 tokens of this 64-wide model read more, so the tiny runs
# are judged by limits of their own, set from their readings at SEED:
# sound 3.0e-4 / 4.7e-3 / 5.2e-3 (loss, grad, change gaps); the float8
# control 2.6e-3 / 5.6e-2 / 2.2e-2; half the batch 6.7e-3 / 8.9e-2 / 2.6e-2.
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.015, "change_gap": 0.06}


def tiny_cell(seq_len=64, rows=4):
    cell = cells.load_cell(CELL)
    return dataclasses.replace(
        cell, config=dict(cell.config, **TINY), limits=TINY_LIMITS,
        traffic=dict(cell.traffic, seq_len=seq_len, global_batch=rows))


def test_cell_config_and_metrics_are_found_by_name():
    cell = cells.load_cell(CELL)
    assert cell.kind == "moe" and cell.chips == 1
    assert cell.traffic["seq_len"] * cell.traffic["global_batch"] == 16384
    bench = cells.benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "tokens_per_s"
        assert callable(cell.module("metrics", name).reduce)
    assert set(METRICS) <= {m["name"] for m in cell.per_layer}
    # no other cell reports them
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not set(METRICS) & {m["name"] for m in cells.load_cell(w["name"]).per_layer}


def test_configuration_states_its_cut():
    c = cells.load_cell(CELL).config
    assert set(c["reduced"]) == set(c["published"]) == {
        "num_hidden_layers", "experts_held", "vocab_size"}
    assert (c["num_hidden_layers"], c["experts_held"], c["vocab_size"]) == (5, 8, 12800)
    assert c["n_routed_experts"] == 64 and c["num_experts_per_tok"] == 6
    assert c["vocab_size"] % 128 == 0 and 8 * c["vocab_size"] == c["published"]["vocab_size"]
    from repro.configs import get_config
    y = get_config(c["arch"]).yarn
    rs = c["rope_scaling"]
    assert (y.factor, y.original, y.beta_fast, y.beta_slow, y.mscale, y.mscale_all_dim) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])


def test_published_sizes_hand_count():
    c = cells.load_cell(CELL).config
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    moe = 2048 * 64 + 2 * 3 * 2048 * 1408 + 0.75 * 3 * 2048 * 1408
    matmul = 5 * mla + 3 * 2048 * 10944 + 4 * moe + 2048 * 12800
    assert moe_flops.forward_flops(c, 4096) == pytest.approx(
        2 * matmul + 5 * 2 * 4096 * 16 * 320)
    assert moe_flops.flops_per_token(c, 4096) == pytest.approx(2.1768e9, rel=1e-4)
    assert moe_flops.pair_flops(c) == 6 * 2048 * 1408


def test_model_flops_at_most_executed():
    from repro.launch import hlo_cost

    seq, rows = 128, 4
    cell = tiny_cell(seq, rows)
    prog = harness.Program.build(cell, jax.devices()[:1])
    params = prog.weights_fn(cell)(jax.random.PRNGKey(0))
    opt = prog.prog.init_opt(params)
    batch = prog.put(ZipfFeed(512, seq, rows, 1.2, 0).host_batch(0))
    tokens = seq * rows
    fwd = jax.jit(lambda p, b: prog.prog.model.train_loss(p, b)[0])
    executed_fwd = hlo_cost.analyze(fwd.lower(params, batch).compile().as_text())["flops"]
    assert moe_flops.forward_flops(cell.config, seq) * tokens <= executed_fwd
    step = prog.prog.train_step.lower(params, opt, batch).compile().as_text()
    assert moe_flops.flops_per_token(cell.config, seq) * tokens <= hlo_cost.analyze(step)["flops"]


# ---------------------------------------------------------------------------
# correct, at a tiny size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fault", [None, *chipbench_faults.ONE_CHIP])
def test_moe_run_is_judged(fault, tmp_path):
    peak = next(iter(cells.peaks().values()))
    res = harness.run_cell(tiny_cell(), jax.devices()[:1], peak, SEED, 0.5, False,
                           time.perf_counter(), tmp_path,
                           step_fault=chipbench_faults.ONE_CHIP.get(fault),
                           log=lambda m: None)
    assert set(res["checks"]) == set(check.NUMBERS)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is (fault is None), res["checks"]


def test_control_fails():
    cell = tiny_cell()
    prog = harness.Program.build(cell, jax.devices()[:1])
    weights = check.flat(prog.weights_fn(cell)(check.seed_key(SEED)))
    tr = cell.traffic
    feed = ZipfFeed(cell.config["vocab_size"], tr["seq_len"], tr["global_batch"],
                    tr["zipf"], SEED)
    batches = [(b["tokens"], b["labels"]) for b in map(feed.host_batch, range(3))]

    def ref(**kw):
        return check.reference_readings(cell.kind, cell.config, weights, batches,
                                        tr["optimizer"], **kw)

    ok, checks = check.judge(check.compare(ref(precision="fp8"), ref()), cell.limits)
    assert not ok, checks


# ---------------------------------------------------------------------------
# the readers on a hand-made trace (times in ns)
# ---------------------------------------------------------------------------

MAP = {"r.1": "moe.route", "e.1": "moe.experts", "e.2": "moe.experts", "o.1": "other"}
HAND = tracing.Trace(
    ops={0: [("r.1", "fusion", 0.0, 100.0), ("e.1", "custom-call", 100.0, 400.0),
             ("e.2", "custom-call", 400.0, 500.0), ("o.1", "fusion", 500.0, 900.0)]},
    async_ops={}, spans=[("data", 0.0, 10.0), ("wait", 10.0, 1000.0)])
STEPS = 2


@dataclasses.dataclass
class Run:
    cell: object
    chips: int
    trace: object
    traced_steps: int
    counters: list
    peak: dict


def _metric(name, run):
    return cells.load_module(cells.ROOT / cells.BENCH_DIR / "metrics"
                             / f"{name}.py").reduce(run)


def test_readers_on_a_hand_made_trace(monkeypatch):
    cell = cells.load_cell(CELL)
    monkeypatch.setattr(blocks, "_readings",
                        {cell.name: scopes.self_ms(HAND, MAP, STEPS)})
    pairs = 49152.0
    run = Run(cell, 1, HAND, STEPS, [{"moe/held_pairs": pairs}] * 3,
              {"bf16_flops_per_s": 197e12})
    assert _metric("moe.route_ms", run) == pytest.approx(100 / 1e6 / STEPS)
    experts_ms = _metric("moe.experts_ms", run)
    assert experts_ms == pytest.approx(400 / 1e6 / STEPS)
    want = 100 * 4 * 6 * 2048 * 1408 * pairs / (experts_ms / 1e3 * 197e12)
    assert _metric("moe.experts_roofline", run) == pytest.approx(want)


def test_a_program_without_blocks_reads_nothing(monkeypatch):
    """Laid over a program that has no block map, every reader is left
    out, and none raises."""
    from repro.train import build

    monkeypatch.delattr(build.Program, "step_blocks")
    monkeypatch.setattr(blocks, "_readings", {})
    run = Run(cells.load_cell(CELL), 1, HAND, STEPS, [{"moe/held_pairs": 1.0}],
              {"bf16_flops_per_s": 197e12})
    assert all(_metric(name, run) is None for name in METRICS)
    # and with no counter of held pairs, no roofline
    monkeypatch.setattr(blocks, "_readings",
                        {CELL: scopes.self_ms(HAND, MAP, STEPS)})
    assert _metric("moe.experts_roofline", dataclasses.replace(run, counters=[{}])) is None
