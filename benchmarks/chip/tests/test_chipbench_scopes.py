"""The train step's layers as the benchmark reads them (``scopes.py``):
the program's map from instruction to layer, at dp=1 and on four virtual
devices; the reducers on a hand-made trace whose sums are known; and a
traced tail of the scoped step recorded on one TPU v5e."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from benchmarks.chip import cells, harness, scopes, tracing
from chipbench_tiny import tiny_cell

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "scoped_tiny_v5e.json"
ONE_CHIP = "qwen2-0.5b.s512.uniform.1chip"
DP4 = "qwen2-0.5b.s512.uniform.dp4.zen"
NEW = ["step.fwd_ms", "step.bwd_ms", "step.remat_ms", "step.opt_ms",
       "step.unscoped_ms", "sync.encode_ms", "sync.exchange_ms",
       "sync.exposed_ms", "zero1.gather_ms", "build.compile_s", "build.compiles"]


# ---------------------------------------------------------------------------
# the program's map
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    cell = tiny_cell(ONE_CHIP)
    prog = harness.Program.build(cell, jax.devices()[:1])
    return prog, prog.prog.step_scopes()


def test_dp1_map_has_the_model_layers_and_no_exchange(one_chip):
    _, mapping = one_chip
    layers = set(mapping.values())
    assert {"fwd", "bwd", "remat", "opt", "unscoped"} <= layers
    # zen is the identity at dp=1, and ZeRO-1 gathers nothing
    assert not {"sync.exchange", "zero1.gather"} & layers
    assert layers <= {"fwd", "bwd", "remat", "opt", "sync.encode", "unscoped"}


def test_map_describes_the_executable_the_trainer_runs(one_chip):
    """The abstract lowering of ``step_scopes`` compiles to the very
    instructions of the step called with the trainer's own arrays."""
    prog, mapping = one_chip
    cell = tiny_cell(ONE_CHIP)
    s = harness.set_up(cell, prog, 2**31 + 3)
    batch = prog.put(s.feed.host_batch(s.next_step))
    compiled = prog.prog.train_step.lower(s.params, s.opt, batch).compile()
    from repro.analysis.scopes import instruction_scopes
    assert instruction_scopes(compiled.as_text()) == mapping


def test_dp4_map_puts_every_collective_in_its_layer():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(HERE / "chipbench_scopes_dp4_worker.py")],
                       env=env, capture_output=True, text=True, timeout=900,
                       check=False)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["same_map"]
    for layer in ("fwd", "bwd", "remat", "opt", "sync.encode", "sync.exchange",
                  "zero1.gather"):
        assert out["layers"].get(layer, 0) > 0, (layer, out["layers"])
    by_layer = {}
    for c in out["collectives"]:
        by_layer.setdefault(c["layer"], []).append(c)
    # zen's push all-to-all and pull all-gathers, the dense psum
    assert {c["kind"] for c in by_layer["sync.exchange"]} >= {
        "all-to-all", "all-gather", "all-reduce"}, by_layer["sync.exchange"]
    assert {c["kind"] for c in by_layer["zero1.gather"]} == {"all-gather"}
    # the one collective in no layer is the pmean of the step's scalar metrics
    assert set(by_layer) <= {"sync.exchange", "zero1.gather", "unscoped"}
    assert [(c["kind"], c["scalars"]) for c in by_layer["unscoped"]] == [
        ("all-reduce", True)]


# ---------------------------------------------------------------------------
# reducers on a hand-made trace (times in ns)
# ---------------------------------------------------------------------------

MAP = {"f.1": "fwd", "e.1": "sync.encode", "ar.1": "sync.exchange",
       "ars.1": "sync.exchange", "w.1": "bwd", "b.1": "bwd", "o.1": "opt",
       "ag.1": "zero1.gather", "ags.1": "zero1.gather", "u.1": "unscoped"}
HAND = tracing.Trace(
    ops={0: [("f.1", "fusion", 0.0, 100.0), ("e.1", "fusion", 100.0, 120.0),
             ("ar.1", "all-reduce", 120.0, 160.0), ("w.1", "while", 200.0, 400.0),
             ("b.1", "fusion", 220.0, 300.0), ("o.1", "fusion", 400.0, 450.0),
             ("ag.1", "all-gather-done", 470.0, 480.0), ("u.1", "copy", 480.0, 490.0)],
         1: [("f.1", "fusion", 0.0, 300.0)]},
    async_ops={0: [("ars.1", "all-reduce-start", 150.0, 210.0),
                   ("ags.1", "all-gather-start", 440.0, 480.0)]},
    spans=[("data", 0.0, 10.0), ("wait", 10.0, 500.0)])
STEPS = 2


def test_hand_trace_self_time_per_layer():
    got = scopes.self_ms(HAND, MAP, STEPS)
    # device 0: fwd 100, encode 20, exchange 40, bwd 120 + 80 nested, opt 50,
    # gather 10, unscoped 10; device 1: fwd 300.  Averaged over 2 chips.
    want_ns = {"fwd": 200, "sync.encode": 10, "sync.exchange": 20, "bwd": 100,
               "opt": 25, "zero1.gather": 5, "unscoped": 5}
    assert got == pytest.approx({k: v / 1e6 / STEPS for k, v in want_ns.items()})
    # the layers account for every busy nanosecond
    busy_ms = 1e3 * tracing.busy_s(HAND) / STEPS
    assert sum(got.values()) == pytest.approx(busy_ms)


def test_hand_trace_in_flight_and_exposed():
    # exchange in flight on device 0: [120,160] u [150,210] = 90 ns
    assert scopes.in_flight_ms(HAND, MAP, STEPS, "sync.exchange") == pytest.approx(
        90 / 2 / STEPS / 1e6)
    # gather: [470,480] u [440,480] = 40 ns
    assert scopes.in_flight_ms(HAND, MAP, STEPS, "zero1.gather") == pytest.approx(
        40 / 2 / STEPS / 1e6)
    # exposed: [120,210] less the non-sync ops ([200,400] ...) = [120,200];
    # the encode op at [100,120] is sync and hides nothing
    exposed = scopes.exposed_ms(HAND, MAP, STEPS)
    assert exposed == pytest.approx(80 / 2 / STEPS / 1e6)
    assert exposed <= scopes.in_flight_ms(HAND, MAP, STEPS, "sync.exchange")


def test_unmapped_time_over_one_percent_reads_nothing():
    def with_unmapped(ns):
        ops = {**HAND.ops, 0: HAND.ops[0] + [("x.9", "fusion", 450.0, 450.0 + ns)]}
        return dataclasses.replace(HAND, ops=ops)

    # busy over both chips 450 + 300 ns: 1 % is 7.5 ns
    assert scopes.self_ms(with_unmapped(5.0), MAP, STEPS) is not None
    assert scopes.self_ms(with_unmapped(20.0), MAP, STEPS) is None


@dataclasses.dataclass
class Run:
    cell: object
    chips: int
    trace: object
    traced_steps: int


def _metric(name, run):
    return cells.load_module(cells.ROOT / cells.BENCH_DIR / "metrics"
                             / f"{name}.py").reduce(run)


def test_metrics_read_the_cells_reading(monkeypatch):
    cell = cells.load_cell(DP4)
    monkeypatch.setattr(scopes, "_readings", {cell.name: {
        "compile": {"executables": 7, "seconds": 1.5}, "scopes": MAP,
        "self_ms": scopes.self_ms(HAND, MAP, STEPS)}})
    run = Run(cell, 2, HAND, STEPS)
    assert _metric("step.bwd_ms", run) == pytest.approx(100 / 1e6 / STEPS)
    assert _metric("step.remat_ms", run) == 0.0
    assert _metric("sync.exposed_ms", run) == pytest.approx(20 / 1e6)
    assert _metric("zero1.gather_ms", run) == pytest.approx(10 / 1e6)
    assert _metric("build.compiles", run) == 7
    assert _metric("build.compile_s", run) == 1.5
    # a map that does not cover the trace: no scope metric, the counters stay
    scopes._readings[cell.name]["self_ms"] = None
    assert all(_metric(n, run) is None for n in NEW[:9])
    assert _metric("build.compiles", run) == 7


def test_a_program_without_scopes_or_counters_reads_nothing(monkeypatch):
    """Laid over an older program, every new metric is left out, and none
    raises."""
    from repro.launch import compile_cache
    from repro.train import build

    monkeypatch.delattr(build.Program, "step_scopes")
    monkeypatch.delattr(compile_cache, "compile_stats")
    monkeypatch.setattr(scopes, "_readings", {})
    run = Run(cells.load_cell(ONE_CHIP), 1, HAND, STEPS)
    assert all(_metric(name, run) is None for name in NEW)


def test_every_new_metric_is_registered():
    bench = cells.benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(per_layer)
    for name in ("sync.exchange_ms", "sync.exposed_ms", "zero1.gather_ms"):
        assert per_layer[name]["workloads"] == [DP4]
    assert {m["name"] for m in cells.load_cell(ONE_CHIP).per_layer} >= (
        set(NEW) - {"sync.exchange_ms", "sync.exposed_ms", "zero1.gather_ms"})
    assert {m["name"] for m in cells.load_cell(DP4).per_layer} >= set(NEW)


# ---------------------------------------------------------------------------
# a traced tail recorded on the chip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    d = json.loads(FIXTURE.read_text())
    return tracing.Trace.from_json(json.dumps(d["trace"])), d["scopes"], d["steps"]


def test_recorded_layers_account_for_busy_time(recorded):
    trace, mapping, steps = recorded
    got = scopes.self_ms(trace, mapping, steps)
    assert got is not None
    assert {"fwd", "bwd", "remat", "opt"} <= set(got)
    busy_ms = 1e3 * tracing.busy_s(trace) / steps
    assert sum(got.values()) == pytest.approx(busy_ms, rel=0.02)


def test_recorded_fixture_is_small():
    assert FIXTURE.stat().st_size <= 300_000
