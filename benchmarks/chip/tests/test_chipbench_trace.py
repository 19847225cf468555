"""The trace reducers, on a trace recorded on a TPU v5e and on a small
hand-made one whose sums are known."""
from pathlib import Path

import pytest

from benchmarks.chip import tracing

FIX = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def probe():
    # three steps of (data, dispatch, wait) around a 4096^2 bf16 matmul,
    # recorded on one TPU v5 lite chip
    return tracing.extract(FIX)


def test_extract_reads_device_ops_and_host_spans(probe):
    assert sorted(probe.ops) == [0]
    assert [s[0] for s in probe.spans] == ["data", "dispatch", "wait"] * 3
    names = {op for _, op, _, _ in probe.ops[0]}
    assert {"fusion", "copy-start", "copy-done"} <= names
    assert all(e > s for _, _, s, e in probe.ops[0])


def test_recorded_busy_and_breakdown(probe):
    busy, window = tracing.busy_s(probe), tracing.window_s(probe)
    assert 0 < busy < window
    # the three matmul fusions ran 0.70 ms each on the chip
    bd = tracing.breakdown(probe)
    top_name, top_s = bd["device_ops"][0]
    assert top_name == "fusion"
    assert top_s == pytest.approx(3 * 704.7e-6, rel=0.01)
    assert bd["idle_gaps"] and all(g[1] > 0 for g in bd["idle_gaps"])
    assert not any(tracing.is_collective(op) for _, op, _, _ in probe.ops[0])


def test_op_name_parses_hlo_text():
    assert tracing.op_name(
        "%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start("
        "f32[8]{0} %p), replica_groups={{0,1,2,3}}") == (
        "all-gather-start.3", "all-gather-start")
    assert tracing.op_name(
        "%fusion.7 = bf16[4096]{0:T(1024)(128)(2,1)} fusion(bf16[4096,4096]"
        "{1,0:T(8,128)(2,1)S(1)} %copy-done), kind=kOutput") == ("fusion.7", "fusion")
    assert tracing.is_collective("all-reduce-done")
    assert not tracing.is_collective("fusion")


HAND = tracing.Trace(
    ops={0: [("f.1", "fusion", 0.0, 100.0), ("ar.1", "all-reduce", 80.0, 150.0),
             ("w.1", "while", 200.0, 400.0), ("f.2", "fusion", 220.0, 300.0)],
         1: [("f.1", "fusion", 10.0, 110.0), ("ar.1", "all-reduce", 110.0, 160.0)]},
    async_ops={0: [("ag.1", "all-gather-start", 300.0, 350.0)]},
    spans=[("data", 0.0, 20.0), ("dispatch", 20.0, 30.0), ("wait", 150.0, 500.0)])


def test_hand_trace_sums():
    assert tracing.window_s(HAND) == pytest.approx(500e-9)
    # device 0 busy [0,150] + [200,400] = 350 ns; device 1 [10,160] = 150
    assert tracing.busy_s(HAND) == pytest.approx(250e-9)
    c0, exposed0 = tracing.collective_ns(HAND, 0)
    assert c0 == [[80.0, 150.0], [300.0, 350.0]]
    # [80,100] overlaps f.1, [300,350] lies inside the while loop
    assert exposed0 == [[100.0, 150.0]]
    c1, exposed1 = tracing.collective_ns(HAND, 1)
    assert tracing.length(exposed1) == 50.0


def test_hand_trace_self_times_and_gaps():
    st = tracing.self_times(HAND.ops[0])
    assert st["w.1"] == pytest.approx(120e-9)   # 200 minus the nested 80
    assert st["f.2"] == pytest.approx(80e-9)
    bd = tracing.breakdown(HAND)
    # device 0 is idle in [150,200] and [400,500], both inside the wait span
    assert bd["idle_gaps"] == [["wait", pytest.approx(100e-9)],
                               ["wait", pytest.approx(50e-9)]]


def test_trace_round_trips_through_json():
    again = tracing.Trace.from_json(HAND.to_json())
    assert again == HAND
