"""Every cell resolves to its files by name, and a cell, a configuration
or a metric added as new files is found with no code edit."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import cells, harness

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = cells.load_cell(name)
    assert cell.chips in (1, 4)
    assert cell.traffic["mesh"][0] * cell.traffic["mesh"][1] == cell.chips
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s"}
    for m in cell.per_layer:
        assert callable(cell.module("metrics", m["name"]).reduce)
    # the configuration file holds the sizes as run: applying them to the
    # program's registry entry changes nothing
    from repro.configs import get_config
    assert harness.arch_config(cell) == get_config(cell.config["arch"])


def test_configs_are_used_and_listed():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    (root / "benchmarks").mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "chip", root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    d = root / "benchmarks" / "chip"
    cfg = json.loads((d / "configs" / "qwen2-0.5b.json").read_text())
    cfg["name"] = "qwen2-0.5b-copy"
    (d / "configs" / "qwen2-0.5b-copy.json").write_text(json.dumps(cfg))
    (d / "traffic" / "s1024.b4.dp1.zen.json").write_text(json.dumps(
        dict(json.loads((d / "traffic" / "s512.b8.dp1.zen.json").read_text()),
             seq_len=1024, global_batch=4)))
    (d / "limits" / "qwen2-0.5b-copy.s1024.1chip.json").write_text(json.dumps(
        {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 1e-2}))
    (d / "metrics" / "data.rows.py").write_text(
        "def reduce(run):\n    return float(run.tokens_per_step)\n")
    bench["configs"].append(dict(bench["configs"][0], name="qwen2-0.5b-copy",
                                 file="benchmarks/chip/configs/qwen2-0.5b-copy.json"))
    bench["workloads"].append({"name": "qwen2-0.5b-copy.s1024.1chip",
                               "config": "qwen2-0.5b-copy",
                               "traffic": "s1024.b4.dp1.zen", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "data.rows", "unit": "tokens", "better": "higher",
                               "source": "host_clock", "layer": "input pipeline",
                               "moves": "tokens_per_s",
                               "workloads": ["qwen2-0.5b-copy.s1024.1chip"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("qwen2-0.5b-copy.s1024.1chip", root=root)
    assert cell.config["name"] == "qwen2-0.5b-copy"
    assert cell.traffic["seq_len"] == 1024
    assert "data.rows" in [m["name"] for m in cell.per_layer]
    rec = dataclasses.make_dataclass("R", ["tokens_per_step"])(4096)
    assert cell.module("metrics", "data.rows").reduce(rec) == 4096.0
    # a metric listed for other cells only is not reported here
    assert "sync.sparse_words" not in [m["name"] for m in cell.per_layer]
    with pytest.raises(KeyError):
        cells.load_cell("no-such-cell", root=root)


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    cell = cells.load_cell(BENCH["workloads"][0]["name"])
    with pytest.raises(cells.NoChip, match="not in peaks.json"):
        cells.require_chips(cell)
    monkeypatch.setattr(jax, "devices", lambda: [type("C", (), {
        "platform": "cpu", "device_kind": "cpu"})()])
    with pytest.raises(cells.NoChip, match="no TPU"):
        cells.require_chips(cell)


def test_peaks_name_their_source():
    for kind, p in cells.peaks().items():
        assert p["source"] and p["bf16_flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0
