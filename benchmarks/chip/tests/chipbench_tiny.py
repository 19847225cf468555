"""Tiny versions of the benchmark's cells for CPU tests: a cell's own
files with every width cut so a step compiles and runs in seconds."""
import dataclasses
import json

from benchmarks.chip import cells

TINY = {
    "dense": dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=512),
    "ssm": dict(n_layer=2, d_model=64, d_state=16, headdim=16, vocab_size=512),
}
PROVISIONAL = {"loss_gap": 1.0, "grad_gap": 1.0, "change_gap": 1.0}


def files_cell(config: str, traffic: str) -> cells.Cell:
    """A cell made from a configuration file and a traffic file alone, for
    configurations that have no cell in BENCHMARK.json."""
    d = cells.ROOT / cells.BENCH_DIR
    cfg = json.loads((d / "configs" / f"{config}.json").read_text())
    tr = json.loads((d / "traffic" / f"{traffic}.json").read_text())
    return cells.Cell(name=f"{config}.{traffic}", chips=tr["mesh"][0] * tr["mesh"][1],
                      config=cfg, traffic=tr, limits=PROVISIONAL, end_to_end=(),
                      per_layer=(), root=cells.ROOT)


def tiny_cell(name: str | None = None, *, config: str | None = None,
              traffic_name: str | None = None, seq_len: int = 64,
              rows_per_chip: int = 4):
    cell = (cells.load_cell(name) if name is not None
            else files_cell(config, traffic_name))
    cfg = dict(cell.config, **TINY[cell.kind])
    if cell.kind == "ssm":
        cfg["published"] = dict(cfg["published"], chunk_size=16)
    tr = dict(cell.traffic, seq_len=seq_len,
              global_batch=rows_per_chip * cell.traffic["mesh"][0])
    return dataclasses.replace(cell, config=cfg, traffic=tr)
