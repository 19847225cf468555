"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric lives in a file of its own under this directory,
found by its name:

    configs/<config>.json    sizes as run, source, departures  (BENCHMARK.json "file")
    traffic/<traffic>.json   seq, global batch, Zipf exponent, mesh, sync, optimizer
    limits/<cell>.json       the limit of each number that decides ``correct``
    metrics/<metric>.py      one reducer per per-layer metric
    flops/<kind>.py          model FLOPs per token of an architecture kind
    reference/<kind>.py      plain float32 reference of an architecture kind

A later cell, configuration or metric adds files; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH_DIR = HERE.relative_to(ROOT)


class NoChip(RuntimeError):
    """The machine cannot run this cell: no TPU, too few chips, or a
    ``device_kind`` the peaks table does not know."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / BENCH_DIR

    @property
    def kind(self) -> str:
        return self.config["kind"]

    def module(self, sub: str, name: str):
        """Load ``<bench dir>/<sub>/<name>.py`` by path (names may hold
        dots, so they are not importable as packages)."""
        return load_module(self.bench_dir / sub / f"{name}.py")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return _read(root / "BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(root / configs[w["config"]]["file"])
    bench_dir = root / BENCH_DIR
    traffic = _read(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _read(bench_dir / "limits" / f"{name}.json")

    def reported(metrics):
        return tuple(m for m in metrics
                     if name in m.get("workloads", (name,)))

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=reported(bench["end_to_end"]),
                per_layer=reported(bench["per_layer"]), root=root)


def peaks(root: Path = ROOT) -> dict:
    return _read(root / BENCH_DIR / "peaks.json")


def require_chips(cell: Cell):
    """The devices this cell runs on and their peaks; raises ``NoChip``
    without a TPU, with fewer chips than the cell asks for, or on a
    ``device_kind`` missing from ``peaks.json``.  Never falls back."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, "
                     f"JAX found {len(devices)}")
    kind = devices[0].device_kind
    table = peaks(cell.root)
    if kind not in table:
        raise NoChip(f"device_kind {kind!r} is not in peaks.json "
                     f"(known: {sorted(table)})")
    return devices[:cell.chips], table[kind]
