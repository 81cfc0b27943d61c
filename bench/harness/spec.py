"""Finds a cell and everything it names, by name, from files alone.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics.  Under the benchmark's directory (its first ``paths`` entry):

- ``configs/...``: each configuration's file, named in ``BENCHMARK.json``;
  its ``reference`` key names its plain reference,
  ``reference/<reference>.py``;
- ``traffic/<mix>.json``: a traffic mix, read by ``harness.traffic``;
- ``metrics/<metric>.py``: the reader of one per-layer metric, a module
  with ``read(ctx) -> float | None``.

A later cell, mix or metric is a new file and a new entry; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict              # the configuration file's contents
    traffic: dict             # the mix file's contents
    end_to_end: tuple         # metric entries this cell reports untraced
    per_layer: tuple          # ... and traced
    bench_dir: Path


def _load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench_dir = root / spec["paths"][0]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=conf["name"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)),
        bench_dir=bench_dir)


def metric_reader(cell: Cell, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _load_module(cell.bench_dir / "metrics" / f"{metric}.py").read


def reference(cell: Cell) -> ModuleType:
    """The configuration's plain reference module."""
    return _load_module(cell.bench_dir / "reference"
                        / f"{cell.config['reference']}.py")
