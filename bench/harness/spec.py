"""Finds a cell and everything it names, by name, from files alone.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics.  Under the benchmark's directory (its first ``paths`` entry):

- ``configs/...``: each configuration's file, named in ``BENCHMARK.json``;
  its ``model`` key names its model module, ``models/<model>.py`` (the
  program's config, the weights' layout and the FLOP count of that
  architecture: ``harness.model`` lists the functions it provides), and
  its ``reference`` key its plain reference, ``reference/<reference>.py``;
- ``traffic/<mix>.json``: a traffic mix, read by ``harness.traffic``;
- ``metrics/<metric>.py``: the reader of one per-layer metric, a module
  with ``read(ctx) -> float | None``.

A later configuration, model, reference, cell, mix or metric is a new file
and a new entry; nothing here changes.
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
    model: ModuleType         # models/<config["model"]>.py
    traffic: dict             # the mix file's contents
    end_to_end: tuple         # metric entries this cell reports untraced
    per_layer: tuple          # ... and traced
    bench_dir: Path


def _load_module(path: Path) -> ModuleType:
    name = f"bench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _named_module(bench_dir: Path, subdir: str, key: str,
                  config_name: str, arch: dict) -> ModuleType:
    """``<subdir>/<arch[key]>.py``, the module a configuration file names
    under ``key``; an error that names both when either is missing."""
    if key not in arch:
        raise KeyError(f"configuration {config_name!r} names no {key!r}")
    path = bench_dir / subdir / f"{arch[key]}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {config_name!r} names {key} {arch[key]!r}, "
            f"but there is no {path}")
    return _load_module(path)


def _check_cuts(conf: dict, arch: dict) -> None:
    """Every key that ``reduced`` lists is stated in the file's ``cut``: the
    published value and the deployment whose one chip's share is held."""
    for key in conf["reduced"]:
        cut = arch.get("cut", {}).get(key, {})
        if key not in arch or not {"published", "deployment"} <= set(cut):
            raise ValueError(
                f"configuration {conf['name']!r} lists {key!r} under "
                f"reduced; its file holds the value held here under that "
                f"key and states cut.{key}.published and .deployment")


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench_dir = root / spec["paths"][0]
    arch = json.loads((root / conf["file"]).read_text())
    _check_cuts(conf, arch)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=conf["name"],
        config=arch,
        model=_named_module(bench_dir, "models", "model", conf["name"], arch),
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
    return _named_module(cell.bench_dir, "reference", "reference",
                         cell.config_name, cell.config)
