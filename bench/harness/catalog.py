"""Find a cell's parts by name, from files alone.

``BENCHMARK.json`` names the cells.  A cell's configuration lives in the
file its ``configs`` entry names, its traffic in
``bench/traffic/<traffic>.json``, the limits of its comparison in
``bench/limits/<cell>.json``, and each metric in a reader
``bench/metrics/<metric>.py`` that defines ``read(rec) -> float | None``.
The runner of a traffic kind is ``bench/runners/<kind>.py``; the reference
and the operation counts of a model family (its attention pairs among
them) are ``bench/reference/<family>.py`` and ``bench/flops/<family>.py``.
Adding a cell, a configuration, a traffic mix or a metric therefore adds
files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import sys

from .traffic import Traffic

__all__ = ["BENCH_DIR", "ROOT", "Cell", "Metric", "load_benchmark",
           "load_cell", "load_module", "metric_reader"]

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple | None = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: Traffic
    limits: dict
    end_to_end: tuple
    per_layer: tuple
    bench_dir: pathlib.Path


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _metrics(entries, cell: str) -> tuple:
    out = []
    for e in entries:
        m = Metric(name=e["name"], unit=e["unit"],
                   workloads=tuple(e["workloads"]) if "workloads" in e
                   else None)
        if m.applies_to(cell):
            out.append(m)
    return tuple(out)


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    bench_dir = root / bench.get("paths", ["bench"])[0]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = Traffic.from_dict(w["traffic"], json.load(f))
    limits_file = bench_dir / "limits" / f"{name}.json"
    with open(limits_file) as f:
        limits = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name),
                bench_dir=bench_dir)


def load_module(path: pathlib.Path, name: str | None = None):
    """Import the Python file ``path`` as a module of its own."""
    path = pathlib.Path(path).resolve()
    name = name or "bench_" + hashlib.blake2b(
        str(path).encode(), digest_size=8).hexdigest()
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: pathlib.Path, metric: str):
    """``read(rec)`` of ``bench_dir/metrics/<metric>.py``."""
    return load_module(bench_dir / "metrics" / f"{metric}.py").read
