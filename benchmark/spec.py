"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own under ``benchmark/``, found by name:

- ``configs/<config>.json``: the sizes, the solver flags, the reference;
- ``traffic/<traffic>.json``: the mix's parameters, naming its driver,
  ``traffic/<driver>.py``;
- ``workloads/<cell>.json``: the cell's own parameters and limits;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``;
- ``references/<reference>.py``: the plain reference a configuration names.

A later PR adds a cell, configuration, mix or metric by adding such files
and entries; no code here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path (names may hold '.' or '-')."""
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list = field(default_factory=list)  # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)
    bench_dir: str = BENCH_DIR

    def driver(self):
        return load_module(os.path.join(
            self.bench_dir, "traffic", self.traffic["driver"] + ".py"))

    def reference(self):
        return load_module(os.path.join(
            self.bench_dir, "references", self.config["reference"] + ".py"))

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    workload = load_json(os.path.join(bench_dir, "workloads", name + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        workload=workload,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )
