"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything one cell needs is data beside this file: ``BENCHMARK.json`` at
the checkout root names the cells and metrics; a configuration is the JSON
file its entry names; a traffic mix is ``bench/traffic/<traffic>.json``; a
per-layer metric is read by ``bench/metrics/<metric>.py``, whose ``read(run)``
returns the number or ``None`` when the run holds nothing to read.  A new
cell, mix or metric is therefore new files and entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional


class Spec:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.configs = {c["name"]: c for c in self.bench["configs"]}
        self.cells = {w["name"]: w for w in self.bench["workloads"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self.configs[name]["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        path = os.path.join(self.root, "bench", "traffic", name + ".json")
        with open(path) as f:
            return json.load(f)

    @staticmethod
    def _applies(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> List[dict]:
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.bench["per_layer"]
                if self._applies(m, cell) and m["moves"] in reported]

    def reader(self, metric: str) -> Callable[[object], Optional[float]]:
        path = os.path.join(self.root, "bench", "metrics", metric + ".py")
        mod_name = "bench_metric_" + metric.replace(".", "_").replace("-", "_")
        loader = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(mod)
        return mod.read

    def readers(self, cell: str) -> Dict[str, Callable]:
        return {m["name"]: self.reader(m["name"]) for m in self.per_layer(cell)}
