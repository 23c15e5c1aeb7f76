"""Finds everything a cell needs by the names ``BENCHMARK.json`` gives.

Nothing here names a configuration, a mix or a metric: a cell is
``BENCHMARK.json``'s workload entry, its configuration is
``configs/<config>.json``, its traffic ``mixes/<traffic>.json``, the limits
of its correctness check ``limits/<workload>.json``, and each metric is read
by ``metrics/<metric>.py``.  Adding a cell, a mix or a metric is adding
files and entries; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def root_of(bench_dir: str = BENCH_DIR) -> str:
    return os.path.dirname(bench_dir)


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name: str, root: Optional[str] = None,
                 bench_dir: str = BENCH_DIR):
        root = root if root is not None else root_of(bench_dir)
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(by_name)}")
        self.name = name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        self.config = _load_json(os.path.join(
            bench_dir, "configs", f"{self.workload['config']}.json"))
        self.mix = _load_json(os.path.join(
            bench_dir, "mixes", f"{self.workload['traffic']}.json"))
        self.limits: Dict[str, float] = _load_json(os.path.join(
            bench_dir, "limits", f"{name}.json"))
        self.bench_dir = bench_dir

    def _metrics(self, section: str) -> List[dict]:
        """The section's metrics that this cell reports: those without a
        ``workloads`` list, and those whose list names the cell."""
        return [m for m in self.spec[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def end_to_end(self) -> List[dict]:
        return self._metrics("end_to_end")

    def per_layer(self) -> List[dict]:
        return self._metrics("per_layer")

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        """``metrics/<metric>.py``'s ``read(ctx)``."""
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        mod_name = "bench_metric_" + "".join(
            c if c.isalnum() else "_" for c in metric)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def read_metrics(cell: Cell, metrics: List[dict], ctx: dict) -> Dict[str, dict]:
    """Each metric's reader over the run's records; a reader that finds
    nothing returns None and the metric is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
