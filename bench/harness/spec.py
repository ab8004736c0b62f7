"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's root
names the cell's configuration and traffic mix and the metrics; each lives
in a file of its own under ``bench/``:

- ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives),
- ``bench/traffic/<traffic>.json``,
- ``bench/metrics/<metric>.py``, a module with ``read(run)`` that returns
  the metric's value or ``None`` where the run holds nothing to read.

Adding a cell, a mix or a metric adds files and entries; no code here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]          # metric entries this cell reports
    per_layer: List[Dict]


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def reader(metric: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(entries: List[Dict], run, root: str = ROOT
                 ) -> Dict[str, Dict]:
    """``{name: {"value": v, "unit": u}}`` for every entry whose reader
    finds something to read."""
    out = {}
    for m in entries:
        value: Optional[float] = reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
