"""From a workload name in ``BENCHMARK.json`` to the files that define
it. Nothing here knows a cell, a configuration, a traffic mix or a
metric by name: each is found by the name ``BENCHMARK.json`` gives it,
so a later PR adds a cell by adding files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Callable, Dict, List, Mapping, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Mapping[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _overlay(base: Dict[str, Any], over: Mapping[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) \
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(workload: str, rehearse: bool = False,
              root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        # the same code at toy sizes on the CPU: each file carries its
        # own toy overrides, so a new cell rehearses with no new code
        config = _overlay(config, config.get("rehearse", {}))
        traffic = _overlay(traffic, traffic.get("rehearse", {}))
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_driver(kind: str) -> Callable[..., Any]:
    """``benchmark/drivers/<kind>.py::run``; a new traffic kind is a
    new file."""
    return importlib.import_module(f"benchmark.drivers.{kind}").run


def load_layer_metric(name: str) -> Optional[Callable[..., Any]]:
    """``benchmark/layer_metrics/<name>.py::read``."""
    try:
        mod = importlib.import_module(f"benchmark.layer_metrics.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.layer_metrics.{name}":
            raise
        return None
    return mod.read
