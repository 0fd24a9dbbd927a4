"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration and
traffic mix. Everything that belongs to one of them sits in a file of its
own, so a new cell, graph, mix or metric is a new file and no edit:

  configs/<config>.json       graph deployment (sizes, family, guarantees)
  families/<family>.py        generator of a graph family: ``generate(cfg, seed)``
  traffic/<traffic>.json      query mix: estimator panel and engine settings
  metrics/<metric>.py         reader of one metric: ``read(run) -> float | None``
  reference/est_<Class>.py    plain reference of one estimator class
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``<ROOT>/BENCHMARK.json`` with its data files
    (configuration and traffic mix, found under ``ROOT``) read. Raises
    ``KeyError`` for an unknown cell and ``OSError`` for a missing file."""
    root = ROOT
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {', '.join(sorted(cells))})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    traffic = read_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def family(name: str):
    return load_module(os.path.join(BENCH_DIR, "families", name + ".py"),
                       f"bench_family_{name}")


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       f"bench_metric_{name.replace('.', '_')}")


def estimator_reference(cls_name: str):
    return load_module(
        os.path.join(BENCH_DIR, "reference", f"est_{cls_name}.py"),
        f"bench_reference_{cls_name}")
