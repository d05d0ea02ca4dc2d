"""Finds a cell's parts by name, each in files of its own:

- the cell and its metrics: ``BENCHMARK.json``;
- its configuration: ``bench/configs/<config>.json``;
- its traffic mix: ``bench/traffic/<traffic>.json``;
- its fixed rate and the limits of its comparison:
  ``bench/workloads/<cell>.json``;
- each per-layer metric's reader: ``bench/metrics/<family>.py``, the
  family being the metric's name without its last ``.``-part.

Adding a cell, a configuration, a mix or a metric adds files and
entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    params: Dict[str, Any]            # bench/workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str


def _applies(metric: Dict[str, Any], cell: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def resolve(cell_name: str, bench_dir: str = BENCH_DIR) -> Cell:
    root = os.path.dirname(bench_dir)
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    w = cells[cell_name]
    config = _load_json(os.path.join(bench_dir, "configs",
                                     w["config"] + ".json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    params_path = os.path.join(bench_dir, "workloads", cell_name + ".json")
    params = _load_json(params_path) if os.path.exists(params_path) else {}
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, cell_name, reported)]
    return Cell(cell_name, int(w["chips"]), config, traffic, params, e2e,
                per_layer, bench_dir)


def reader(metric_name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """The ``read(ctx)`` function of a per-layer metric."""
    family = metric_name.rsplit(".", 1)[0]
    path = os.path.join(bench_dir, "metrics", family + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{family.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
