"""Resolve a cell of `BENCHMARK.json` into the files that define it.

Everything is found by name, so a later PR adds a configuration, a traffic
mix, a cell or a per-layer metric by adding files and entries:

    workload.config   -> the `file` of the entry of `configs` with that name
    workload.traffic  -> <dir>/traffic/<traffic>.json   for a <dir> in `paths`
    traffic.kind      -> <dir>/loops/<kind>.py          (imported as a module)
    what a configuration names (its reference, glue and work module)
                      -> <dir>/<that path>              (imported as a module)
    per-layer metric  -> <dir>/layer_metrics/<name>.py  (loaded by path)
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNS_DIR = ".bench_runs"    # under the checkout, git-ignored: a run's files


class NoResult(Exception):
    """The run cannot produce a result at all (no chip, no program): the
    process exits non-zero and prints no last line."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    paths: List[str]
    root: str


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _find(root: str, paths: List[str], *parts: str) -> str:
    for directory in paths:
        candidate = os.path.join(root, directory, *parts)
        if os.path.isfile(candidate):
            return candidate
    raise FileNotFoundError(
        f"no {os.path.join(*parts)} under any of {paths}")


def _for_cell(metrics: List[Dict[str, Any]], cell: str
              ) -> List[Dict[str, Any]]:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def resolve(workload: str, benchmark_file: Optional[str] = None,
            root: str = ROOT) -> Cell:
    bench = load_json(benchmark_file
                      or os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r}; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    paths = list(bench["paths"])
    return Cell(
        name=workload, chips=int(entry["chips"]), why=entry["why"],
        config_name=entry["config"],
        config=load_json(os.path.join(root, config_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=load_json(_find(root, paths, "traffic",
                                entry["traffic"] + ".json")),
        end_to_end=_for_cell(bench["end_to_end"], workload),
        per_layer=_for_cell(bench["per_layer"], workload),
        paths=paths, root=root)


def module(root: str, paths: List[str], relative: str):
    """A module of the benchmark by its path under one of `paths`
    (`reference/gpt2.py`), imported by its dotted name."""
    path = _find(root, paths, *relative.split("/"))
    dotted = os.path.relpath(path, root)[:-len(".py")].replace(os.sep, ".")
    return importlib.import_module(dotted)


def loop_module(cell: Cell):
    """The module that runs this cell's `kind` of traffic."""
    return module(cell.root, cell.paths,
                  "loops/" + cell.traffic["kind"] + ".py")


def layer_reader(cell: Cell, metric: str) -> Callable[[Dict[str, Any]],
                                                      Optional[float]]:
    """`read(run)` of the per-layer metric's own file."""
    path = _find(cell.root, cell.paths, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + "".join(
            c if c.isalnum() else "_" for c in metric), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
