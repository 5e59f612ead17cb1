"""Find a cell's pieces by the names in BENCHMARK.json.

A workload names a configuration and a traffic mix. Each lives in a file
of its own, and so do the entry a configuration runs and the reader of
each per-layer metric:

  bench/configs/<config>.json    sizes, source, `entry`
  bench/traffic/<traffic>.json   parameters of the test stream
  bench/entries/<entry>.py       set-up, one test, the reference check
  bench/metrics/<metric>.py      `read(ctx)` -> value or None

A later cell, mix, entry or metric is new files and a new entry in
BENCHMARK.json; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from types import ModuleType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: ModuleType
    end_to_end: list      # manifest entries this cell reports with --trace 0
    per_layer: list       # (manifest entry, reader module) with --trace 1


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import a file by path under a name made from its own path, so two
    files never share a module."""
    name = "bench_file" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e_here: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_here


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Everything one run of workload `name` needs, found under `root`."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    bench = os.path.join(root, "bench")
    config = load_json(os.path.join(bench, "configs", w["config"] + ".json"))
    traffic = load_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    entry = load_module(os.path.join(bench, "entries",
                                     config["entry"] + ".py"))
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [(m, load_module(os.path.join(bench, "metrics",
                                              m["name"] + ".py")))
                 for m in manifest["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, entry=entry, end_to_end=e2e,
                per_layer=per_layer)
