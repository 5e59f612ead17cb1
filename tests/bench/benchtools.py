"""A copy of the benchmark at a size a CPU test can hold."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {"n": 96, "d": 32}
TINY_PERMS = 63

# Feature-table cells through the `pipeline` entry, added to the copy as a
# later PR would add them: a configuration, two traffic mixes, and their
# entries in BENCHMARK.json.
FEATURE_CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "feature_cells")


def _feature_manifest() -> dict:
    with open(os.path.join(FEATURE_CELLS, "cells.json")) as f:
        return json.load(f)


def _add_feature_cells(root: str) -> None:
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(FEATURE_CELLS, "emp_features.json"),
                os.path.join(bench, "configs"))
    for mix in ("braycurtis", "jaccard"):
        shutil.copy(os.path.join(FEATURE_CELLS, mix + ".json"),
                    os.path.join(bench, "traffic"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    for kind, items in _feature_manifest().items():
        m[kind] += items
    with open(path, "w") as f:
        json.dump(m, f)


def tiny_root(tmp_path) -> str:
    """BENCHMARK.json and bench/ copied under tmp_path with the feature
    cells added, every configuration cut to TINY and every traffic mix to
    TINY_PERMS."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    _add_feature_cells(root)
    for sub, patch in (("configs", TINY), ("traffic", {"n_perms": TINY_PERMS})):
        d = os.path.join(root, "bench", sub)
        for name in os.listdir(d):
            path = os.path.join(d, name)
            with open(path) as f:
                obj = json.load(f)
            obj.update({k: v for k, v in patch.items()
                        if k in obj or k == "n_perms"})
            with open(path, "w") as f:
                json.dump(obj, f)
    return root


def workloads():
    """The cells of BENCHMARK.json, then the feature cells."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    return cells + [w["name"] for w in _feature_manifest()["workloads"]]


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
