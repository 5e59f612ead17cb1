"""A later PR adds a configuration, a traffic mix, an entry and a
per-layer metric as new files and new entries in BENCHMARK.json; the
harness finds them by name and no file that was there changes."""

import hashlib
import io
import json
import os
from contextlib import redirect_stdout

from benchtools import last_json, tiny_root

from bench import manifest, run


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for name in files:
            if name.endswith((".py", ".json")):
                path = os.path.join(dirpath, name)
                with open(path, "rb") as f:
                    out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_cell_is_picked_up_without_editing_a_file(tmp_path):
    root = tiny_root(tmp_path)
    before = _digests(root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "emp_matrix.json")) as f:
        cfg = json.load(f)
    cfg.update(name="emp_matrix_jaccard", metric="jaccard",
               entry="engine_run_copy")
    with open(os.path.join(bench, "configs", "emp_matrix_jaccard.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "p255.json"), "w") as f:
        json.dump({"n_perms": 255}, f)
    with open(os.path.join(bench, "entries", "engine_run.py")) as f:
        src = f.read()
    with open(os.path.join(bench, "entries", "engine_run_copy.py"), "w") as f:
        f.write(src)
    with open(os.path.join(bench, "metrics", "tests.count.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.trace.tests)\n")

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "emp_matrix_jaccard", "source": "x",
                         "file": "bench/configs/emp_matrix_jaccard.json",
                         "reduced": [], "why": "a later cell"})
    m["workloads"].append({"name": "emp_matrix_jaccard.p255",
                           "config": "emp_matrix_jaccard",
                           "traffic": "p255", "chips": 1,
                           "why": "a later cell"})
    m["per_layer"].append({"name": "tests.count", "unit": "count",
                           "better": "higher", "source": "device_trace",
                           "layer": "whole test", "moves": "test_s",
                           "workloads": ["emp_matrix_jaccard.p255"]})
    with open(path, "w") as f:
        json.dump(m, f)

    cell = manifest.load_cell("emp_matrix_jaccard.p255", root)
    assert cell.config["metric"] == "jaccard"
    assert cell.traffic["n_perms"] == 255
    assert cell.entry.__file__.endswith("engine_run_copy.py")
    assert [x["name"] for x, _ in cell.per_layer][-1] == "tests.count"
    # the old cells read exactly what they read before
    old = manifest.load_cell("emp_matrix.p3999", root)
    assert "tests.count" not in [x["name"] for x, _ in old.per_layer]

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run.main(["--workload", "emp_matrix_jaccard.p255", "--seed",
                         "5", "--seconds", "0.1", "--trace", "0"],
                        root=root, require_chip=False) == 0
    assert last_json(buf.getvalue())["correct"] is True

    after = _digests(root)
    assert {p: h for p, h in after.items() if p in before} == before


def test_metrics_reach_the_cells_their_manifest_entries_name(tmp_path):
    """A per-layer metric with no `workloads` key is read in every cell
    that reports the end-to-end metric it moves, later cells too; an
    end-to-end metric with a `workloads` key only in the cells listed."""
    root = tiny_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["end_to_end"].append({"name": "later_rate", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["emp_features.jaccard"]})
    m["per_layer"].append({"name": "later.layer", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "later", "moves": "later_rate"})
    with open(path, "w") as f:
        json.dump(m, f)
    with open(os.path.join(root, "bench", "metrics", "later.layer.py"),
              "w") as f:
        f.write("def read(ctx):\n    return None\n")
    general = {x["name"] for x in m["per_layer"]
               if "workloads" not in x and x["moves"] == "test_s"}
    assert general
    for name in [w["name"] for w in m["workloads"]]:
        cell = manifest.load_cell(name, root)
        e2e = [x["name"] for x in cell.end_to_end]
        layers = {x["name"] for x, _ in cell.per_layer}
        assert general <= layers
        later = name == "emp_features.jaccard"
        assert ("later_rate" in e2e) is later
        assert ("later.layer" in layers) is later
