"""BENCHMARK.json keeps to the benchmark's contract, and every piece it
names is a file of its own that the harness finds by name."""

import json
import os
import re

from benchtools import REPO

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    M = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(M) == KEYS
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
    cmd = M["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    for w in cmd[1:]:
        if os.path.exists(os.path.join(REPO, w)):
            assert any(w == p or w.startswith(p + "/") for p in M["paths"])


def test_run_seconds_fits_the_check_with_24_cells():
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    total = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_entry_keys():
    seen = set()
    for kind, keys in (("configs", {"name", "source", "file", "reduced",
                                    "why"}),
                       ("workloads", {"name", "config", "traffic", "chips",
                                      "why"}),
                       ("end_to_end", {"name", "unit", "better", "bound",
                                       "source"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves"})):
        items = M[kind]
        assert 1 <= len(items)
        for it in items:
            extra = set(it) - keys - ({"workloads"} if kind in (
                "end_to_end", "per_layer") else set())
            assert not extra and keys <= set(it), (kind, it)
            assert NAME.match(it["name"]), it["name"]
            assert (kind, it["name"]) not in seen
            seen.add((kind, it["name"]))
            if "unit" in it:
                assert UNIT.match(it["unit"]) and it["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                if k in it and kind != "end_to_end":
                    assert _line(it[k]), (k, it)


def test_configs_files_and_reduced():
    used = {w["config"] for w in M["workloads"]}
    files = set()
    for c in M["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and k in cfg
        assert os.path.exists(os.path.join(REPO, "bench", "entries",
                                           cfg["entry"] + ".py"))


def test_workloads_find_their_files_and_chips():
    pairs = set()
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(REPO, "bench", "traffic",
                                           w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


def test_end_to_end_metrics_and_bounds():
    names = {m["name"] for m in M["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.25
    assert next(m for m in M["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25


def test_per_layer_metrics_have_readers_and_cells():
    cells = {w["name"] for w in M["workloads"]}
    e2e = {m["name"] for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py"))
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])


def test_peaks_table_names_its_source():
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
