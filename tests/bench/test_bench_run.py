"""The harness end to end at a tiny size on the CPU: the last line's
keys, and `correct` coming out false when the timed path is broken
underneath it."""

import dataclasses
import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchtools import TINY_PERMS, last_json, tiny_root, workloads

import repro.engine
import repro.pipeline
from bench import run

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _run(root, cell, seed=2**33 + 5, seconds=0.2):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", "0"], root=root,
                      require_chip=False)
    assert rc == 0
    return last_json(buf.getvalue())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", workloads())
def test_last_line_keys_and_correct(root, cell):
    res = _run(root, cell)
    assert set(res) == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"test_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_no_chip_no_result(root, capsys):
    rc = run.main(["--workload", workloads()[0], "--seed", "1", "--seconds",
                   "1", "--trace", "0"], root=root)
    assert rc != 0
    assert capsys.readouterr().out == ""


# -- faults planted in the program, underneath the harness ----------------

def _stale(real):
    """A test that hands back the state it already had: every call
    returns the first call's result."""
    first = []

    def fake(*a, **kw):
        if not first:
            first.append(real(*a, **kw))
        return first[0]
    return fake


def _half(real):
    """Half of the permutations left out, their F taken as the mean of
    the rest."""
    def fake(*a, **kw):
        res = real(*a, **kw)
        f = np.asarray(res.f_perms).copy()
        h = f.shape[0] // 2
        f[h:] = f[:h].mean()
        return dataclasses.replace(res, f_perms=f)
    return fake


def _altered_f(real):
    """The observed F altered where it is produced."""
    def fake(*a, **kw):
        res = real(*a, **kw)
        f = np.asarray(res.f_perms).copy()
        f[0] *= 1.001
        return dataclasses.replace(res, f_perms=f, f_stat=f[0])
    return fake


def _altered_p(real):
    """The p-value altered where it is produced."""
    def fake(*a, **kw):
        res = real(*a, **kw)
        return dataclasses.replace(
            res, p_value=float(res.p_value) + 1.0 / (TINY_PERMS + 1))
    return fake


def _nan(real):
    """Every answer NaN: the comparison fails it and still prints."""
    def fake(*a, **kw):
        res = real(*a, **kw)
        f = np.full(np.shape(res.f_perms), np.nan)
        return dataclasses.replace(res, f_perms=f, p_value=np.nan,
                                   s_t=np.nan)
    return fake


FAULTS = {"stale": _stale, "half": _half, "altered_f": _altered_f,
          "altered_p": _altered_p, "nan": _nan}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["emp_matrix.p3999",
                                  "emp_features.braycurtis"])
def test_broken_program_is_not_correct(root, cell, fault, monkeypatch):
    if cell.startswith("emp_matrix"):
        monkeypatch.setattr(repro.engine, "run",
                            FAULTS[fault](repro.engine.run))
    else:
        monkeypatch.setattr(repro.pipeline, "pipeline",
                            FAULTS[fault](repro.pipeline.pipeline))
    res = _run(root, cell, seconds=0.3)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert json.dumps(res)         # still one printable line
