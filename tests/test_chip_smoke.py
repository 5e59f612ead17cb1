"""chip_smoke.py on the CPU: its phases run at tiny sizes (Pallas kernels
interpreted) and pass their own checks; its checks raise on a mismatch;
and without a TPU it reports no success and exits non-zero."""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO, SRC, run_subprocess


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


def test_paper_cell_phase_tiny(smoke):
    out = smoke.phase_paper_cell(n=300, n_perms=39, n_groups=3,
                                 n_features=16, impl="pallas_matmul",
                                 ref_perms=3)
    assert out["plan"].startswith("pallas_matmul")
    assert "interpreted" in out["plan"]        # CPU backend
    assert out["f"].shape == (40,) and np.all(np.isfinite(out["f"]))


def test_features_phase_tiny(smoke, tmp_path):
    out = smoke.phase_features(n=150, d=40, n_perms=29,
                               metrics=("braycurtis", "jaccard"),
                               workdir=str(tmp_path / "slabs"))
    assert set(out) == {"braycurtis", "jaccard"}
    assert not (tmp_path / "slabs").exists()   # set-up data cleaned up


def test_serving_phase_tiny(smoke):
    results = smoke.phase_serving(sizes=(20, 45), n_perms=49)
    assert [r.status for r in results] == ["ok", "ok"]


def test_four_chip_phase_on_virtual_devices():
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke
for impl in ("auto", "pallas_matmul"):   # CPU plan; the TPU plan's kernel
    out = chip_smoke.phase_four_chips(n=1001, n_perms=49, n_groups=4,
                                      n_features=16, impl=impl)
    print("FOUR-OK", impl, out["f"].shape)
"""
    out = run_subprocess(code, devices=4)
    assert "FOUR-OK auto (50,)" in out
    assert "FOUR-OK pallas_matmul (50,)" in out


def test_checks_raise_on_mismatch(smoke):
    with pytest.raises(AssertionError):
        smoke.check("x", 2e-4, 1e-4)
    with pytest.raises(AssertionError):
        smoke.check("x", float("nan"), 1e-4)
    f = np.array([5.0, 1.0, 2.0, 6.0])
    with pytest.raises(AssertionError):       # observed F ranks differ
        smoke.check_p("p", f, np.array([5.0, 6.0, 7.0, 1.0]), 100, 3, 1e-4)


def test_main_refuses_without_tpu(smoke, capsys):
    rc = smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
