"""The four-chip matrix cell (emp_matrix_65k.rows4) on 4 virtual CPU
devices: D born row-sharded by the benchmark's own builder equals the
one-place builder bit for bit, the cell comes out correct at a tiny size
through the harness, a broken program underneath it does not, each call
of the program runs in the entry's host span, and the cell's readers
reduce a trace with ops on 4 devices."""

import json
import os

import pytest

from benchtools import REPO, TINY, tiny_root
from conftest import run_subprocess

from bench import devtrace, manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "emp_matrix_65k.rows4"
CALL = manifest.load_module(os.path.join(REPO, "bench", "entries",
                                         "distributed.py")).CALL

CODE = r"""
import io, json, sys
from contextlib import redirect_stdout
sys.path[:0] = [%(repo)r, %(here)r]
import jax
import numpy as np
from benchtools import last_json, tiny_root
from test_bench_run import FAULTS
from bench import data, devtrace, run, sharded
from repro import obs
import repro.core.distributed as dist

assert len(jax.devices()) == 4
out = {"builder": {}, "faults": {}}
for n in (96, 203):
    x, _ = data.counts(jax.random.key(n), n=n, d=32, n_groups=8,
                       density=0.3, scale=10.0, effect=1.0)
    mesh = sharded.mesh(4)
    dm = sharded.distances(x, mesh=mesh, metric="braycurtis")
    want = np.asarray(data.distances(x, metric="braycurtis"))
    got = np.asarray(dm)
    rows = {s.data.shape[0] for s in dm.addressable_shards}
    out["builder"][n] = {
        "shape": list(dm.shape), "rows_per_device": sorted(rows),
        "devices": len({s.device for s in dm.addressable_shards}),
        "spec": str(dm.sharding.spec),
        "bits": bool(np.array_equal(got[:n].view(np.uint32),
                                    want.view(np.uint32))),
        "pad_zero": bool(np.all(got[n:] == 0))}

root = tiny_root(%(tmp)r)
run.OUT_DIR = %(tmp)r + "/out"
SEED = 2**33 + 7

def main(seconds, trace=0):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", %(cell)r, "--seed", str(SEED),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_chip=False)
    assert rc == 0
    return last_json(buf.getvalue())

out["correct"] = main(0.2)
real = dist.permanova_distributed
for name, fault in sorted(FAULTS.items()):
    dist.permanova_distributed = fault(real)
    try:
        out["faults"][name] = main(0.3)
    finally:
        dist.permanova_distributed = real

def no_spans(*args, **kw):          # the same program, opening no spans
    obs.enable(trace=False, metrics=True)
    try:
        return real(*args, **kw)
    finally:
        obs.enable(trace=True, metrics=True)

out["spans"] = {}
for tag, program in (("program", real), ("no_spans", no_spans)):
    dist.permanova_distributed = program
    try:
        main(0.2, trace=1)
    finally:
        dist.permanova_distributed = real
    tr = devtrace.load(run.OUT_DIR + "/" + %(cell)r + "." + str(SEED))
    out["spans"][tag] = tr.spans
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("four"))
    text = run_subprocess(CODE % {"repo": REPO, "here": HERE, "tmp": tmp,
                                  "cell": CELL}, devices=4, timeout=600)
    return json.loads(text.strip().splitlines()[-1])


@pytest.mark.parametrize("n", [96, 203])
def test_row_sharded_builder_is_the_one_place_builder_bit_for_bit(
        four_devices, n):
    b = four_devices["builder"][str(n)]
    rows = -(-n // 4)
    assert b["shape"] == [4 * rows, n]
    assert b["rows_per_device"] == [rows] and b["devices"] == 4
    assert b["spec"].startswith("PartitionSpec('model'")
    assert b["bits"] and b["pad_zero"]


def test_tiny_cell_is_correct_on_four_devices(four_devices):
    res = four_devices["correct"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"test_s", "setup_s"}
    assert res["device"]["count"] == 4
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("fault", ["altered_f", "altered_p", "half", "nan",
                                   "stale"])
def test_broken_program_is_not_correct(four_devices, fault):
    res = four_devices["faults"][fault]
    assert res["correct"] is False and res["failed"] >= 1


def _inside(inner, outer):
    return all(any(o[1] <= s[1] and s[2] <= o[2] for o in outer)
               for s in inner)


@pytest.mark.parametrize("program", ["program", "no_spans"])
def test_each_call_of_the_program_runs_in_the_entry_span(four_devices,
                                                         program):
    spans = [tuple(s) for s in four_devices["spans"][program]]

    def named(*names):
        return [s for s in spans if s[0] in names]

    tests, calls = named(devtrace.TEST), named(CALL)
    assert len(calls) == len(tests) >= 1
    assert _inside(calls, tests)
    phases = named("engine.dist.sw", "engine.dist.finalize")
    assert len(phases) == (2 * len(calls) if program == "program" else 0)
    assert _inside(phases, calls)


def test_host_idle_reads_a_program_without_spans_of_its_own():
    # two tests of 500 ns, the program's call [20, 480] in each and its
    # ops [100, 400]: the gaps [0, 100] and [900, 1000] fall in a call,
    # [400, 600] (midpoint 500) in the second bench.test alone
    ops = {0: [(OTHER, t0 + 100, t0 + 400) for t0 in (0, 500)]}
    tests = [(devtrace.TEST, t0, t0 + 500) for t0 in (0, 500)]
    calls = [(CALL, t0 + 20, t0 + 480) for t0 in (0, 500)]
    window = [(devtrace.WINDOW, 0, 1000)]
    host = _reader("host.idle_ms")
    tr = devtrace.Trace(ops=ops, spans=window + tests + calls)
    assert host.read(_ctx(tr)) == pytest.approx(200e-9 / 2 * 1e3)
    assert host.read(_ctx(devtrace.Trace(ops=ops,
                                         spans=window + tests))) is None


def test_cell_reads_its_metrics(tmp_path):
    cell = manifest.load_cell(CELL, tiny_root(tmp_path))
    assert cell.chips == 4 and cell.config["n"] == TINY["n"]
    assert cell.entry.__file__.endswith(os.path.join("entries",
                                                     "distributed.py"))
    layers = {m["name"] for m, _ in cell.per_layer}
    assert {"collective.ms", "sw_rows_roofline", "host.idle_ms",
            "device.idle", "device.peak_gb",
            "compile.window_count"} <= layers
    assert "sw_roofline" not in layers
    other = manifest.load_cell("emp_matrix.p3999", tiny_root(tmp_path / "b"))
    assert not {"collective.ms", "sw_rows_roofline"} & {
        m["name"] for m, _ in other.per_layer}


# -- the readers on a trace with ops on 4 devices --------------------------

def _reader(name):
    return manifest.load_module(os.path.join(REPO, "bench", "metrics",
                                             name + ".py"))


KERNEL = ("%sw_matmul_rows_partial.1 = f32[1,1,1536]{2,1,0} "
          "custom-call(f32[16384,65536]{1,0} %p0), "
          'custom_call_target="tpu_custom_call"')
PSUM_START = ("%all-reduce-start = f32[1000]{0} all-reduce-start("
              "f32[1000]{0} %x), channel_id=1, replica_groups={{0,1,2,3}}")
PSUM_DONE = ("%all-reduce-done = f32[1000]{0} all-reduce-done("
             "f32[1000]{0} %all-reduce-start)")
SUM_T = ("%all-reduce.2 = f32[] all-reduce(f32[] %r), channel_id=2, "
         "replica_groups={{0,1,2,3}}, to_apply=%add")
OTHER = "%fusion.3 = s32[1000,65536]{1,0} fusion(s32[65536]{0} %g)"


def _four_chip_trace():
    """Two tests in a 1000 ns window; chip d's kernel takes 300 + 10 d ns
    a test, so chip 3 is the slowest; device 0 waits in the psum."""
    ops = {}
    for d in range(4):
        k = 300 + 10 * d
        ops[d] = []
        for t0 in (0, 500):
            ops[d] += [(OTHER, t0 + 10, t0 + 100),
                       (KERNEL, t0 + 100, t0 + 100 + k)]
            if d == 0:
                ops[d] += [(PSUM_START, t0 + 400, t0 + 431),
                           (PSUM_DONE, t0 + 420, t0 + 440),
                           (SUM_T, t0 + 450, t0 + 455)]
    spans = [("bench.window", 0, 1000), ("bench.test", 0, 500),
             ("bench.test", 500, 1000)]
    return devtrace.Trace(ops=ops, spans=spans)


def _ctx(trace, chips=4, n=65536, n_perms=999):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return run.Context(config={"n": n}, traffic={"n_perms": n_perms},
                       peaks=peaks, chips=chips, trace=trace, compiles=0,
                       peak_bytes=0)


def test_collective_ms_on_four_devices():
    tr = _four_chip_trace()
    # per test on device 0: start and done overlap in [400, 440], 40 ns,
    # and the s_T sum 5 ns
    assert _reader("collective.ms").read(_ctx(tr)) == pytest.approx(45e-6)
    one_chip = devtrace.Trace(
        ops={0: [op for op in tr.ops[0] if op[0] in (OTHER, KERNEL)]},
        spans=tr.spans)
    assert _reader("collective.ms").read(_ctx(one_chip, chips=1)) is None


def test_sw_rows_roofline_on_four_devices():
    tr = _four_chip_trace()
    reader = _reader("sw_rows_roofline")
    n, p = 512, 99
    ops, nbytes = reader.work(n, p, 4)
    assert ops == n * (n - 1) * (p + 1) / 4 and nbytes == n * n
    least = max(ops / 197e12, nbytes / 819e9)
    # the slowest chip's kernel, 330 ns a test, not device 0's 300
    got = reader.read(_ctx(tr, n=n, n_perms=p))
    assert got == pytest.approx(100.0 * least / 330e-9)
    assert got < 100.0
    no_kernel = devtrace.Trace(
        ops={d: [op for op in v if op[0] != KERNEL]
             for d, v in tr.ops.items()}, spans=tr.spans)
    assert reader.read(_ctx(no_kernel, n=n, n_perms=p)) is None
