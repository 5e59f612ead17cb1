"""The reduction from a device trace to per-layer metrics: on a hand-made
trace against values worked out by hand, and on a small trace recorded on
a TPU v5e (emp_features.braycurtis, one test)."""

import json
import os

import pytest

from benchtools import REPO

from bench import devtrace, manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return manifest.load_module(os.path.join(REPO, "bench", "metrics",
                                             name + ".py"))


def _ctx(trace, config, traffic, chips=1, compiles=0, peak=0):
    return run.Context(config=config, traffic=traffic, peaks=PEAKS,
                       chips=chips, trace=trace, compiles=compiles,
                       peak_bytes=peak)


@pytest.fixture
def hand():
    ops = {0: [("%k.1 = f32[8] custom-call(f32[8] %a)", 10, 30),
               ("%fusion = s32[4] fusion(s32[4] %b)", 20, 40),
               ("%k.1 = f32[8] custom-call(f32[8] %a)", 50, 60),
               ("%sort = s32[4] sort(s32[4] %c)", 95, 110),
               ("%late", 120, 130)]}
    spans = [("bench.window", 0, 100), ("bench.test", 0, 100),
             ("engine.sw_chunk", 55, 90)]
    return devtrace.Trace(ops=ops, spans=spans)


def test_union_busy_and_idle(hand):
    # [10, 40] + [50, 60] + [95, 100] inside the window of 100 ns
    assert devtrace.busy_s(hand, [0]) == pytest.approx(45e-9)
    assert _reader("device.idle").read(_ctx(hand, {}, {})) == \
        pytest.approx(55.0)


def test_kernel_grouping_and_names(hand):
    assert devtrace.kernel_s(hand, r"^%k\b") == pytest.approx(30e-9)
    assert devtrace.top_ops(hand)[0] == ["%k.1 custom-call",
                                         pytest.approx(30e-9)]
    assert devtrace.short("%late") == "%late"


def test_idle_gaps_are_named_by_the_innermost_span(hand):
    gaps = devtrace.idle_gaps(hand)
    assert gaps[0] == ["engine.sw_chunk", pytest.approx(35e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx([10e-9, 10e-9,
                                                        35e-9])


def test_sw_roofline_arithmetic():
    # n=1000, P=999: 1000*999*1000 ops -> 5.0711e-6 s at 197 TFLOP/s,
    # 4e6 bytes -> 4.884e-6 s at 819 GB/s; ops bind. 1 ms of kernel.
    tr = devtrace.Trace(ops={0: [("%permanova_sw.1 custom-call", 0, 1e6)]},
                        spans=[("bench.window", 0, 1e9),
                               ("bench.test", 0, 1e9)])
    v = _reader("sw_roofline").read(_ctx(tr, {"n": 1000}, {"n_perms": 999}))
    assert v == pytest.approx(100 * 9.99e8 / 197e12 / 1e-3)
    # bytes bind when the kernel's work is small: n=1000, P=0
    v = _reader("sw_roofline").read(_ctx(tr, {"n": 1000}, {"n_perms": 0}))
    assert v == pytest.approx(100 * 4e6 / 819e9 / 1e-3)


def test_fusedk_roofline_arithmetic():
    # n=1000, d=100, P=999, Bray-Curtis: 3*100*499500 + 2*499500*1000 ops
    tr = devtrace.Trace(ops={0: [("%fused_sw_rows.1 custom-call", 0, 2e6),
                                 ("%fusion fusion", 2e6, 3e6)]},
                        spans=[("bench.window", 0, 1e9),
                               ("bench.test", 0, 1e9)])
    cfg, traffic = {"n": 1000, "d": 100}, {"n_perms": 999,
                                           "metric": "braycurtis"}
    v = _reader("fusedk_roofline").read(_ctx(tr, cfg, traffic))
    assert v == pytest.approx(100 * 1.14885e9 / 197e12 / 2e-3)


def test_a_reader_with_nothing_to_read_returns_nothing():
    tr = devtrace.Trace(ops={0: [("%other custom-call", 0, 5)]},
                        spans=[("bench.window", 0, 10)])
    ctx = _ctx(tr, {"n": 10, "d": 4}, {"n_perms": 9, "metric": "jaccard"})
    assert _reader("sw_roofline").read(ctx) is None
    assert _reader("fusedk_roofline").read(ctx) is None
    assert _reader("device.idle").read(ctx) == pytest.approx(50.0)


def test_recorded_v5e_trace():
    with open(os.path.join(HERE, "trace_braycurtis.json")) as f:
        tr = devtrace.Trace.from_json(json.load(f))
    assert tr.tests == 1
    assert tr.window_s == pytest.approx(10.894916954)
    busy = devtrace.busy_s(tr, [0])
    k = devtrace.kernel_s(tr, _reader("fusedk_roofline").KERNELS)
    assert busy == pytest.approx(10.856698731)
    assert k == pytest.approx(10.610796717)
    assert k < busy < tr.window_s
    names = {n for n, _ in devtrace.top_ops(tr, k=100)}
    assert sum(n.startswith("%fused_sw_rows") for n in names) == 1
    assert devtrace.kernel_s(tr, _reader("sw_roofline").KERNELS) == 0
    ctx = _ctx(tr, {"n": 25145, "d": 1024},
               {"n_perms": 999, "metric": "braycurtis"})
    roof = _reader("fusedk_roofline").read(ctx)
    # the work over the whole window bounds the kernel's share from below
    ops, _ = _reader("fusedk_roofline").work(25145, 1024, 999, "braycurtis")
    assert 100 * ops / 197e12 / tr.window_s < roof < 100
    assert _reader("device.idle").read(ctx) == pytest.approx(
        100 * (1 - 10.856698731 / 10.894916954))
    assert devtrace.idle_gaps(tr)[0][0] == "fusedk.chunk"
