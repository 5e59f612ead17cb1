"""The per-layer metrics that read the program's own names: the label
layer's device scope (`labels.device_ms`) and the host phases' spans
(`host.idle_ms`). Their reductions on hand-made intervals and on a small
trace recorded on a TPU v5e, the cells that report them, and the host
spans reaching the profiler's trace on the clock of the device ops."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtools import REPO, tiny_root, workloads

from bench import devscope, devtrace, manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
LABELS = "labels.device_ms"
HOST = "host.idle_ms"


def _reader(name):
    return manifest.load_module(os.path.join(REPO, "bench", "metrics",
                                             name + ".py"))


def _ctx(trace):
    return run.Context(config={}, traffic={}, peaks=None, chips=1,
                       trace=trace, compiles=0, peak_bytes=0)


def _labels(ops, trace, monkeypatch):
    monkeypatch.setattr(devscope, "window_ops", lambda device=0: ops)
    return _reader(LABELS).read(_ctx(trace))


# -- on hand-made intervals ----------------------------------------------

SCOPED = [("%fusion fusion", 10, 30, "jit(_step)/engine.labels/vmap()/gather"),
          ("%sort sort", 30, 35, "jit(_step)/engine.labels/sort"),
          ("%k.1 custom-call", 40, 90, "jit(_step)/jit(permanova_sw)/k"),
          ("%fusion fusion", 95, 120, "jit(_step)/engine.labels/gather")]


@pytest.fixture
def hand():
    ops = {0: [(n, s, e) for n, s, e, _ in SCOPED]}
    spans = [("bench.window", 0, 100), ("bench.test", 0, 100),
             ("engine.plan", 0, 8),
             ("engine.sw", 8, 93), ("engine.fetch", 35, 40),
             ("engine.finalize", 93, 100)]
    return devtrace.Trace(ops=ops, spans=spans)


def test_scoped_ms_sums_the_scope_inside_the_window():
    # 20 + 5 + 5 (the last op clipped at the window's end) ns over 2 tests
    ms = devscope.scoped_ms(SCOPED, (0, 100), 2, "engine.labels")
    assert ms == pytest.approx(30e-6 / 2)
    assert devscope.scoped_ms(SCOPED, (0, 100), 2, "engine.other") is None
    assert devscope.scoped_ms(SCOPED, (200, 300), 1, "engine.labels") is None


def test_labels_reader(hand, monkeypatch):
    assert _labels(SCOPED, hand, monkeypatch) == pytest.approx(30e-6)


def test_host_idle_counts_the_program_gaps_only(hand):
    # gaps: [0, 10] engine.plan, [35, 40] engine.fetch, [90, 95]
    # engine.sw; [95, 100] is busy. bench.* and "no span" gaps do not count
    gaps = devtrace.idle_gaps(hand, k=None)
    assert sorted(g[0] for g in gaps) == ["engine.fetch", "engine.plan",
                                          "engine.sw"]
    assert _reader(HOST).read(_ctx(hand)) == pytest.approx(20e-6)
    tr = devtrace.Trace(ops=hand.ops, spans=hand.spans + [
        ("bench.entry", 3, 7)])        # a shorter bench span names [0, 10]
    assert _reader(HOST).read(_ctx(tr)) == pytest.approx(10e-6)


def test_readers_with_nothing_to_read_return_nothing(hand, monkeypatch):
    unscoped = [(n, s, e, "jit(_step)/vmap()/gather") for n, s, e, _ in
                SCOPED]
    assert _labels(unscoped, hand, monkeypatch) is None
    assert _labels([], hand, monkeypatch) is None
    bench_only = devtrace.Trace(ops=hand.ops, spans=[
        s for s in hand.spans if s[0].startswith("bench.")])
    assert _reader(HOST).read(_ctx(bench_only)) is None
    idle = devtrace.Trace(ops={}, spans=hand.spans)
    assert _reader(HOST).read(_ctx(idle)) is None


def test_window_ops_of_a_run_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    assert devscope.window_ops() == []
    assert devscope.newest(str(tmp_path)) is None


# -- the xplane reading ----------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, v):
    return _varint(field << 3) + _varint(v)


def _msg(field, body):
    body = body.encode() if isinstance(body, str) else body
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _entry(field, key, value):                 # a protobuf map entry
    return _msg(field, _int(1, key) + _msg(2, value))


def _xspace():
    """One TPU plane, as tsl's xplane.proto lays it out: the scope of the
    first op on its event metadata, of the second interned (a ref to a
    stat metadata entry named by the string), of the third on the event
    itself; a fourth op without one, and a line that is not the ops."""
    stat_md = b"".join(_entry(5, i, _int(1, i) + _msg(2, name)) for i, name in
                       ((1, "tf_op"), (2, "jit(_step)/engine.labels/sort"),
                        (3, "run_id")))
    ev_md = b"".join(_entry(4, i, _int(1, i) + _msg(2, name) + body)
                     for i, name, body in (
        (10, "%fusion = s32[4] fusion()",
         _msg(5, _int(1, 1) + _msg(5, "jit(_step)/engine.labels/gather"))),
        (11, "%sort = s32[4] sort()", _msg(5, _int(1, 1) + _int(7, 2))),
        (12, "%select = s32[4] select()", b""),
        (13, "%permanova_sw.1 = f32[8] custom-call()", b"")))
    events = [(10, 1000, 20000, b""), (11, 21000, 5000, b""),
              (12, 30000, 1000, _msg(4, _int(1, 1) + _msg(
                  5, "jit(_step)/engine.labels/select_n"))),
              (13, 40000, 50000, _msg(4, _int(1, 3) + _int(4, 7)))]
    line = _int(1, 1) + _msg(2, "XLA Ops") + _int(3, 100) + b"".join(
        _msg(4, _int(1, m) + _int(2, off) + _int(3, dur) + stats)
        for m, off, dur, stats in events)
    other = _int(1, 2) + _msg(2, "Steps") + _int(3, 100) + _msg(
        4, _int(1, 10) + _int(2, 0) + _int(3, 99000))
    plane = (_int(1, 1) + _msg(2, "/device:TPU:0") + _msg(3, line)
             + _msg(3, other) + ev_md + stat_md)
    return _msg(1, _int(1, 7) + _msg(2, "/host:CPU")) + _msg(1, plane)


def test_load_reads_the_scope_wherever_the_stat_is(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    ops = devscope.load(str(path))
    assert [(n.split()[0], s, e, sc) for n, s, e, sc in ops] == [
        ("%fusion", 101.0, 121.0, "jit(_step)/engine.labels/gather"),
        ("%sort", 121.0, 126.0, "jit(_step)/engine.labels/sort"),
        ("%select", 130.0, 131.0, "jit(_step)/engine.labels/select_n"),
        ("%permanova_sw.1", 140.0, 190.0, "")]
    assert devscope.scoped_ms(ops, (100, 200), 1,
                              "engine.labels") == pytest.approx(26e-6)
    assert devscope.load(str(path), device=1) == []
    # names and times as jax's own reader gives them
    pd = jax.profiler.ProfileData.from_serialized_xspace(_xspace())
    ref = [(e.name, e.start_ns, e.end_ns) for p in pd.planes
           if p.name == "/device:TPU:0" for ln in p.lines
           if ln.name == "XLA Ops" for e in ln.events]
    assert ref == [op[:3] for op in ops]


# -- on a trace recorded on a TPU v5e ---------------------------------------

def _recorded():
    """emp_matrix.p3999 on a TPU v5e, seed 4300000001, the 2 tests of a
    10 s window: every op of device 0 with its op_name cut to two path
    components, and every host span, in ns from the window's start."""
    with open(os.path.join(HERE, "trace_matrix_spans.json")) as f:
        obj = json.load(f)
    ops = [tuple(o) for o in obj["ops"]["0"]]
    trace = devtrace.Trace(ops={0: [o[:3] for o in ops]},
                           spans=[tuple(s) for s in obj["spans"]])
    return ops, trace


def test_recorded_v5e_trace(monkeypatch):
    ops, tr = _recorded()
    assert tr.tests == 2
    assert tr.window_s == pytest.approx(14.081302325)
    labels = _labels(ops, tr, monkeypatch)
    assert labels == pytest.approx(1280.577391)
    # the gather and the two sorts, as the breakdown names them, are
    # nearly all of the label layer
    named = 1e3 / tr.tests * sum(
        v for n, v in devtrace.top_ops(tr, k=100)
        if re.match(r"^%(fusion|sort(\.\d+)?) ", n))
    assert named == pytest.approx(1271.389521)
    assert labels == pytest.approx(named, rel=0.05)

    host = _reader(HOST).read(_ctx(tr))
    assert host == pytest.approx(9.778839)
    idle = _reader("device.idle").read(_ctx(tr))
    assert 0 < host <= idle / 100 * tr.window_s * 1e3 / tr.tests
    gaps = devtrace.idle_gaps(tr, k=None)
    assert gaps[0][0] == "engine.fetch"
    # every gap inside a chunk falls in its dispatch or its fetch
    assert "engine.sw_chunk" not in {name for name, _ in gaps}


# -- which cells report them ---------------------------------------------

def test_labels_in_the_matrix_cell_host_idle_in_every_test_s_cell(
        tmp_path):
    root = tiny_root(tmp_path)
    for name in workloads():
        cell = manifest.load_cell(name, root)
        layers = [m["name"] for m, _ in cell.per_layer]
        assert (LABELS in layers) is (name == "emp_matrix.p3999")
        assert (HOST in layers) is ("test_s" in [
            m["name"] for m in cell.end_to_end])
        assert HOST in layers


# -- the program's host spans on the profiler's clock ----------------------

def test_engine_spans_reach_the_profiler_host_plane(tmp_path):
    from repro import engine, obs
    rng = np.random.default_rng(0)
    x = rng.random((64, 8))
    dm = jnp.asarray(np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1)),
                     jnp.float32)
    g = jnp.asarray(np.arange(64) % 3, jnp.int32)
    prev = (obs.trace_enabled(), obs.metrics_enabled())
    obs.enable(trace=True, metrics=False)
    try:
        engine.run(dm, g, n_perms=99, chunk=40).p_value.block_until_ready()
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                engine.run(dm, g, n_perms=99,
                           chunk=40).p_value.block_until_ready()
    finally:
        obs.enable(trace=prev[0], metrics=prev[1])
        obs.clear()
    tr = devtrace.load(str(tmp_path))
    lo, hi = tr.window
    spans = sorted((s for s in tr.spans if s[0] != devtrace.WINDOW),
                   key=lambda s: s[1])
    assert all(lo <= s <= e <= hi for _, s, e in spans)
    top = [s for s in spans if s[0] in ("engine.plan", "engine.sw",
                                        "engine.finalize")]
    assert [s[0] for s in top] == ["engine.plan", "engine.sw",
                                   "engine.finalize"]
    sw = top[1]
    chunks = [s for s in spans if s[0] == "engine.sw_chunk"]
    assert len(chunks) == 3
    for c in chunks:
        assert sw[1] <= c[1] <= c[2] <= sw[2]
        inner = [s[0] for s in spans if c[1] <= s[1] and s[2] <= c[2]
                 and s is not c]
        assert inner == ["engine.dispatch", "engine.fetch"]
