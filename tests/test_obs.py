"""Observability subsystem: span nesting + Chrome export, counter
byte-accuracy against the streaming bridge, the retrace counter (catches
shape-polymorphic re-jits; warm calls report zero), allocation-free
disabled mode, predicted-vs-measured report content, psum-free snapshot
merging, and the autotune cache counters + warn-once."""

import json
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs, pipeline
from repro.engine import planner as eplanner
from repro.obs import jaxhooks

N, D, G = 53, 24, 4

# the host phases of engine.run, as its span sites name them
ENGINE_SPANS = ("engine.plan", "engine.sw", "engine.sw_chunk",
                "engine.dispatch", "engine.fetch", "engine.finalize")


def _matrix_study(n=64, g=3):
    rng = np.random.default_rng(1)
    x = rng.random((n, 8))
    dm = np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1)).astype(np.float32)
    return jnp.asarray(dm), jnp.asarray(np.arange(n) % g, jnp.int32)


def _study(seed=0, n=N, d=D, g=G):
    rng = np.random.default_rng(seed)
    x = rng.gamma(1.0, 1.0, size=(n, d)).astype(np.float32)
    x[:, 0] = np.maximum(x[:, 0], 1e-3)
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[:g] = np.arange(g)
    return x, grouping


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with telemetry off and buffers empty."""
    obs.disable()
    obs.clear()
    obs.metrics.reset()
    yield
    obs.disable()
    obs.clear()
    obs.metrics.reset()


class TestSpans:
    def test_nesting_depth_and_parent(self):
        obs.enable(trace=True, metrics=False)
        with obs.span("outer"):
            with obs.span("inner", {"k": 1}):
                pass
        evs = {e["name"]: e for e in obs.events()}
        assert evs["outer"]["args"]["depth"] == 0
        assert "parent" not in evs["outer"]["args"]
        assert evs["inner"]["args"]["depth"] == 1
        assert evs["inner"]["args"]["parent"] == "outer"
        assert evs["inner"]["args"]["k"] == 1
        # inner completes first and nests inside outer's window
        assert evs["inner"]["ts"] >= evs["outer"]["ts"]
        assert (evs["inner"]["ts"] + evs["inner"]["dur"]
                <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-3)

    def test_export_chrome_trace_shape(self, tmp_path):
        obs.enable(trace=True, metrics=False)
        with obs.span("stage1.test", {"predicted_bytes": 64.0}):
            pass
        path = str(tmp_path / "trace.json")
        obs.trace.export(path, extra_metadata={"run": "t"})
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"]["source"] == "repro.obs"
        assert doc["otherData"]["run"] == "t"
        (ev,) = doc["traceEvents"]
        # the golden trace_event fields chrome://tracing requires
        assert ev["ph"] == "X" and ev["cat"] == "repro"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(ev)
        assert ev["args"]["predicted_bytes"] == 64.0

    def test_stage_table_aggregates(self):
        obs.enable(trace=True, metrics=False)
        for _ in range(3):
            with obs.span("s", {"predicted_bytes": 10.0}):
                pass
        row = obs.trace.stage_table()["s"]
        assert row["calls"] == 3
        assert row["predicted_bytes"] == 30.0
        assert row["total_s"] >= 0.0 and row["mean_s"] >= 0.0

    def test_session_restores_prior_state(self, tmp_path):
        assert not obs.enabled()
        path = str(tmp_path / "t.json")
        with obs.session(path):
            assert obs.trace_enabled()
            with obs.span("inside"):
                pass
        assert not obs.enabled()
        assert json.load(open(path))["traceEvents"]


class TestDisabledMode:
    def test_span_is_shared_noop_singleton(self):
        assert obs.span("a") is obs.span("b", {"x": 1})

    def test_no_events_no_counters(self):
        with obs.span("ghost"):
            pass
        obs.metrics.inc("ghost.counter")
        assert obs.events() == []
        assert obs.metrics.value("ghost.counter") == 0.0

    def test_hot_path_allocation_free(self):
        # warm every lazy path, then assert the steady state allocates
        # nothing: this is the per-chunk cost the scheduler loop pays
        for _ in range(4):
            with obs.span("warm"):
                pass
            obs.metrics.inc("warm")
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            for _ in range(100):
                with obs.span("hot", {"lo": 0}):
                    pass
                obs.metrics.inc("hot", 1.0)
                # engine.run's span sites as they are written: no attrs
                with obs.span("engine.plan"):
                    pass
                with obs.span("engine.sw_chunk"):
                    with obs.span("engine.dispatch"):
                        pass
                    with obs.span("engine.fetch"):
                        pass
                with obs.span("engine.finalize"):
                    pass
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        grown = sum(s.size_diff for s in after.compare_to(before, "lineno")
                    if s.size_diff > 0 and any(
                        "obs" in (fr.filename or "")
                        for fr in s.traceback))
        assert grown == 0, f"disabled obs hot path allocated {grown} bytes"

    def test_engine_run_builds_no_span_attrs_while_off(self, monkeypatch):
        """Every span site of engine.run passes no attrs while tracing is
        off: a site costs the one bool check inside span()."""
        calls = []
        real = obs.span

        def spy(name, attrs=None):
            calls.append((name, attrs))
            return real(name, attrs)

        monkeypatch.setattr(obs, "span", spy)
        dm, g = _matrix_study()
        engine.run(dm, g, n_perms=99, chunk=40)     # streaming, 3 chunks
        engine.run(dm, g, n_perms=99)               # one batch
        assert {name for name, _ in calls} == set(ENGINE_SPANS)
        assert all(attrs is None for _, attrs in calls)
        assert obs.events() == []


class TestEngineSpans:
    @pytest.mark.parametrize("chunk,n_chunks", [(40, 3), (None, 1)])
    def test_host_phases_nest(self, chunk, n_chunks):
        obs.enable(trace=True, metrics=False)
        dm, g = _matrix_study()
        engine.run(dm, g, n_perms=99, chunk=chunk)
        ev = sorted(obs.events(), key=lambda e: e["ts"])
        assert {e["name"] for e in ev} == set(ENGINE_SPANS)
        top = [e["name"] for e in ev if e["args"]["depth"] == 0]
        assert top == ["engine.plan", "engine.sw", "engine.finalize"]

        def under(name, parent, depth):
            es = [e for e in ev if e["name"] == name]
            assert len(es) == n_chunks
            assert all(e["args"]["depth"] == depth
                       and e["args"]["parent"] == parent for e in es)
            return es

        chunks = under("engine.sw_chunk", "engine.sw", 1)
        dispatch = under("engine.dispatch", "engine.sw_chunk", 2)
        fetch = under("engine.fetch", "engine.sw_chunk", 2)
        for c, d, f in zip(chunks, dispatch, fetch):
            end = c["ts"] + c["dur"]
            assert c["ts"] <= d["ts"] <= d["ts"] + d["dur"] <= f["ts"]
            assert f["ts"] + f["dur"] <= end + 1e-3

    def test_device_memory_read_by_report_not_by_entries(self,
                                                         monkeypatch):
        calls = []
        monkeypatch.setattr(jaxhooks, "record_device_memory",
                            lambda: calls.append(1))
        obs.enable(trace=True, metrics=True)
        dm, g = _matrix_study()
        engine.run(dm, g, n_perms=99, chunk=40)
        assert calls == []
        obs.report(file=None)
        assert calls == [1]


class TestCounters:
    def test_mat2_bytes_built_exact(self):
        from repro.pipeline.streaming import build_mat2_streaming
        n, d = 96, 16
        x = jnp.asarray(np.random.default_rng(0).random((n, d)), jnp.float32)
        prepare, rows_fn, _ = pipeline.get("braycurtis.blocked").bound(
            block=32)
        obs.enable(trace=True, metrics=True)
        mat2, stats = build_mat2_streaming(prepare(x), rows_fn, block=32)
        assert obs.metrics.value("pipeline.mat2_bytes_built") == 4.0 * n * n
        assert mat2.shape == (n, n)
        # one span per 32-row block
        tbl = obs.trace.stage_table()
        assert tbl["stream.mat2_block"]["calls"] == n // 32

    def test_retrace_counter_catches_shape_polymorphic_rejit(self):
        obs.enable(trace=False, metrics=True)

        @jax.jit
        def f(v):
            return jnp.sum(v * 2.0)

        f(jnp.ones((8,))).block_until_ready()
        before = obs.metrics.value(jaxhooks.RETRACES)
        f(jnp.ones((8,))).block_until_ready()      # warm: same shape
        assert obs.metrics.value(jaxhooks.RETRACES) == before
        f(jnp.ones((9,))).block_until_ready()      # new shape: re-jit
        assert obs.metrics.value(jaxhooks.RETRACES) >= before + 1

    def test_backend_compiles_counted_by_program(self):
        obs.enable(trace=False, metrics=True)

        @jax.jit
        def _compile_probe(v):
            return v * 3.0 + 1.0

        total = obs.metrics.value(jaxhooks.BACKEND_COMPILES)
        name = f"{jaxhooks.BACKEND_COMPILES}.jit(_compile_probe)"
        _compile_probe(jnp.ones((7,))).block_until_ready()
        assert obs.metrics.value(name) == 1.0
        assert obs.metrics.value(jaxhooks.BACKEND_COMPILES) >= total + 1
        _compile_probe(jnp.ones((7,))).block_until_ready()   # warm
        assert obs.metrics.value(name) == 1.0
        _compile_probe(jnp.ones((5,))).block_until_ready()   # new shape
        assert obs.metrics.value(name) == 2.0

    def test_merge_snapshots_psum_free(self):
        hosts = [
            {"counters": {"engine.perm_chunks": 3.0},
             "gauges": {"device0.peak_bytes_in_use": 100.0},
             "histograms": {"t": {"count": 2, "total": 4.0,
                                  "min": 1.0, "max": 3.0}}},
            {"counters": {"engine.perm_chunks": 5.0},
             "gauges": {"device0.peak_bytes_in_use": 250.0},
             "histograms": {"t": {"count": 1, "total": 9.0,
                                  "min": 9.0, "max": 9.0}}},
        ]
        m = obs.metrics.merge_snapshots(hosts)
        assert m["counters"]["engine.perm_chunks"] == 8.0        # sum
        assert m["gauges"]["device0.peak_bytes_in_use"] == 250.0  # peak
        h = m["histograms"]["t"]
        assert (h["count"], h["total"], h["min"], h["max"]) == (3, 13.0,
                                                                1.0, 9.0)

    def test_counter_delta(self):
        obs.enable(trace=False, metrics=True)
        obs.metrics.inc("a", 2.0)
        before = obs.metrics.snapshot()
        obs.metrics.inc("a", 3.0)
        obs.metrics.inc("b", 1.0)
        assert obs.metrics.counter_delta(before) == {"a": 3.0, "b": 1.0}


class TestWarmPipeline:
    @pytest.mark.parametrize("mat", ["dense", "stream", "fused-kernel"])
    def test_second_call_zero_retraces_and_report(self, mat, capsys):
        x, grouping = _study()
        xj, gj = jnp.asarray(x), jnp.asarray(grouping)
        kw = dict(metric="braycurtis", n_perms=39, key=jax.random.key(0),
                  materialize=mat)
        obs.enable(trace=True, metrics=True)
        r1 = pipeline.pipeline(xj, gj, **kw)
        jax.block_until_ready(r1.f_perms)
        before = obs.metrics.value(jaxhooks.RETRACES)
        r2 = pipeline.pipeline(xj, gj, **kw)
        jax.block_until_ready(r2.f_perms)
        delta = obs.metrics.value(jaxhooks.RETRACES) - before
        assert delta == 0, (f"warm {mat} pipeline re-traced {delta} "
                            "jaxprs on an identical second call")
        assert float(r1.f_stat) == pytest.approx(float(r2.f_stat))
        # the reconciliation table names the stage and a bandwidth column
        text = obs.report(file=None)
        assert "GB/s" in text
        expect = {"dense": "stage1.braycurtis",
                  "stream": "stage1.braycurtis",
                  "fused-kernel": "bridge.fused-kernel"}[mat]
        assert expect in text

    def test_trace_kwarg_exports_without_global_enable(self, tmp_path):
        x, grouping = _study()
        path = str(tmp_path / "pipe.json")
        res = pipeline.pipeline(jnp.asarray(x), jnp.asarray(grouping),
                                metric="braycurtis", n_perms=19,
                                key=jax.random.key(0), materialize="stream",
                                trace=path)
        assert 0.0 <= float(res.p_value) <= 1.0
        names = {e["name"] for e in json.load(open(path))["traceEvents"]}
        assert "stage1.braycurtis" in names
        assert "engine.sw" in names
        assert not obs.enabled()   # session restored the disabled state


class TestAutotuneCacheCounters:
    def test_hit_miss_and_disabled_warn_once(self, tmp_path, monkeypatch,
                                             caplog):
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv(eplanner.AUTOTUNE_CACHE_ENV, path)
        eplanner.load_autotune_cache(reload=True)
        obs.enable(trace=False, metrics=True)
        assert eplanner.measured_impl("cpu", 64, 4) is None
        assert obs.metrics.value("autotune.cache.miss") == 1.0
        cands = list(eplanner._default_candidates("cpu"))
        eplanner.record_entry(eplanner._persist_key("cpu", 64, 4),
                              {"impl": "matmul", "candidates": cands})
        assert eplanner.measured_impl("cpu", 64, 4) == "matmul"
        assert obs.metrics.value("autotune.cache.hit") == 1.0

        # disabled path warns exactly once (logging, not warnings: tier-1
        # runs with -W error semantics on the library surface)
        monkeypatch.setenv(eplanner.AUTOTUNE_CACHE_ENV, "off")
        eplanner.load_autotune_cache(reload=True)
        eplanner._WARNED.discard("disabled")
        import logging
        with caplog.at_level(logging.WARNING, logger=eplanner.__name__):
            eplanner._save_autotune_cache()
            eplanner._save_autotune_cache()
        msgs = [r for r in caplog.records
                if "autotune cache disabled" in r.message]
        assert len(msgs) == 1

    def test_stale_schema_dropped_counter(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune.json")
        # dist| keys require the current schema stamp; a schema-less one
        # (pre-PR6 format) must be dropped on load, not silently trusted
        stale = {"dist|cpu|braycurtis|blocked": {"impl": "blocked"}}
        with open(path, "w") as f:
            json.dump(stale, f)
        monkeypatch.setenv(eplanner.AUTOTUNE_CACHE_ENV, path)
        obs.enable(trace=False, metrics=True)
        eplanner._WARNED.discard("stale")
        cache = eplanner.load_autotune_cache(reload=True)
        assert cache == {}
        assert obs.metrics.value("autotune.cache.stale_dropped") == 1.0


class TestSpanRingBuffer:
    """An always-on server traces indefinitely: the completed-span buffer
    is a ring capped at `set_buffer_cap(n)` — oldest spans drop first,
    drops are counted, and export keeps the most recent COMPLETE spans."""

    def test_cap_keeps_most_recent_spans(self, tmp_path):
        prev = obs.buffer_cap()
        obs.clear()
        obs.enable(trace=True, metrics=False)
        try:
            obs.set_buffer_cap(10)
            for i in range(25):
                with obs.span(f"serve.step{i}"):
                    pass
            evs = obs.events()
            assert len(evs) == 10
            # the survivors are exactly the 10 most recent, in order
            assert [e["name"] for e in evs] == [
                f"serve.step{i}" for i in range(15, 25)]
            assert obs.dropped_events() == 15
            # export under cap writes the surviving spans
            out = tmp_path / "ring.json"
            obs.trace.export(str(out))
            data = json.loads(out.read_text())
            names = [e["name"] for e in data["traceEvents"]
                     if e.get("name", "").startswith("serve.step")]
            assert names == [f"serve.step{i}" for i in range(15, 25)]
        finally:
            obs.disable()
            obs.clear()
            obs.set_buffer_cap(prev)

    def test_shrinking_cap_trims_immediately(self):
        prev = obs.buffer_cap()
        obs.clear()
        obs.enable(trace=True, metrics=False)
        try:
            obs.set_buffer_cap(None)          # unbounded
            for i in range(8):
                with obs.span(f"s{i}"):
                    pass
            assert len(obs.events()) == 8
            obs.set_buffer_cap(3)
            assert [e["name"] for e in obs.events()] == ["s5", "s6", "s7"]
            assert obs.dropped_events() == 5
            # clear() resets the drop counter with the buffer
            obs.clear()
            assert obs.dropped_events() == 0
        finally:
            obs.disable()
            obs.clear()
            obs.set_buffer_cap(prev)


class TestNoSilentFallback:
    def test_failed_autotune_candidate_counted_and_logged(self, caplog,
                                                          monkeypatch):
        import jax.numpy as jnp
        from repro.engine import planner, registry

        def boom(**_):
            def fn(mat2, groupings, inv_gs):
                raise RuntimeError("refused by the compiler")
            return fn

        monkeypatch.setitem(registry._REGISTRY, "boom", registry.SwImpl(
            name="boom", kind="jnp", make=boom, backends=(), tuning={},
            pad_contract="none"))
        x, g = _study(n=24, d=6, g=3)
        dm = jnp.asarray(np.abs(x[:, None, 0] - x[None, :, 0]))
        inv_gs = jnp.asarray([1.0 / np.sum(g == k) for k in range(3)],
                             jnp.float32)
        obs.enable(trace=False, metrics=True)
        with caplog.at_level("WARNING", logger="repro.engine.planner"):
            win = planner.autotune(dm * dm, jnp.asarray(g), inv_gs,
                                   candidates=["matmul", "boom"],
                                   sample_perms=4, use_cache=False)
        assert win == "matmul"
        assert obs.metrics.value("autotune.candidate_failed") == 1
        assert obs.metrics.value("autotune.candidate_failed.boom") == 1
        assert any("boom" in r.getMessage()
                   and "refused by the compiler" in r.getMessage()
                   for r in caplog.records)

    def test_reference_bandwidth_keyed_by_device_kind(self):
        from repro import hw
        from repro.obs.report import reference_gbps
        from repro.pipeline.registry import tier_bandwidth_gbps
        assert reference_gbps("TPU v5 lite") == pytest.approx(819.0)
        assert reference_gbps("cpu") == hw.HOST_CPU.hbm_bandwidth / 1e9
        assert tier_bandwidth_gbps("hbm", "TPU v5 lite") == \
            pytest.approx(819.0)
        assert reference_gbps() == reference_gbps("cpu")   # this backend
        for fn in (hw.device_spec, reference_gbps):
            with pytest.raises(KeyError, match="no peak-rate entry"):
                fn("TPU v99")
        assert all(spec.source for spec in hw.DEVICES.values())
