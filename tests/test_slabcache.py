"""Out-of-core slab streaming: cache round-trips (dense + csr), corrupt
slab quarantine, the async prefetcher's exception-safety and accounting,
residency-tiered planning, and the acceptance bar — pipeline(cache) is
bit-identical to the in-memory fused bridge at the same slab boundaries
for every metric, on both OOC materialize forms, including odd slab
sizes, the csr/jaccard path and covariate+strata designs."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, pipeline
from repro.data import microbiome, slabcache
from repro.pipeline import planner as pplanner
from repro.pipeline import registry as preg

N, D, G = 100, 24, 4
SLAB = 32            # 100/32 -> 4 slabs, ragged tail of 4 rows
PERMS = 49


def _study(seed=0, n=N, d=D, g=G):
    rng = np.random.default_rng(seed)
    x = rng.gamma(1.0, 1.0, size=(n, d)).astype(np.float32)
    x *= rng.random(size=(n, d)) < 0.5        # sparsity: jaccard informative
    x[:, 0] = np.maximum(x[:, 0], 1e-3)       # no all-zero samples
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[:g] = np.arange(g)
    return x, grouping


def _no_prefetch_threads(timeout=5.0):
    """True once no slab-prefetch worker thread remains alive."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not [t for t in threading.enumerate()
                if t.name == "slab-prefetch"]:
            return True
        time.sleep(0.01)
    return False


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    """Telemetry off/empty around every test; warn-once set reset so each
    quarantine test observes its own warning; no leaked worker threads."""
    obs.disable()
    obs.clear()
    obs.metrics.reset()
    slabcache._WARNED.clear()
    yield
    assert _no_prefetch_threads(), "slab-prefetch thread leaked"
    obs.disable()
    obs.clear()
    obs.metrics.reset()


class TestCacheRoundTrip:
    def test_dense_round_trip(self, tmp_path):
        x, _ = _study()
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        assert (cache.n, cache.d) == (N, D)
        assert cache.n_slabs == -(-N // SLAB)
        assert cache.rows_in_slab(cache.n_slabs - 1) == N % SLAB or SLAB
        assert cache.disk_bytes == 4 * N * D
        assert cache.feature_bytes == 4 * N * D
        np.testing.assert_array_equal(cache.to_array(), x)
        s0 = cache.read_slab(0)
        np.testing.assert_array_equal(s0, x[:SLAB])

    def test_reopen_and_staging_read(self, tmp_path):
        x, _ = _study(1)
        slabcache.build_slab_cache(tmp_path / "c", x, slab_rows=SLAB)
        cache = slabcache.SlabCache.open(tmp_path / "c")
        buf = np.full((SLAB, D), 9.0, np.float32)
        tail = cache.read_slab(cache.n_slabs - 1, out=buf)
        np.testing.assert_array_equal(
            tail, x[(cache.n_slabs - 1) * SLAB:])
        with pytest.raises(IndexError):
            cache.read_slab(cache.n_slabs)

    def test_odd_slab_rows(self, tmp_path):
        x, _ = _study(2)
        cache = slabcache.build_slab_cache(tmp_path / "c", x, slab_rows=7)
        assert cache.n_slabs == -(-N // 7)
        np.testing.assert_array_equal(cache.to_array(), x)

    def test_writer_uneven_appends_match_oneshot(self, tmp_path):
        x, _ = _study(3)
        with slabcache.SlabCacheWriter(tmp_path / "w", d=D,
                                       slab_rows=SLAB) as w:
            for lo, hi in ((0, 3), (3, 53), (53, N)):
                w.append(x[lo:hi])
        cache = slabcache.SlabCache.open(tmp_path / "w")
        ref = slabcache.build_slab_cache(tmp_path / "ref", x,
                                         slab_rows=SLAB)
        assert cache.n_slabs == ref.n_slabs
        np.testing.assert_array_equal(cache.to_array(), ref.to_array())

    def test_csr_round_trip_presence_only(self, tmp_path):
        x, _ = _study(4)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB, fmt="csr")
        assert cache.fmt == "csr"
        np.testing.assert_array_equal(cache.to_array(),
                                      (x > 0).astype(np.float32))
        # structure-only storage beats the dense footprint at ~50% density
        assert cache.disk_bytes < 4 * N * D

    def test_empty_finalize_rejected(self, tmp_path):
        w = slabcache.SlabCacheWriter(tmp_path / "w", d=D)
        with pytest.raises(slabcache.SlabCacheError, match="empty"):
            w.finalize()


class TestQuarantine:
    def test_truncated_slab_quarantined(self, tmp_path):
        x, _ = _study()
        slabcache.build_slab_cache(tmp_path / "c", x, slab_rows=SLAB)
        victim = tmp_path / "c" / "slab_00001.bin"
        victim.write_bytes(victim.read_bytes()[:100])
        obs.enable(trace=False, metrics=True)
        with pytest.raises(slabcache.SlabCacheError, match="truncated"):
            slabcache.SlabCache.open(tmp_path / "c")
        assert (tmp_path / "c" / "slab_00001.bin.corrupt").exists()
        assert not victim.exists()
        assert obs.metrics.value("slabcache.corrupt_quarantined") == 1

    def test_missing_meta_is_clear_error(self, tmp_path):
        with pytest.raises(slabcache.SlabCacheError, match="no slab cache"):
            slabcache.SlabCache.open(tmp_path / "nothing")

    def test_missing_slab_file(self, tmp_path):
        x, _ = _study()
        slabcache.build_slab_cache(tmp_path / "c", x, slab_rows=SLAB)
        os.remove(tmp_path / "c" / "slab_00002.bin")
        with pytest.raises(slabcache.SlabCacheError, match="missing"):
            slabcache.SlabCache.open(tmp_path / "c")

    def test_garbled_manifest_quarantined(self, tmp_path):
        x, _ = _study()
        slabcache.build_slab_cache(tmp_path / "c", x, slab_rows=SLAB)
        (tmp_path / "c" / slabcache.META_NAME).write_text("{not json")
        with pytest.raises(slabcache.SlabCacheError, match="unreadable"):
            slabcache.SlabCache.open(tmp_path / "c")
        assert (tmp_path / "c"
                / (slabcache.META_NAME + ".corrupt")).exists()


class TestSyntheticSparseCounts:
    def test_deterministic_and_slabwise(self, tmp_path):
        a, ga = microbiome.synthetic_sparse_counts(
            90, 16, density=0.2, seed=5, cache_dir=tmp_path / "a",
            slab_rows=32, n_groups=G)
        b, gb = microbiome.synthetic_sparse_counts(
            90, 16, density=0.2, seed=5, cache_dir=tmp_path / "b",
            slab_rows=32, n_groups=G)
        np.testing.assert_array_equal(a.to_array(), b.to_array())
        np.testing.assert_array_equal(ga, gb)
        c, _ = microbiome.synthetic_sparse_counts(
            90, 16, density=0.2, seed=6, cache_dir=tmp_path / "d",
            slab_rows=32, n_groups=G)
        assert not np.array_equal(a.to_array(), c.to_array())
        assert set(np.asarray(ga)[:G]) == set(range(G))


class TestPrefetcher:
    def test_full_iteration_accounting(self, tmp_path):
        x, _ = _study()
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        sched = list(slabcache.ooc_schedule(cache.n_slabs))
        assert len(sched) == cache.n_slabs * (cache.n_slabs + 1)
        seen = []
        with slabcache.SlabPrefetcher(cache, sched) as pf:
            for idx, dev in pf:
                assert dev.shape == (SLAB, D)
                seen.append(idx)
        assert seen == sched
        assert pf.slabs_fetched == len(sched)
        assert pf.bytes_read == (cache.n_slabs + 1) * cache.disk_bytes
        assert pf.stall_s >= 0.0
        assert _no_prefetch_threads()

    def test_clean_shutdown_on_midsweep_exception(self, tmp_path):
        x, _ = _study()
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        with pytest.raises(RuntimeError, match="sweep died"):
            with slabcache.SlabPrefetcher(
                    cache, list(range(cache.n_slabs)) * 4) as pf:
                next(pf)
                raise RuntimeError("sweep died")
        assert _no_prefetch_threads(), \
            "prefetch worker survived a mid-sweep exception"

    def test_worker_error_surfaces_to_consumer(self, tmp_path):
        x, _ = _study()
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        os.remove(tmp_path / "c" / "slab_00001.bin")   # after validation
        with slabcache.SlabPrefetcher(cache, [0, 1, 2]) as pf:
            next(pf)
            with pytest.raises(slabcache.SlabCacheError,
                               match="prefetch failed"):
                for _ in pf:
                    pass
        assert _no_prefetch_threads()

    def test_pad_to_smaller_than_slab_rejected(self, tmp_path):
        x, _ = _study()
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        with pytest.raises(ValueError, match="pad_to"):
            slabcache.SlabPrefetcher(cache, [0], pad_to=SLAB - 1)
        assert _no_prefetch_threads()


class TestResidencyPlanning:
    def test_tier_grading(self):
        kw = dict(device_budget_bytes=2**20, host_budget_bytes=2**30)
        assert preg.residency_tier(2**10, **kw) == "hbm"
        assert preg.residency_tier(2**25, **kw) == "host"
        assert preg.residency_tier(2**31, **kw) == "disk"

    def test_tier_bandwidth_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_TIER_GBPS_DISK", "5.5")
        assert preg.tier_bandwidth_gbps("disk") == 5.5
        assert preg.tier_bandwidth_gbps("host") > \
            preg.tier_bandwidth_gbps("disk")

    def test_disk_traffic_model(self):
        # (n_slabs + 1) full passes: row operand once + column stream
        # once per row slab
        assert preg.ooc_disk_traffic_bytes(4, 1000) == 5000.0

    def test_ooc_plan_forces_slab_geometry(self):
        pl = pplanner.plan_pipeline(
            N, D, PERMS + 1, G, features_on_disk=True, slab_rows=SLAB,
            features_disk_bytes=4 * N * D, device_budget_bytes=1024)
        assert pl.residency == "host"
        assert pl.materialize == "fused-kernel"
        assert pl.row_block == SLAB
        assert preg.get_fused(pl.fused_impl).kind == "xla"
        text = pl.explain()
        assert "residency: host" in text
        assert "slab-cache traffic" in text
        assert "tier bandwidth model" in text

    def test_ooc_plan_disk_tier_and_pins_rejected(self):
        pl = pplanner.plan_pipeline(
            N, D, PERMS + 1, G, features_on_disk=True, slab_rows=SLAB,
            features_disk_bytes=4 * N * D, device_budget_bytes=1024,
            host_budget_bytes=2048)
        assert pl.residency == "disk"
        for bad in ("dense", "stream"):
            with pytest.raises(ValueError, match="resident"):
                pplanner.plan_pipeline(
                    N, D, PERMS + 1, G, features_on_disk=True,
                    slab_rows=SLAB, features_disk_bytes=4 * N * D,
                    device_budget_bytes=1024, materialize=bad)
        with pytest.raises(ValueError, match="XLA"):
            pplanner.plan_pipeline(
                N, D, PERMS + 1, G, features_on_disk=True,
                slab_rows=SLAB, features_disk_bytes=4 * N * D,
                device_budget_bytes=1024,
                fused_impl="braycurtis.fusedk.pallas")
        with pytest.raises(ValueError, match="f32"):
            pplanner.plan_pipeline(
                N, D, PERMS + 1, G, features_on_disk=True,
                slab_rows=SLAB, features_disk_bytes=4 * N * D,
                device_budget_bytes=1024,
                fused_tuning=preg.precision_tuning("fp8"))

    def test_plan_slab_rows_scales_with_budget(self):
        small = pplanner.plan_slab_rows(100_000, 4096,
                                        device_budget_bytes=2 * 2**30)
        large = pplanner.plan_slab_rows(100_000, 4096,
                                        device_budget_bytes=64 * 2**30)
        assert small < large
        assert small & (small - 1) == 0      # power of two


class TestOocPipeline:
    def test_bit_identity_all_metrics_both_forms(self, tmp_path):
        """The acceptance bar: OOC F/p == the in-memory fused bridge at
        row_block == slab_rows for every metric, on both OOC materialize
        forms (chunked 'fused' and onepass 'fused-kernel' — both
        accumulate f64 host-side in fused order).

        The two drivers do not share the distance step: the OOC sweep
        builds each m2 row slab from (slab, slab) tiles of per-slab
        prepared features, the in-memory bridge in one (slab, n) call over
        the prepared table. The Gram-form metrics (euclidean, aitchison)
        contract the feature axis in matmuls whose blocking follows the
        operand shapes, so F may differ in the last f32 bits; p-values
        and every other statistic must still agree exactly."""
        x, g = _study()
        key = jax.random.key(0)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        for metric in pipeline.metrics():
            ref = pipeline.pipeline(
                jnp.asarray(x), g, metric=metric, n_perms=PERMS,
                materialize="fused", row_block=SLAB, key=key)
            for mat in ("fused", "fused-kernel"):
                res = pipeline.pipeline(
                    cache, g, metric=metric, n_perms=PERMS,
                    materialize=mat, device_budget_bytes=1024, key=key)
                assert f"ooc-{mat}" in res.method, res.method
                np.testing.assert_allclose(
                    np.asarray(res.f_perms), np.asarray(ref.f_perms),
                    rtol=1e-4, err_msg=f"{metric}/{mat}")
                np.testing.assert_allclose(float(res.s_t), float(ref.s_t),
                                           rtol=1e-4)
                assert float(res.p_value) == float(ref.p_value)

    def test_bit_identity_odd_slab(self, tmp_path):
        x, g = _study(7)
        key = jax.random.key(3)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=23)
        ref = pipeline.pipeline(jnp.asarray(x), g, n_perms=PERMS,
                                materialize="fused", row_block=23,
                                key=key)
        res = pipeline.pipeline(cache, g, n_perms=PERMS,
                                materialize="fused",
                                device_budget_bytes=1024, key=key)
        np.testing.assert_array_equal(np.asarray(res.f_perms),
                                      np.asarray(ref.f_perms))

    def test_csr_jaccard_and_metric_guard(self, tmp_path):
        x, g = _study(8)
        key = jax.random.key(1)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB, fmt="csr")
        presence = (x > 0).astype(np.float32)
        ref = pipeline.pipeline(jnp.asarray(presence), g,
                                metric="jaccard", n_perms=PERMS,
                                materialize="fused", row_block=SLAB,
                                key=key)
        res = pipeline.pipeline(cache, g, metric="jaccard",
                                n_perms=PERMS, materialize="fused",
                                device_budget_bytes=1024, key=key)
        np.testing.assert_array_equal(np.asarray(res.f_perms),
                                      np.asarray(ref.f_perms))
        with pytest.raises(ValueError, match="presence"):
            pipeline.pipeline(cache, g, metric="braycurtis",
                              n_perms=PERMS, device_budget_bytes=1024,
                              key=key)

    def test_directory_path_input(self, tmp_path):
        x, g = _study(9)
        key = jax.random.key(2)
        slabcache.build_slab_cache(tmp_path / "c", x, slab_rows=SLAB)
        res = pipeline.pipeline(str(tmp_path / "c"), g, n_perms=PERMS,
                                device_budget_bytes=1024, key=key)
        ref = pipeline.pipeline(jnp.asarray(x), g, n_perms=PERMS,
                                materialize="fused", row_block=SLAB,
                                key=key)
        np.testing.assert_array_equal(np.asarray(res.f_perms),
                                      np.asarray(ref.f_perms))

    def test_hbm_residency_short_circuit(self, tmp_path):
        x, g = _study(10)
        key = jax.random.key(4)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        res = pipeline.pipeline(cache, g, n_perms=PERMS, key=key)
        assert "residency=hbm" in res.plan
        ref = pipeline.pipeline(jnp.asarray(x), g, n_perms=PERMS,
                                key=key)
        np.testing.assert_array_equal(np.asarray(res.f_perms),
                                      np.asarray(ref.f_perms))

    def test_design_terms_bit_identical(self, tmp_path):
        x, g = _study(11)
        rng = np.random.default_rng(11)
        cov = rng.normal(size=(N, 2))
        st = (np.arange(N) % 4).astype(np.int32)
        key = jax.random.key(5)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        ref = pipeline.pipeline(jnp.asarray(x), g, n_perms=PERMS,
                                covariates=cov, strata=st, n_groups=G,
                                materialize="fused", row_block=SLAB,
                                key=key)
        res = pipeline.pipeline(cache, g, n_perms=PERMS,
                                covariates=cov, strata=st, n_groups=G,
                                materialize="fused",
                                device_budget_bytes=1024, key=key)
        assert len(res.terms) == len(ref.terms)
        for t_ooc, t_ref in zip(res.terms, ref.terms):
            assert t_ooc.name == t_ref.name
            np.testing.assert_array_equal(np.asarray(t_ooc.f_perms),
                                          np.asarray(t_ref.f_perms))
            assert float(t_ooc.p_value) == float(t_ref.p_value)

    def test_ordination_and_autotune_guards(self, tmp_path):
        x, g = _study(12)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        with pytest.raises(ValueError, match="resident"):
            pipeline.pipeline(cache, g, n_perms=PERMS, ordination=2,
                              device_budget_bytes=1024,
                              key=jax.random.key(0))
        with pytest.warns(UserWarning, match="autotune"):
            pipeline.pipeline(cache, g, n_perms=PERMS, autotune=True,
                              device_budget_bytes=1024,
                              key=jax.random.key(0))

    def test_trace_and_counters(self, tmp_path):
        """The trace artifact carries the overlap evidence: a bridge.ooc
        span with measured stall_ms + the predicted disk traffic, and
        the prefetch counters account for every scheduled slab."""
        x, g = _study(13)
        cache = slabcache.build_slab_cache(tmp_path / "c", x,
                                           slab_rows=SLAB)
        obs.enable(trace=False, metrics=True)
        out = tmp_path / "trace.json"
        pipeline.pipeline(cache, g, n_perms=PERMS,
                          device_budget_bytes=1024,
                          key=jax.random.key(0), trace=str(out))
        doc = json.loads(out.read_text())
        spans = {e["name"]: e for e in doc["traceEvents"]}
        assert {"bridge.ooc", "prefetch.fetch",
                "prefetch.wait"} <= set(spans)
        args = spans["bridge.ooc"]["args"]
        assert args["stall_ms"] >= 0.0
        assert args["predicted_bytes"] == preg.ooc_disk_traffic_bytes(
            cache.n_slabs, cache.disk_bytes)
        assert args["disk_bytes_read"] == \
            (cache.n_slabs + 1) * cache.disk_bytes
        n_sched = cache.n_slabs * (cache.n_slabs + 1)
        assert obs.metrics.value("prefetch.slabs") == n_sched
        assert obs.metrics.value("prefetch.stall_ms") >= 0.0


class TestSloBudgets:
    def test_violation_detection(self):
        obs.enable(trace=True, metrics=False)
        with obs.span("stage1.braycurtis"):
            time.sleep(0.01)
        viol = obs.budget_violations({"stage1.*": 0.0})
        assert len(viol) == 1
        assert viol[0]["pattern"] == "stage1.*"
        assert viol[0]["measured_s"] >= 0.01
        assert viol[0]["stages"] == ["stage1.braycurtis"]
        assert obs.budget_violations({"stage1.*": 60.0}) == []
        # a pattern matching no spans is "not run", never a violation
        assert obs.budget_violations({"fusedk.*": 0.0}) == []

    def test_report_renders_budget_section(self):
        obs.enable(trace=True, metrics=False)
        with obs.span("stage1.braycurtis"):
            time.sleep(0.005)
        text = obs.report(budgets={"stage1.*": 0.0, "fusedk.*": 1.0},
                          file=None)
        assert "wall-clock SLO budgets" in text
        assert "[OVER]" in text
        assert "[not run]" in text
