"""Hardware-aware execution engine: registry parity against the Algorithm 1
oracle (every registered impl, awkward shapes included), planner dispatch
rules, streaming scheduler equivalence + fixed-memory contract, and the
batched multi-study API."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import fstat, permutations
from repro.core.permanova import permanova

# (n, n_groups) — prime n exercises pad paths; the (9, 8) case has
# singleton groups (inv size 1.0, no within-group pairs contributed).
SHAPES = [
    (32, 3),
    (37, 4),    # prime n: tiled + pallas padding paths
    (53, 5),    # prime n
    (9, 8),     # singleton groups
]


def _instance(n, g, seed=0, n_perms=6):
    rng = np.random.default_rng(seed)
    d = rng.random((n, n)).astype(np.float32)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[:g] = np.arange(g)
    inv_gs = np.asarray(permutations.inv_group_sizes(jnp.asarray(grouping), g))
    gperms = np.asarray(permutations.permutation_batch(
        jax.random.key(seed + 1), jnp.asarray(grouping), 0, n_perms))
    return d, grouping, inv_gs, gperms


def _f_err(f, ref, n, n_groups):
    """Worst |dF| / (F + (n-a)/(a-1)) over the permutations: the relative
    error of s_W, which F's cancellation does not amplify."""
    f, ref = np.asarray(f, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(f - ref)
                        / (ref + (n - n_groups) / (n_groups - 1))))


class TestRegistryParity:
    """Every registered impl must match the literal Algorithm 1 oracle."""

    @pytest.mark.parametrize("name", engine.names())
    @pytest.mark.parametrize("n,g", SHAPES)
    def test_matches_algorithm1(self, name, n, g):
        d, grouping, inv_gs, gperms = _instance(n, g, seed=n + g)
        oracle = fstat.sw_algorithm1_numpy(d, gperms, inv_gs)
        spec = engine.get(name)
        # shrink pallas/tiled tiles for these small shapes
        overrides = {"tile_r": 16, "tile_c": 16, "perm_block": 2,
                     "tile": 16, "block": 2}
        fn = spec.bound(**overrides)
        got = np.asarray(fn(jnp.asarray(d * d), jnp.asarray(gperms),
                            jnp.asarray(inv_gs)))
        np.testing.assert_allclose(got, oracle, rtol=5e-5, atol=1e-5)

    def test_registry_metadata_complete(self):
        assert set(engine.names()) == {
            "brute", "tiled", "matmul",
            "pallas_brute", "pallas_permblock", "pallas_matmul"}
        for name in engine.names():
            spec = engine.get(name)
            assert spec.backends, name
            assert spec.pad_contract in ("none", "internal")
        # every impl resolves to some row-sharded companion
        for name in engine.names():
            assert callable(engine.get_sharded(name))

    def test_sharded_partials_sum_to_oracle(self):
        d, grouping, inv_gs, gperms = _instance(48, 3, seed=2)
        oracle = fstat.sw_algorithm1_numpy(d, gperms, inv_gs)
        for name in ("brute", "matmul", "tiled", "pallas_matmul"):
            fn = engine.get_sharded(name)
            parts = [np.asarray(fn(jnp.asarray((d * d)[o:o + 16]), o,
                                   jnp.asarray(gperms), jnp.asarray(inv_gs)))
                     for o in (0, 16, 32)]
            np.testing.assert_allclose(sum(parts), oracle, rtol=5e-5)


class TestTiledPadding:
    """Satellite fix: prime n must pad to the requested tile (sentinel
    group), not degrade toward a tile=1 scalar scan."""

    @pytest.mark.parametrize("n", [37, 53, 61])
    def test_prime_n_matches_oracle(self, n):
        d, grouping, inv_gs, gperms = _instance(n, 4, seed=n)
        oracle = fstat.sw_algorithm1_numpy(d, gperms, inv_gs)
        got = np.asarray(fstat.sw_tiled(
            jnp.asarray(d * d), jnp.asarray(gperms), jnp.asarray(inv_gs),
            tile=16))
        np.testing.assert_allclose(got, oracle, rtol=5e-5, atol=1e-5)

    def test_pad_region_contributes_zero(self):
        # padding a matrix with explicit zeros must not change the result
        d, grouping, inv_gs, gperms = _instance(30, 3, seed=1)
        a = np.asarray(fstat.sw_tiled_one(
            jnp.asarray((d * d)), jnp.asarray(gperms[1]),
            jnp.asarray(inv_gs), tile=16))
        b = np.asarray(fstat.sw_tiled_one(
            jnp.asarray((d * d)), jnp.asarray(gperms[1]),
            jnp.asarray(inv_gs), tile=15))  # 30 % 15 == 0: no-pad path
        np.testing.assert_allclose(a, b, rtol=1e-5)


class TestPlanner:
    """backend -> impl dispatch must encode the paper's Fig. 1 result."""

    def test_gpu_prefers_brute(self):
        assert engine.plan(4096, 1000, 8, backend="gpu").impl == "brute"

    def test_cpu_large_prefers_tiled(self):
        # mat2 spills the modeled LLC -> cache-tiled Algorithm 2
        assert engine.plan(8192, 1000, 8, backend="cpu").impl == "tiled"

    def test_cpu_small_prefers_matmul(self):
        assert engine.plan(256, 1000, 8, backend="cpu").impl == "matmul"

    def test_tpu_prefers_pallas_matmul(self):
        assert engine.plan(4096, 1000, 8, backend="tpu").impl == "pallas_matmul"
        assert engine.plan(64, 1000, 8, backend="tpu").impl == "matmul"

    def test_pinned_impl_respected(self):
        pl = engine.plan(512, 1000, 8, backend="cpu", impl="brute")
        assert pl.impl == "brute"

    def test_chunk_respects_budget(self):
        spec = engine.get("matmul")
        n = 1024
        chunk = engine.chunk_for_budget(n, 10 ** 6, spec, 8,
                                        budget_bytes=64 * 2 ** 20)
        # label tensor for the chunk must fit comfortably in the budget
        assert 4 * n * chunk <= 64 * 2 ** 20
        assert chunk >= 64
        # bigger budget, bigger chunk
        bigger = engine.chunk_for_budget(n, 10 ** 6, spec, 8,
                                         budget_bytes=512 * 2 ** 20)
        assert bigger > chunk

    def test_plan_streaming_flag(self):
        pl = engine.plan(512, 100_001, 8, backend="cpu",
                         memory_budget_bytes=4 * 2 ** 20)
        assert pl.streaming and pl.chunk < 100_001
        small = engine.plan(512, 100, 8, backend="cpu")
        assert not small.streaming

    def test_autotune_returns_registered_impl(self):
        d, grouping, inv_gs, _ = _instance(32, 3)
        name = engine.autotune(jnp.asarray(d * d), jnp.asarray(grouping),
                               jnp.asarray(inv_gs), sample_perms=4,
                               use_cache=False)
        assert name in engine.names()


class TestStreamingScheduler:
    def test_stream_equals_batch(self):
        d, grouping, _, _ = _instance(37, 4, seed=5)
        dm = jnp.asarray(d)
        key = jax.random.key(9)
        batch = engine.run(dm, grouping, n_perms=200, impl="matmul", key=key)
        stream = engine.run(dm, grouping, n_perms=200, impl="matmul",
                            key=key, chunk=33)  # ragged last chunk
        assert "stream" in stream.plan and "chunks=7" in stream.plan
        np.testing.assert_allclose(np.asarray(stream.f_perms),
                                   np.asarray(batch.f_perms), rtol=1e-5)
        assert float(stream.p_value) == float(batch.p_value)

    @pytest.mark.parametrize("chunk", ["1", "7", "P", "P+1", "2P"])
    @pytest.mark.parametrize("n", [37, 64])
    def test_stream_equals_batch_at_chunk_edges(self, n, chunk):
        """Chunks of one label, ragged chunks, one short of the whole
        sweep (its last chunk holds one permutation), exactly the sweep
        (one chunk: the batch plan) and larger than it, over P = 40
        permutations and the observed grouping."""
        p = 40
        c = {"1": 1, "7": 7, "P": p, "P+1": p + 1, "2P": 2 * p}[chunk]
        d, grouping, _, _ = _instance(n, 4, seed=n + c)
        dm, key = jnp.asarray(d), jax.random.key(n)
        batch = engine.run(dm, grouping, n_perms=p, impl="matmul", key=key)
        stream = engine.run(dm, grouping, n_perms=p, impl="matmul",
                            key=key, chunk=c)
        n_chunks = -(-(p + 1) // c)
        assert re.search(r"chunks=(\d+)", stream.plan).group(1) == \
            str(n_chunks), stream.plan
        assert ("stream" in stream.plan) == (n_chunks > 1), stream.plan
        assert _f_err(stream.f_perms, batch.f_perms, n, 4) < 1e-5
        assert float(stream.p_value) == float(batch.p_value)

    def test_fixed_memory_contract(self):
        """Large sweep under a small budget: label footprint stays bounded
        and the (n_perms, n) tensor is never materialized."""
        d, grouping, _, _ = _instance(64, 4, seed=6)
        mat2 = jnp.asarray(d * d)
        inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), 4)
        fn = engine.get("matmul").bound()
        n_total = 100_001
        budget = 1 * 2 ** 20
        chunk = engine.chunk_for_budget(64, n_total, engine.get("matmul"),
                                        4, budget_bytes=budget)
        s_w, stats = engine.sw_streaming(mat2, jnp.asarray(grouping), inv_gs,
                                         jax.random.key(0), n_total, fn,
                                         chunk=chunk)
        assert stats.n_total == n_total
        assert stats.n_chunks == -(-n_total // stats.chunk) > 1
        assert stats.peak_label_bytes <= budget
        assert s_w.shape == (n_total,)
        # spot-check a mid-stream chunk against direct generation
        lo = stats.chunk * 2
        g = permutations.permutation_batch(jax.random.key(0),
                                           jnp.asarray(grouping), lo, lo + 8)
        np.testing.assert_allclose(s_w[lo:lo + 8],
                                   np.asarray(fn(mat2, g, inv_gs)), rtol=1e-5)

    def test_identity_perm_first(self):
        d, grouping, _, _ = _instance(32, 3, seed=7)
        res = engine.run(jnp.asarray(d), grouping, n_perms=100, chunk=17,
                         impl="brute", key=jax.random.key(1))
        inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), 3)
        obs = fstat.sw_brute_one(jnp.asarray(d * d), jnp.asarray(grouping),
                                 inv_gs)
        np.testing.assert_allclose(float(res.s_w), float(obs), rtol=1e-5)


def _f_and_p_float64(d, labels, n_groups):
    """F of every labelling and p, summed in float64 on the host."""
    n = d.shape[0]
    d2 = np.asarray(d, np.float64) ** 2
    s_t = d2.sum() / (2 * n)
    f = []
    for lab in labels:
        w = 1.0 / np.bincount(lab, minlength=n_groups)[lab]
        s_w = 0.5 * np.sum(d2 * (lab[:, None] == lab[None, :]) * w[:, None])
        f.append(((s_t - s_w) / (n_groups - 1)) / (s_w / (n - n_groups)))
    f = np.asarray(f)
    return f, (np.sum(f[1:] >= f[0]) + 1) / len(f)


class TestPallasMatmulEndToEnd:
    """engine.run through the one-hot Pallas kernel, the paper cell's
    path, with 32-tiles so n straddles a tile."""

    @pytest.mark.parametrize("g", [2, 17])
    @pytest.mark.parametrize("n", [31, 32, 33, 67])
    def test_f_and_p_match_float64_sum(self, n, g):
        """F of every permutation and p against a float64 NumPy sum over
        the documented draw: permutation k relabels by
        grouping[jax.random.permutation(fold_in(key, k), n)], k = 0 the
        observed grouping."""
        p = 99
        d, grouping, _, _ = _instance(n, g, seed=n * g)
        key = jax.random.key(n + g)
        res = engine.run(jnp.asarray(d), grouping, n_perms=p,
                         impl="pallas_matmul", key=key,
                         tuning={"tile_r": 32, "tile_c": 32})
        assert res.plan.startswith("pallas_matmul"), res.plan
        labels = [grouping] + [
            grouping[np.asarray(jax.random.permutation(
                jax.random.fold_in(key, k), n))] for k in range(1, p + 1)]
        f64, p64 = _f_and_p_float64(d, np.stack(labels), g)
        assert res.f_perms.shape == (p + 1,)
        assert _f_err(res.f_perms, f64, n, g) < 1e-5
        assert float(res.p_value) == pytest.approx(p64, abs=1e-7)


class TestEntryPoints:
    def test_core_permanova_routes_through_engine(self, small_study):
        dm, grouping, _, _ = small_study
        res = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=29)
        assert res.method.startswith("permanova[")
        assert res.plan  # engine always records its plan

    def test_auto_matches_pinned(self, small_study):
        dm, grouping, _, _ = small_study
        auto = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=29,
                         sw_impl="auto")
        pinned = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=29,
                           sw_impl="brute")
        np.testing.assert_allclose(float(auto.f_stat), float(pinned.f_stat),
                                   rtol=1e-4)
        assert float(auto.p_value) == float(pinned.p_value)

    def test_budget_kwarg_streams(self, small_study):
        dm, grouping, _, _ = small_study
        res = permanova(jnp.asarray(dm), jnp.asarray(grouping),
                        n_perms=2000, sw_impl="matmul",
                        memory_budget_bytes=48 * 48 * 4 * 2 + 40000)
        assert "stream" in res.plan

    def test_pallas_impl_name_accepted(self, small_study):
        dm, grouping, _, _ = small_study
        ref = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=19,
                        sw_impl="brute")
        res = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=19,
                        sw_impl="pallas_matmul")
        np.testing.assert_allclose(float(res.f_stat), float(ref.f_stat),
                                   rtol=1e-4)


class TestPermanovaMany:
    def test_matches_independent_runs(self):
        g = 4
        studies = [_instance(32, g, seed=s)[0] for s in range(3)]
        groupings = [_instance(32, g, seed=s)[1] for s in range(3)]
        dms = jnp.stack([jnp.asarray(d) for d in studies])
        gs = jnp.stack([jnp.asarray(x) for x in groupings])
        key = jax.random.key(11)
        many = engine.permanova_many(dms, gs, n_groups=g, n_perms=49,
                                     key=key, impl="matmul")
        assert len(many) == 3
        for s in range(3):
            single = engine.run(dms[s], gs[s], n_perms=49,
                                key=jax.random.fold_in(key, s),
                                impl="matmul")
            np.testing.assert_allclose(np.asarray(many.f_perms[s]),
                                       np.asarray(single.f_perms), rtol=1e-4)
            assert float(many.p_value[s]) == float(single.p_value)

    def test_chunked_scan_inside_jit(self):
        d0, g0, _, _ = _instance(24, 3, seed=1)
        d1, g1, _, _ = _instance(24, 3, seed=2)
        dms = jnp.stack([jnp.asarray(d0), jnp.asarray(d1)])
        gs = jnp.stack([jnp.asarray(g0), jnp.asarray(g1)])
        a = engine.permanova_many(dms, gs, n_groups=3, n_perms=99, chunk=100)
        b = engine.permanova_many(dms, gs, n_groups=3, n_perms=99, chunk=13)
        np.testing.assert_allclose(np.asarray(a.f_perms),
                                   np.asarray(b.f_perms), rtol=1e-5)

    def test_study_view(self):
        d, g, _, _ = _instance(24, 3, seed=4)
        dms = jnp.stack([jnp.asarray(d)] * 2)
        gs = jnp.stack([jnp.asarray(g)] * 2)
        many = engine.permanova_many(dms, gs, n_groups=3, n_perms=19)
        one = many.study(0)
        assert one.n_objects == 24 and one.f_perms.shape == (20,)
