"""Low-precision & sparse megakernel arithmetic (tentpole of the
precision PR): packed-bit jaccard bit-equality against the fp32 matmul
form, fp8 feature slabs against an fp64 oracle under pinned per-metric
tolerances, block-sparse design-basis contraction bit-matching dense,
and the precision-aware traffic/workset models the planner reports."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipeline
from repro.core import distance as dist
from repro.core import fstat, permutations
from repro.kernels.distance import ops as dops
from repro.kernels.fused_sw import ops as fops
from repro.pipeline import registry as dreg
from repro.pipeline import streaming

N, D, G = 53, 24, 5   # prime n, ragged groups (same envelope as fused_sw)


def _study(seed=0, n=N, d=D, g=G, sparsity=0.5):
    rng = np.random.default_rng(seed)
    x = rng.gamma(1.0, 1.0, size=(n, d)).astype(np.float32)
    x *= rng.random(size=(n, d)) < sparsity
    x[:, 0] = np.maximum(x[:, 0], 1e-3)
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[:g] = np.arange(g)
    return x, grouping


def _perm_batch(grouping, n_perms, seed=3):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(grouping) for _ in range(n_perms)])


def _sw_oracle_f64(xprep64, metric, g_batch, inv_gs):
    """fp64 numpy PERMANOVA s_W for explicit label batches — the oracle
    the quantized paths are pinned against."""
    n = xprep64.shape[0]
    if metric == "euclidean":
        sq = (xprep64 * xprep64).sum(axis=1)
        dm2 = sq[:, None] + sq[None, :] - 2.0 * xprep64 @ xprep64.T
        dm2 = np.maximum(dm2, 0.0)
    elif metric == "braycurtis":
        num = np.abs(xprep64[:, None, :] - xprep64[None, :, :]).sum(-1)
        den = (xprep64[:, None, :] + xprep64[None, :, :]).sum(-1)
        dm = num / np.maximum(den, 1e-30)
        dm2 = dm * dm
    elif metric == "jaccard":
        b = (xprep64 > 0).astype(np.float64)
        inter = b @ b.T
        card = b.sum(axis=1)
        union = card[:, None] + card[None, :] - inter
        dm = 1.0 - inter / np.maximum(union, 1.0)
        dm2 = dm * dm
    else:
        raise ValueError(metric)
    np.fill_diagonal(dm2, 0.0)
    sws = []
    for g in np.asarray(g_batch):
        s = 0.0
        for k in range(len(inv_gs)):
            mask = g == k
            s += inv_gs[k] * dm2[np.ix_(mask, mask)].sum()
        sws.append(0.5 * s)
    return np.asarray(sws)


# ---------------------------------------------------------------------------
# Quantization primitives
# ---------------------------------------------------------------------------

class TestPrimitives:
    def test_pack_presence_bits_matches_manual(self):
        x, _ = _study(seed=1, d=70)       # 70 features -> 3 ragged words
        packed = np.asarray(dist.pack_presence_bits(jnp.asarray(x)))
        assert packed.shape == (N, 3) and packed.dtype == np.uint32
        bits = (x > 0).astype(np.uint64)
        for w in range(3):
            block = bits[:, 32 * w: 32 * (w + 1)]
            manual = sum(block[:, b].astype(np.uint64) << b
                         for b in range(block.shape[1]))
            np.testing.assert_array_equal(packed[:, w],
                                          manual.astype(np.uint32))

    def test_fp8_scale_calibration(self):
        x = jnp.asarray([[0.5, -900.0, 3.0]], jnp.float32)
        s = float(dist.fp8_scale(x))
        assert s == pytest.approx(900.0 / dist.FP8_MAX)
        # presence tables are exactly representable: jaccard pins scale 1
        assert float(dist.fp8_metric_scale(x, "jaccard")) == 1.0
        # all-zero input must not divide by zero
        assert float(dist.fp8_scale(jnp.zeros((2, 2)))) == \
            pytest.approx(1e-12)

    def test_fp8_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.gamma(2.0, 5.0, (64, 32)).astype(np.float32))
        rt = np.asarray(dist.fp8_roundtrip(x))
        rel = np.abs(rt - np.asarray(x)) / np.maximum(np.asarray(x), 1e-9)
        assert rel.max() < 0.07            # e4m3: 3 mantissa bits ~ 2^-4

    def test_precision_tag_tuning_roundtrip(self):
        for tag in dreg.PRECISIONS:
            assert dreg.precision_tag(dreg.precision_tuning(tag)) == tag
        assert dreg.precision_tag(None) == "f32"
        with pytest.raises(ValueError, match="unknown precision"):
            dreg.precision_tuning("int4")


# ---------------------------------------------------------------------------
# Packed-bit jaccard: exact integer counts -> bit-identical everything
# ---------------------------------------------------------------------------

class TestPackedJaccard:
    @pytest.mark.parametrize("shape", [(53, 24), (31, 70), (17, 5)])
    def test_stage1_bit_identical(self, shape):
        n, d = shape
        x, _ = _study(seed=2, n=n, d=d, g=3)
        xprep = dist.ROW_METRICS["jaccard"].prepare(jnp.asarray(x))
        dm = dops.pairwise_distance(xprep, metric="jaccard", tile_r=16,
                                    tile_c=16, feat_block=8)
        dmp = dops.pairwise_distance(xprep, metric="jaccard", tile_r=16,
                                     tile_c=16, feat_block=8, packed=1)
        np.testing.assert_array_equal(np.asarray(dm), np.asarray(dmp))
        rows = dops.pairwise_distance_rows(xprep[:7], xprep,
                                           metric="jaccard", tile_r=8,
                                           tile_c=16, feat_block=8)
        rowsp = dops.pairwise_distance_rows(xprep[:7], xprep,
                                            metric="jaccard", tile_r=8,
                                            tile_c=16, feat_block=8,
                                            packed=1)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(rowsp))

    @pytest.mark.parametrize("tiles", [
        dict(tile_r=16, tile_c=16, feat_block=8, perm_block=4),
        dict(tile_r=8, tile_c=32, feat_block=16, perm_block=3),
    ])
    def test_fused_bit_identical(self, tiles):
        x, grouping = _study(seed=3)
        xprep = dist.ROW_METRICS["jaccard"].prepare(jnp.asarray(x))
        inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), G)
        g = jnp.asarray(_perm_batch(grouping, 9))
        sw, rs = fops.fused_sw_rows(xprep, xprep, g, g, inv_gs, 0,
                                    metric="jaccard", **tiles)
        swp, rsp = fops.fused_sw_rows(xprep, xprep, g, g, inv_gs, 0,
                                      metric="jaccard", feat_packed=1,
                                      **tiles)
        np.testing.assert_array_equal(np.asarray(sw), np.asarray(swp))
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(rsp))

    def test_pipeline_f_bit_identical(self):
        """Acceptance: the packed fused path returns the IDENTICAL F."""
        x, grouping = _study(seed=4)
        tiles = dict(tile_r=16, tile_c=16, feat_block=8, perm_block=4)
        base = pipeline.pipeline(jnp.asarray(x), jnp.asarray(grouping),
                                 metric="jaccard", n_perms=29,
                                 materialize="fused-kernel",
                                 fused_impl="pallas", fused_tuning=tiles)
        packed = pipeline.pipeline(jnp.asarray(x), jnp.asarray(grouping),
                                   metric="jaccard", n_perms=29,
                                   materialize="fused-kernel",
                                   fused_impl="pallas",
                                   fused_tuning={**tiles, "feat_packed": 1})
        assert float(packed.f_stat) == float(base.f_stat)
        np.testing.assert_array_equal(np.asarray(packed.f_perms),
                                      np.asarray(base.f_perms))

    def test_packed_requires_jaccard(self):
        x, grouping = _study(seed=5)
        xprep = jnp.asarray(x)
        with pytest.raises(ValueError, match="jaccard"):
            dops.pairwise_distance(xprep, metric="euclidean", packed=1)
        inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), G)
        g = jnp.asarray(_perm_batch(grouping, 2))
        with pytest.raises(ValueError, match="jaccard"):
            fops.fused_sw_rows(xprep, xprep, g, g, inv_gs, 0,
                               metric="euclidean", feat_packed=1)
        with pytest.raises(ValueError, match="mutually exclusive"):
            fops.fused_sw_rows(xprep, xprep, g, g, inv_gs, 0,
                               metric="jaccard", feat_packed=1, feat_fp8=1)


# ---------------------------------------------------------------------------
# fp8 slabs vs the fp64 oracle (pinned per-metric tolerances)
# ---------------------------------------------------------------------------

class TestFp8Parity:
    # pinned: quantization error through each metric's finalize arithmetic
    # on raw s_W (F ratios cancel most of it — the e2e pipeline check in
    # the benchmarks sees ~1e-3); jaccard presence bits are exactly
    # representable in e4m3 -> near-exact
    TOLS = {"euclidean": 2e-2, "braycurtis": 2e-2, "jaccard": 1e-5}

    @pytest.mark.parametrize("metric", ["euclidean", "braycurtis",
                                        "jaccard"])
    def test_fused_fp8_vs_f64_oracle(self, metric):
        x, grouping = _study(seed=6)
        mdef = dist.ROW_METRICS[metric]
        xprep = mdef.prepare(jnp.asarray(x))
        inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), G)
        g = jnp.asarray(_perm_batch(grouping, 8))
        sw8, _ = fops.fused_sw_rows(
            xprep, xprep, g, g, inv_gs, 0, metric=metric, feat_fp8=1,
            tile_r=16, tile_c=16, feat_block=8, perm_block=4)
        oracle = _sw_oracle_f64(np.asarray(xprep, np.float64), metric,
                                g, np.asarray(inv_gs, np.float64))
        np.testing.assert_allclose(np.asarray(sw8), oracle,
                                   rtol=self.TOLS[metric])

    def test_megakernel_matches_xla_at_fp8(self):
        """Both fused impls quantize identically (shared calibration), so
        they agree to accumulation order at fp8 too."""
        x, grouping = _study(seed=7)
        mdef = dist.ROW_METRICS["braycurtis"]
        xprep = mdef.prepare(jnp.asarray(x))
        inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), G)
        key = jax.random.key(11)
        tuning = dict(tile_r=16, tile_c=16, feat_block=8, perm_block=4,
                      feat_fp8=1)
        sw_p, st_p, _ = streaming.fused_kernel_sw(
            xprep, mdef.rows, jnp.asarray(grouping), inv_gs, key, 21,
            impl="pallas", kernel_metric="braycurtis", row_block=16,
            chunk=7, tuning=tuning)
        sw_x, st_x, _ = streaming.fused_kernel_sw(
            xprep, mdef.rows, jnp.asarray(grouping), inv_gs, key, 21,
            impl="xla", kernel_metric="braycurtis", row_block=16,
            chunk=7, tuning={"feat_fp8": 1})
        np.testing.assert_allclose(sw_p, sw_x, rtol=1e-4)
        assert st_p == pytest.approx(st_x, rel=1e-4)


# ---------------------------------------------------------------------------
# Block-sparse design-basis contraction
# ---------------------------------------------------------------------------

def _block_design(n=23, k_per=2, n_strata=3, seed=8):
    """Strata-blocked basis: each column is supported on ONE stratum."""
    rng = np.random.default_rng(seed)
    strata = np.sort(rng.integers(0, n_strata, n)).astype(np.int32)
    strata[:n_strata] = np.arange(n_strata)
    strata.sort()
    k = k_per * n_strata
    basis = np.zeros((n, k), np.float32)
    for s in range(n_strata):
        rows = np.flatnonzero(strata == s)
        basis[np.ix_(rows, range(k_per * s, k_per * (s + 1)))] = \
            rng.normal(size=(len(rows), k_per)).astype(np.float32)
    return basis, strata


class TestBlockSparse:
    def test_sparse_col_groups_structure(self):
        basis, strata = _block_design()
        groups = fstat.sparse_col_groups(basis, strata)
        assert len(groups) == 3
        cols_seen = sorted(c for cols, _ in groups for c in cols)
        assert cols_seen == list(range(basis.shape[1]))
        for cols, rows in groups:
            sup = {int(strata[r]) for r in rows}
            assert len(sup) == 1           # one stratum per group here
            assert np.all(basis[np.ix_(
                [r for r in range(len(strata)) if r not in rows],
                cols)] == 0)

    def test_contract_sparse_bit_matches_dense(self):
        basis, strata = _block_design()
        n, k = basis.shape
        rng = np.random.default_rng(9)
        m2 = rng.random((n, n)).astype(np.float32)
        m2 = m2 + m2.T
        np.fill_diagonal(m2, 0.0)
        groups = fstat.sparse_col_groups(basis, strata)
        # the permuted operand keeps the column support: rows permute
        # WITHIN strata (what strata-restricted draws guarantee)
        perms = np.stack([
            np.concatenate([rng.permutation(np.flatnonzero(strata == s))
                            for s in range(3)]) for _ in range(5)])
        v = jnp.asarray(np.stack([basis[p] for p in perms]))  # (P, n, K)
        # The skipped terms are exact zeros, but each group's gathered
        # matmul has a shorter contraction axis than the dense one, and
        # the backend blocks (reassociates) that sum by operand shape:
        # agreement is to f32 rounding of the (P, K) sums, not bitwise.
        dense = fstat.sw_cols_contract(jnp.asarray(m2), v, v)
        sparse = fstat.sw_cols_contract_sparse(jnp.asarray(m2), v, v,
                                               groups)
        tol = 1e-6 * float(np.max(np.abs(np.asarray(dense))))
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   rtol=1e-6, atol=tol)
        # slab-partial form (the fused bridge's unit) agrees the same way
        dense_s = fstat.sw_cols_contract(jnp.asarray(m2[:9]), v, v[:, :9])
        sparse_s = fstat.sw_cols_contract_sparse(jnp.asarray(m2[:9]), v,
                                                 v[:, :9], groups)
        tol = 1e-6 * float(np.max(np.abs(np.asarray(dense_s))))
        np.testing.assert_allclose(np.asarray(sparse_s),
                                   np.asarray(dense_s), rtol=1e-6, atol=tol)

    def test_fused_design_sparse_bit_matches_dense(self):
        basis, strata = _block_design(n=29)
        x, _ = _study(seed=10, n=29)
        mdef = dist.ROW_METRICS["braycurtis"]
        xprep = mdef.prepare(jnp.asarray(x))
        design = types.SimpleNamespace(
            k_cols=basis.shape[1], basis=jnp.asarray(basis),
            strata=jnp.asarray(strata))
        key = jax.random.key(3)
        dense = streaming.fused_sw_design(
            xprep, mdef.rows, design, key, 17, row_block=8, chunk=5,
            block_sparse=False)
        sparse = streaming.fused_sw_design(
            xprep, mdef.rows, design, key, 17, row_block=8, chunk=5,
            block_sparse=True)
        np.testing.assert_array_equal(dense[0], sparse[0])
        assert dense[1] == sparse[1]


# ---------------------------------------------------------------------------
# Precision-aware traffic / workset models (what plan.explain() reports)
# ---------------------------------------------------------------------------

class TestTrafficModel:
    def test_packed_moves_32x_fewer_feature_bytes(self):
        """Acceptance: >= 8x fewer feature-slab bytes (model gives 32x)."""
        spec = dreg.get_fused("jaccard.fusedk.pallas")
        n, d = 1024, 512
        f32 = dreg.fused_feat_traffic_bytes(spec, n, d,
                                            dreg.precision_tuning("f32"))
        packed = dreg.fused_feat_traffic_bytes(
            spec, n, d, dreg.precision_tuning("packed"))
        assert f32 / packed == 32.0
        assert f32 / packed >= 8.0

    def test_precision_ordering(self):
        spec = dreg.get_fused("braycurtis.fusedk.pallas")
        t = {tag: dreg.fused_feat_traffic_bytes(
                spec, 512, 256, dreg.precision_tuning(tag))
             for tag in ("f32", "bf16", "fp8")}
        assert t["fp8"] < t["bf16"] < t["f32"]
        assert t["f32"] == 4 * t["fp8"] and t["f32"] == 2 * t["bf16"]
        w = {tag: dreg.fused_workset_bytes(
                spec, 512, 256, 64, 8, 256,
                dreg.precision_tuning(tag))
             for tag in ("f32", "bf16", "fp8")}
        assert w["fp8"] < w["bf16"] < w["f32"]

    def test_xla_kind_gets_no_precision_credit(self):
        """The one-jit sweep streams f32 regardless — the model must not
        flatter it (value parity only)."""
        spec = dreg.get_fused("braycurtis.fusedk.xla")
        f32 = dreg.fused_feat_traffic_bytes(spec, 512, 256,
                                            dreg.precision_tuning("f32"))
        fp8 = dreg.fused_feat_traffic_bytes(spec, 512, 256,
                                            dreg.precision_tuning("fp8"))
        assert f32 == fp8
        # and the round-tripped copy COSTS workset instead
        assert dreg.fused_workset_bytes(spec, 512, 256, 64, 8, 256,
                                        dreg.precision_tuning("fp8")) > \
            dreg.fused_workset_bytes(spec, 512, 256, 64, 8, 256,
                                     dreg.precision_tuning("f32"))

    def test_plan_explain_reports_precisions(self):
        pl = pipeline.plan_pipeline(512, 64, 100, 8, backend="cpu",
                                    metric="jaccard",
                                    materialize="fused-kernel",
                                    fused_impl="pallas",
                                    fused_tuning={"feat_packed": 1})
        text = pl.explain()
        for tag in ("f32", "bf16", "fp8", "packed"):
            assert tag in text
        assert "packed" in text.split("<- planned")[0].splitlines()[-1]
        # non-jaccard metrics must not advertise a packed row
        pl2 = pipeline.plan_pipeline(512, 64, 100, 8, backend="cpu",
                                     metric="euclidean",
                                     materialize="fused-kernel",
                                     fused_impl="pallas")
        assert "packed" not in pl2.explain()
