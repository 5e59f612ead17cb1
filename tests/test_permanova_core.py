"""Core PERMANOVA correctness: every s_W variant against the literal
Algorithm 1 transcription, full-test statistics, p-value semantics."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fstat, permutations, s_total, f_from_sw, \
    p_value_from_null, permanova
from repro.core.permanova import SW_IMPLS
from repro.data import synthetic_study

N_PERMS = 9


def _perms(grouping, n):
    return np.asarray(permutations.permutation_batch(
        jax.random.key(3), jnp.asarray(grouping), 0, n))


class TestSwVariants:
    @pytest.mark.parametrize("impl", sorted(SW_IMPLS))
    def test_matches_algorithm1(self, small_study, impl):
        dm, grouping, inv_gs, mat2 = small_study
        gperms = _perms(grouping, N_PERMS)
        oracle = fstat.sw_algorithm1_numpy(dm, gperms, inv_gs)
        got = np.asarray(SW_IMPLS[impl](
            jnp.asarray(mat2), jnp.asarray(gperms), jnp.asarray(inv_gs)))
        np.testing.assert_allclose(got, oracle, rtol=2e-5)

    def test_full_matrix_form_equals_triangle(self, small_study):
        dm, grouping, inv_gs, mat2 = small_study
        gperms = _perms(grouping, 4)
        tri = np.asarray(fstat.sw_brute(jnp.asarray(mat2),
                                        jnp.asarray(gperms),
                                        jnp.asarray(inv_gs)))
        full = np.asarray(jax.vmap(
            lambda g: fstat.sw_full_one(jnp.asarray(mat2), g,
                                        jnp.asarray(inv_gs)))(
            jnp.asarray(gperms)))
        np.testing.assert_allclose(full, tri, rtol=2e-5)

    def test_row_partials_sum_to_total(self, small_study):
        dm, grouping, inv_gs, mat2 = small_study
        gperms = _perms(grouping, 5)
        oracle = fstat.sw_algorithm1_numpy(dm, gperms, inv_gs)
        for fn in (fstat.sw_rows_partial, fstat.sw_matmul_rows_partial):
            parts = [np.asarray(fn(jnp.asarray(mat2[o:o + 16]), o,
                                   jnp.asarray(gperms),
                                   jnp.asarray(inv_gs)))
                     for o in (0, 16, 32)]
            np.testing.assert_allclose(sum(parts), oracle, rtol=2e-5)


class TestFullTest:
    def test_identity_perm_first(self, small_study):
        dm, grouping, _, _ = small_study
        gperms = _perms(grouping, 3)
        np.testing.assert_array_equal(gperms[0], grouping)

    def test_partition_identity(self, small_study):
        """s_A + s_W = s_T for every permutation."""
        dm, grouping, inv_gs, mat2 = small_study
        gperms = _perms(grouping, N_PERMS)
        s_w = np.asarray(fstat.sw_brute(jnp.asarray(mat2),
                                        jnp.asarray(gperms),
                                        jnp.asarray(inv_gs)))
        st = float(s_total(jnp.asarray(mat2)))
        # s_A is defined as s_T - s_W: check s_W <= s_T (non-negativity
        # of the between-group term) for the observed grouping
        assert np.all(s_w <= st + 1e-4)

    def test_p_value_bounds_and_f_positive(self, small_study):
        dm, grouping, _, _ = small_study
        res = permanova(jnp.asarray(dm), jnp.asarray(grouping),
                        n_perms=49, sw_impl="brute")
        assert 1.0 / 50 <= float(res.p_value) <= 1.0
        assert float(res.f_stat) > 0
        assert res.f_perms.shape == (50,)

    def test_impls_agree_end_to_end(self, small_study):
        dm, grouping, _, _ = small_study
        results = {impl: permanova(jnp.asarray(dm), jnp.asarray(grouping),
                                   n_perms=29, sw_impl=impl)
                   for impl in sorted(SW_IMPLS)}
        f = [float(r.f_stat) for r in results.values()]
        p = [float(r.p_value) for r in results.values()]
        np.testing.assert_allclose(f, f[0], rtol=1e-4)
        np.testing.assert_allclose(p, p[0], atol=1e-6)

    def test_planted_effect_gives_small_p(self):
        from repro.core import distance
        from repro.data.microbiome import synthetic_study
        x, grouping = synthetic_study(60, 40, 2, effect_size=5.0, seed=1)
        dm = distance.braycurtis(jnp.asarray(x))
        res = permanova(dm, jnp.asarray(grouping), n_perms=99)
        assert float(res.p_value) <= 0.05

    def test_null_p_is_not_extreme(self):
        from repro.core import distance
        from repro.data.microbiome import synthetic_study
        x, grouping = synthetic_study(60, 40, 2, effect_size=0.0, seed=2)
        dm = distance.braycurtis(jnp.asarray(x))
        res = permanova(dm, jnp.asarray(grouping), n_perms=99,
                        key=jax.random.key(11))
        assert float(res.p_value) > 0.05

    @pytest.mark.parametrize("n_perms", [1, 9, 99, 999])
    def test_all_ties_give_p_of_one_exactly(self, n_perms):
        """Every off-diagonal distance 1.0 and four groups of 8: every
        partial sum is exact in float32, so every permutation's F is the
        observed F bit for bit and the >= rule counts all of them."""
        n, size = 32, 8
        dm = jnp.ones((n, n), jnp.float32) - jnp.eye(n, dtype=jnp.float32)
        grouping = jnp.repeat(jnp.arange(n // size, dtype=jnp.int32), size)
        res = permanova(dm, grouping, n_perms=n_perms,
                        sw_impl="pallas_matmul", key=jax.random.key(n_perms))
        f = np.asarray(res.f_perms)
        assert f.shape == (n_perms + 1,)
        np.testing.assert_array_equal(f, np.full_like(f, f[0]))
        assert float(res.p_value) == 1.0


@pytest.mark.parametrize("f_perms,p", [
    ([2.0, 2.0, 2.0, 1.0, 1.5, 2.0, 0.5, 3.0], 5 / 8),
    ([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0], 1.0),
    ([9.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0], 1 / 8),
], ids=["ties_at_f0", "all_above", "all_below"])
def test_p_value_from_null_counts_ties(f_perms, p):
    """(#{F_k >= F_0} + 1) / (P + 1) over 7 permutations, index 0 the
    observed F: ties count as at least as extreme."""
    assert float(p_value_from_null(jnp.asarray(f_perms, jnp.float32))) == p


def test_synthetic_study_effect_controls_structure():
    x0, g0 = synthetic_study(40, 30, 2, effect_size=0.0, seed=0)
    x1, g1 = synthetic_study(40, 30, 2, effect_size=5.0, seed=0)
    np.testing.assert_array_equal(g0, g1)
    assert x1.sum() > x0.sum()          # planted bump adds abundance
    assert x0.shape == (40, 30)


class TestPermutations:
    def test_group_sizes_invariant(self, small_study):
        _, grouping, _, _ = small_study
        gperms = _perms(grouping, 20)
        base = np.bincount(grouping, minlength=3)
        for g in gperms:
            np.testing.assert_array_equal(np.bincount(g, minlength=3), base)

    def test_global_index_folding_shard_equivalence(self, small_study):
        """Any shard holding range [lo,hi) generates the same labels."""
        _, grouping, _, _ = small_study
        key = jax.random.key(5)
        g = jnp.asarray(grouping)
        full = np.asarray(permutations.permutation_batch(key, g, 0, 16))
        lo_hi = np.asarray(permutations.permutation_batch(key, g, 4, 12))
        np.testing.assert_array_equal(full[4:12], lo_hi)


def _index_gather_batch(key, grouping, lo, chunk, identity_first):
    """The draw as an index permutation gathered through: the form the
    labels are pinned to, bit for bit."""
    n = grouping.shape[0]
    idx = lo + jnp.arange(chunk)
    perms = jnp.stack([
        grouping[jax.random.permutation(jax.random.fold_in(key, i), n)]
        for i in idx])
    if identity_first:
        perms = jnp.where((idx == 0)[:, None], grouping[None, :], perms)
    return perms


class TestLabelsRideTheSort:
    """The labels are carried through the shuffle's sorts as their
    payload; they equal the index permutation's gather bit for bit."""

    # 1625 / 1626: either side of the shuffle's step from one sort
    # round to two
    @pytest.mark.parametrize("identity_first", [True, False])
    @pytest.mark.parametrize("n", [1, 2, 7, 1625, 1626, 25145])
    def test_batch_is_the_index_gather(self, n, identity_first):
        rng = np.random.default_rng(n)
        # distinct labels: equal labels mean the same permutation
        grouping = jnp.asarray(rng.permutation(n), jnp.int32)
        key = jax.random.key(1234567)
        step = jax.jit(permutations.permutation_batch_dyn,
                       static_argnames=("chunk", "identity_first"))
        for lo in (0, 37):
            got = step(key, grouping, jnp.int32(lo), chunk=5,
                       identity_first=identity_first)
            want = _index_gather_batch(key, grouping, lo, 5, identity_first)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("n_valid", [1, 2, 57, 99, 100])
    def test_masked_draw_is_the_argsort_gather(self, n_valid):
        n = 100
        rng = np.random.default_rng(n_valid)
        grouping = np.full(n, 7, np.int32)          # sentinel on the pad
        grouping[:n_valid] = rng.integers(0, 5, n_valid)
        grouping = jnp.asarray(grouping)
        draw = jax.jit(permutations.masked_permute_grouping)

        def old(key, nv):
            u = jax.random.uniform(key, (n,))
            u = jnp.where(jnp.arange(n) < nv, u, jnp.inf)
            return grouping[jnp.argsort(u)]

        for k in range(5):
            key = jax.random.fold_in(jax.random.key(99), k)
            got = np.asarray(draw(key, grouping, jnp.int32(n_valid)))
            np.testing.assert_array_equal(
                got, np.asarray(old(key, jnp.int32(n_valid))))
            np.testing.assert_array_equal(got[n_valid:], 7)


OLD_DRAW = r"""
import json
import jax, jax.numpy as jnp
import numpy as np
from repro import engine
from repro.core import distance, distributed, permutations
from repro.data.microbiome import synthetic_study
from repro.launch.mesh import make_mesh

N, G, PERMS = 203, 5, 99
x, g = synthetic_study(N, 16, G, effect_size=0.3, seed=5)
x, g = jnp.asarray(x, jnp.float32), jnp.asarray(g, jnp.int32)
dm = distance.braycurtis(x)
mesh = make_mesh((2, 2), ("data", "model"))
dm_rows = distributed.distance_matrix_sharded(mesh, x, "braycurtis")
key = jax.random.key(2024)
studies = {}
for n_groups in (2, 17):
    xs, gs = synthetic_study(N, 16, n_groups, effect_size=0.3, seed=5)
    studies[n_groups] = (distance.braycurtis(jnp.asarray(xs, jnp.float32)),
                         jnp.asarray(gs, jnp.int32))

def runs():
    out = {}
    for name, chunk in (("engine.run", None), ("engine.run.chunked", 32)):
        r = engine.run(dm, g, n_perms=PERMS, key=key, chunk=chunk)
        out[name] = (np.asarray(r.f_perms), float(r.p_value))
    for n_groups, (dm_s, g_s) in studies.items():
        for chunk in (1, 7, PERMS + 1):
            r = engine.run(dm_s, g_s, n_perms=PERMS, key=key, chunk=chunk)
            out[f"chunk{chunk}.groups{n_groups}"] = (np.asarray(r.f_perms),
                                                     float(r.p_value))
    r = distributed.permanova_distributed(mesh, dm_rows, g, n_perms=PERMS,
                                          key=key)
    out["distributed"] = (np.asarray(r.f_perms), float(r.p_value))
    return out

new = runs()

def gather_draw(key, grouping):
    perm = jax.random.permutation(key, grouping.shape[0])
    return grouping[perm]

permutations.permute_grouping = gather_draw
jax.clear_caches()
old = runs()
print(json.dumps({k: {"f_bits": bool(np.array_equal(new[k][0], old[k][0])),
                      "n_f": int(new[k][0].shape[0]),
                      "p": [new[k][1], old[k][1]]} for k in new}))
"""


@pytest.fixture(scope="module")
def old_draw_runs():
    from conftest import run_subprocess
    text = run_subprocess(OLD_DRAW, devices=4, timeout=600)
    return json.loads(text.strip().splitlines()[-1])


@pytest.mark.parametrize("entry", [
    "engine.run", "engine.run.chunked", "distributed",
    # chunks of one permutation, ragged chunks, and the whole sweep in one
    # chunk (P + 1 = 100 labels), at 2 groups and at the paper's 17
    "chunk1.groups2", "chunk7.groups2", "chunk100.groups2",
    "chunk1.groups17", "chunk7.groups17", "chunk100.groups17"])
def test_f_and_p_equal_the_index_gather_draw(old_draw_runs, entry):
    """At a fixed key, every F and the p of a whole test equal those of
    the same program drawing its labels by the index gather."""
    r = old_draw_runs[entry]
    assert r["n_f"] == 100
    assert r["f_bits"]
    assert r["p"][0] == r["p"][1]
