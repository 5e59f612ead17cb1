"""The plain reference: its permutations are the program's documented
draws, its distances are exact, and its control fails the comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.spatial import distance as sd

from benchtools import REPO, TINY, TINY_PERMS, tiny_root
from conftest import run_subprocess

from bench import data, manifest, reference


def test_labels_match_the_programs_draws():
    from repro.core import permutations
    key = jax.random.key(123)
    g = jnp.asarray(np.arange(40) % 5, jnp.int32)
    prog = np.asarray(permutations.permutation_batch(key, g, 0, 20))
    ref = reference.labels(key, np.asarray(g), list(range(20)))
    np.testing.assert_array_equal(prog, ref)


@pytest.mark.parametrize("metric", ["braycurtis", "jaccard"])
def test_distances_are_exact(metric):
    x, _ = data.counts(jax.random.key(3), n=50, d=40, n_groups=4,
                       density=0.2, scale=10.0, effect=1.0)
    got = np.asarray(data.distances(x, metric=metric), np.float64)
    xn = np.asarray(x, np.float64)
    want = sd.squareform(sd.pdist(xn if metric == "braycurtis" else xn > 0,
                                  metric))
    np.testing.assert_allclose(got, want, rtol=2e-7, atol=0)
    assert np.all(np.diag(got) == 0) and np.array_equal(got, got.T)


def test_counts_are_whole_and_seeded():
    a, ga = data.counts(jax.random.key(9), n=64, d=16, n_groups=8,
                        density=0.1, scale=10.0, effect=0.0)
    b, gb = data.counts(jax.random.key(9), n=64, d=16, n_groups=8,
                        density=0.1, scale=10.0, effect=0.0)
    a = np.asarray(a)
    assert np.array_equal(a, np.asarray(b))
    assert np.array_equal(np.asarray(ga), np.asarray(gb))
    assert np.all(a == np.floor(a)) and a.max() <= data.COUNT_CAP
    assert np.all((a > 0).sum(axis=1) >= 1)
    assert set(np.asarray(ga).tolist()) == set(range(8))


def test_seeds_take_any_size():
    for s in (0, 2**31 + 1, 2**40 + 3):
        k1, k2, rng = data.seeds(s)
        k1b, _, _ = data.seeds(s)
        assert np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k1b))


def test_s_w_fp64_against_a_loop():
    rng = np.random.default_rng(0)
    n, a = 30, 3
    d = rng.random((n, n)).astype(np.float32)
    d = np.triu(d, 1) + np.triu(d, 1).T
    g = rng.integers(0, a, n)
    g[:a] = np.arange(a)
    sw, st = reference.s_w_fp64(jnp.asarray(d), g[None, :], a, rows=7)
    d64 = d.astype(np.float64) ** 2
    want = sum(d64[i, j] / np.sum(g == g[i])
               for i in range(n) for j in range(i + 1, n) if g[i] == g[j])
    assert sw[0] == pytest.approx(want, rel=1e-12)
    assert st == pytest.approx(np.triu(d64, 1).sum() / n, rel=1e-12)


@pytest.mark.parametrize("cell", ["emp_matrix.p3999", "emp_features.jaccard"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, cell):
    """The control (the reference in bfloat16) fails a number the cell
    compares; the program on the same seed passes every one."""
    c = manifest.load_cell(cell, tiny_root(tmp_path))
    state = c.entry.setup(c.config, c.traffic, 77)
    prog = [c.entry.run_test(state, t)[0] for t in (1, 2)]
    dm = c.entry.reference_matrix(state)
    ctl = [reference.control_answer(dm, state.grouping,
                                    data.test_key(state.perm_key, t), t,
                                    TINY_PERMS, c.config["n_groups"])
           for t in (1, 2)]
    for answers, want in ((prog, True), (ctl, False)):
        checks, failed = reference.compare(
            answers, dm, np.asarray(state.grouping), state.perm_key,
            TINY_PERMS, c.config["n_groups"], c.config["limits"],
            c.config["check_perms"], np.random.default_rng(1))
        assert all(x.ok for x in checks) is want, checks
        assert (failed == 0) is want
    assert TINY["n"] == dm.shape[0]


def test_reference_reads_a_row_sharded_padded_matrix():
    """Over D born row-sharded on 4 devices with pad rows, as the
    distributed path takes it, the reference agrees with the program."""
    code = """
import sys; sys.path.insert(0, %r)
import numpy as np
from bench import data, reference
from repro.core import distributed
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_ways=4)
dk, pk, rng = data.seeds(11)
x, g = data.counts(dk, n=203, d=32, n_groups=8, density=0.3, scale=10.0,
                   effect=1.0)
dm = distributed.distance_matrix_sharded(mesh, x, "braycurtis")
assert dm.shape == (204, 203)
res = distributed.permanova_distributed(mesh, dm, g, n_perms=99,
                                        key=data.test_key(pk, 1), impl="auto")
ans = [reference.Answer(1, np.asarray(res.f_perms, np.float64),
                        float(res.p_value), float(res.s_t))]
checks, failed = reference.compare(ans, dm, np.asarray(g), pk,
                                   ans[0].f.shape[0] - 1, 8,
                                   {"sw": 2e-5, "s_t": 2e-5, "p": 0}, 16, rng)
assert failed == 0 and all(c.ok for c in checks), checks
print("ok")
""" % REPO
    assert run_subprocess(code, devices=4, timeout=300).strip() == "ok"


@pytest.mark.parametrize("n_perms,k,chunk", [(3999, 8, 2668), (999, 16, 143),
                                             (63, 8, 16), (5, 8, 1)])
def test_sample_checks_every_chunk_of_every_test(n_perms, k, chunk):
    """Each test gets its observed grouping and k picks, one per stratum;
    every chunk of `chunk` permutations the program computes apart holds
    a pick of every test, where chunk >= 2 n_perms / k."""
    answers = [reference.Answer(test=t, f=np.zeros(n_perms + 1), p=1.0,
                                s_t=1.0) for t in (3, 4, 5)]
    strata = reference.strata(n_perms, k)
    assert strata[0][0] == 1 and strata[-1][1] == n_perms + 1
    assert all(a[1] == b[0] for a, b in zip(strata, strata[1:]))
    for seed in range(20):
        picks = reference.sample(answers, n_perms, k,
                                 np.random.default_rng(seed))
        for a in answers:
            ks = [p for t, p in picks if t == a.test]
            assert ks[0] == 0 and len(ks) == 1 + len(strata)
            assert all(1 <= p <= n_perms for p in ks[1:])
            if chunk >= 2 * n_perms / k:
                for lo in range(0, n_perms + 1, chunk):
                    hi = min(lo + chunk, n_perms + 1)
                    if hi - lo >= n_perms / k:      # a short last chunk
                        assert any(lo <= p < hi for p in ks[1:]), (lo, hi)
