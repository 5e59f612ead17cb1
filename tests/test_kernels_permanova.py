"""Pallas permanova_sw kernels vs the pure-jnp oracle: shape/dtype sweeps
in interpret mode (per-kernel allclose deliverable)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fstat, permutations
from repro.core.distributed import pad_to_multiple
from repro.kernels import common
from repro.kernels.permanova_sw import kernel, ops
from repro.kernels.permanova_sw.ref import sw_ref, sw_ref_f64

SHAPES = [
    # (n, n_groups, n_perms, tile, perm_block)
    (32, 2, 4, 16, 2),
    (48, 3, 7, 16, 4),
    (64, 5, 16, 32, 8),
    (96, 4, 6, 32, 3),
    (130, 2, 5, 32, 4),     # ragged: padding path
    (57, 7, 9, 16, 16),     # perm_block > n_perms
    (24, 3, 5, 32, 8),      # one tile (nt = 1): the triangle is one step
    (48, 17, 6, 16, 4),     # 17 groups pad to 24 one-hot rows; nt = 3
    (200, 17, 10, 64, 8),   # 17 groups, ragged, nt = 4
]


def _instance(n, g, p, seed=0):
    rng = np.random.default_rng(seed)
    d = rng.random((n, n)).astype(np.float32)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[:g] = np.arange(g)
    inv_gs = np.asarray(permutations.inv_group_sizes(
        jnp.asarray(grouping), g))
    gperms = np.stack([rng.permutation(grouping) for _ in range(p)])
    gperms[0] = grouping
    return jnp.asarray(d * d), jnp.asarray(gperms), jnp.asarray(inv_gs)


@pytest.mark.parametrize("variant", ops.VARIANTS)
@pytest.mark.parametrize("n,g,p,tile,pb", SHAPES)
def test_kernel_matches_oracle(variant, n, g, p, tile, pb):
    mat2, gperms, inv_gs = _instance(n, g, p, seed=n + g + p)
    ref = np.asarray(sw_ref(mat2, gperms, inv_gs))
    got = np.asarray(ops.permanova_sw(mat2, gperms, inv_gs, variant=variant,
                                      tile_r=tile, tile_c=tile,
                                      perm_block=pb))
    np.testing.assert_allclose(got, ref, rtol=5e-5, atol=1e-5)


@pytest.mark.parametrize("variant", ["matmul"])
def test_kernel_bf16_within_tolerance(variant):
    mat2, gperms, inv_gs = _instance(64, 4, 8, seed=3)
    ref64 = sw_ref_f64(mat2, gperms, inv_gs)
    got = np.asarray(ops.permanova_sw(
        mat2.astype(jnp.bfloat16), gperms, inv_gs, variant=variant,
        tile_r=32, tile_c=32, perm_block=4))
    rel = np.max(np.abs(got - ref64) / np.maximum(np.abs(ref64), 1e-6))
    assert rel < 5e-3, f"bf16 matmul rel err {rel}"


@pytest.mark.parametrize("n,g,p,tile", [(24, 3, 5, 32), (64, 4, 6, 32),
                                         (90, 17, 9, 32)])
def test_triangle_matches_full_tile_list(n, g, p, tile):
    """The whole matrix (upper triangle of tiles, weights 2 and 1) and the
    row-slab entry over all of it (the plain grid over every tile)
    agree."""
    mat2, gperms, inv_gs = _instance(n, g, p, seed=n * g)
    tri = np.asarray(ops.permanova_sw(mat2, gperms, inv_gs, variant="matmul",
                                      tile_r=tile, tile_c=tile,
                                      perm_block=8))
    full = np.asarray(ops.sw_matmul_rows_partial(
        mat2, 0, gperms, inv_gs, tile_r=tile, tile_c=tile, perm_block=8))
    np.testing.assert_allclose(tri, full, rtol=1e-6)


def _edge_instance(n, g, p, seed):
    """D (f32, zero diagonal) and p labellings (row 0 the observed one)
    drawn from g groups, the highest label always present, so the
    one-hot operand's last real row is used; with g > n some groups are
    empty and weigh 0."""
    rng = np.random.default_rng(seed)
    d = rng.random((n, n)).astype(np.float32)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[-1] = g - 1
    gperms = np.stack([rng.permutation(grouping) for _ in range(p)])
    gperms[0] = grouping
    inv_gs = np.asarray(permutations.inv_group_sizes(
        jnp.asarray(grouping), g))
    return d, gperms, inv_gs


# the two s_W kernels the benchmark cells run: the square one-hot kernel
# over the upper triangle of tiles, and the row-slab partial over every
# tile (here the whole matrix as one slab, row offset 0)
SW_KERNELS = {
    "triangle": lambda mat2, gperms, inv_gs, **kw: ops.permanova_sw(
        mat2, gperms, inv_gs, variant="matmul", **kw),
    "rows": lambda mat2, gperms, inv_gs, **kw: ops.sw_matmul_rows_partial(
        mat2, 0, gperms, inv_gs, **kw),
}


def _sw_against_algorithm1(kernel, d, gperms, inv_gs, **kw):
    got = np.asarray(SW_KERNELS[kernel](
        jnp.asarray(d * d), jnp.asarray(gperms), jnp.asarray(inv_gs), **kw))
    oracle = fstat.sw_algorithm1_numpy(d, gperms, inv_gs)
    np.testing.assert_allclose(got, oracle, rtol=5e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", sorted(SW_KERNELS))
@pytest.mark.parametrize("g", [8, 9, 16, 24, 25, 33])
@pytest.mark.parametrize("n", [32, 33, 95])
def test_group_padding_and_tile_edges(kernel, g, n):
    """Group counts on either side of a multiple of 8 (the one-hot pads
    G to round_up(G, 8) rows: 8 -> 8, 9 -> 16, 25 -> 32, 33 -> 40) at n
    of exactly one 32-tile, one past it, and one short of three."""
    d, gperms, inv_gs = _edge_instance(n, g, 6, seed=100 * n + g)
    _sw_against_algorithm1(kernel, d, gperms, inv_gs, tile_r=32, tile_c=32,
                           perm_block=8)


@pytest.mark.parametrize("kernel", sorted(SW_KERNELS))
@pytest.mark.parametrize("pb,p", [(16, 15), (16, 16), (16, 17),
                                  (64, 63), (64, 64), (64, 65)])
def test_perm_block_edges(kernel, pb, p):
    """Permutation counts one short of, equal to and one past the block:
    the square kernel's default block (16) and the row-slab kernel's in
    the four-chip cell (64). Pad permutations repeat the last one and are
    sliced off."""
    d, gperms, inv_gs = _edge_instance(40, 5, p, seed=pb + p)
    _sw_against_algorithm1(kernel, d, gperms, inv_gs, tile_r=32, tile_c=32,
                           perm_block=pb)


@pytest.mark.parametrize("slabs", [1, 2, 3, 4])
@pytest.mark.parametrize("n", [48, 49], ids=["even", "padded"])
def test_row_slabs_sum_to_oracle(slabs, n):
    """D's rows split into `slabs` slabs as the row-sharded program takes
    them (rows zero-padded to a multiple of the slab count, as
    core.distributed._rows_on_mesh pads them; n=49 leaves 1, 2 and 3 pad
    rows at 2, 3 and 4 slabs): the slab partials at their row offsets sum
    to the whole s_W. 17 groups, as in the four-chip cell."""
    d, gperms, inv_gs = _edge_instance(n, 17, 5, seed=slabs * n)
    rows, pad = pad_to_multiple(jnp.asarray(d * d), slabs, axis=0)
    assert rows.shape[0] == common.round_up(n, slabs) == n + pad
    n_local = rows.shape[0] // slabs
    parts = [np.asarray(ops.sw_matmul_rows_partial(
        rows[k * n_local:(k + 1) * n_local], k * n_local,
        jnp.asarray(gperms), jnp.asarray(inv_gs), tile_r=16, tile_c=16,
        perm_block=8)) for k in range(slabs)]
    oracle = fstat.sw_algorithm1_numpy(d, gperms, inv_gs)
    np.testing.assert_allclose(sum(parts), oracle, rtol=5e-5, atol=1e-5)


def test_tile_share_gauge():
    """sw.tile_share: nt(nt+1)/2 / nt^2 after a square call with square
    tiles, 1.0 after a row-slab call or with unequal tiles."""
    from repro import obs
    mat2, gperms, inv_gs = _instance(80, 3, 7, seed=5)
    obs.enable(trace=False, metrics=True)
    try:
        ops.permanova_sw(mat2, gperms, inv_gs, variant="matmul", tile_r=16,
                         tile_c=16, perm_block=8)
        assert obs.metrics.gauge_value("sw.tile_share") == 15 / 25
        ops.sw_matmul_rows_partial(mat2[:48], 16, gperms, inv_gs,
                                   tile_r=16, tile_c=16, perm_block=8)
        assert obs.metrics.gauge_value("sw.tile_share") == 1.0
        ops.permanova_sw(mat2, gperms, inv_gs, variant="matmul", tile_r=16,
                         tile_c=40, perm_block=8)
        assert obs.metrics.gauge_value("sw.tile_share") == 1.0
    finally:
        obs.disable()


def _split_values(kind):
    if kind == "instance":
        return _instance(200, 17, 1, seed=11)[0]
    if kind == "binades":
        rng = np.random.default_rng(12)
        mant = rng.random(4096) + 1.0
        return jnp.asarray((mant * 10.0 ** rng.uniform(-30, 30, 4096))
                           .astype(np.float32).reshape(32, 128))
    return jnp.zeros((16, 128), jnp.float32)


@pytest.mark.parametrize("kind", ["instance", "binades", "zeros"])
def test_split_bf16x3_reconstructs_f32(kind):
    """hi + mid + lo is the f32 tile bit for bit: squared distances of a
    test instance, values spread over 1e-30 .. 1e30, and zeros."""
    x = _split_values(kind)
    parts = jax.jit(kernel.split_bf16x3)(x)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    hi, mid, lo = (np.asarray(p, np.float64) for p in parts)
    back = (hi + mid + lo).astype(np.float32)
    np.testing.assert_array_equal(back.view(np.uint32),
                                  np.asarray(x).view(np.uint32))
    assert np.all(np.abs(lo) <= np.abs(mid))


@pytest.mark.parametrize("kernel_name", sorted(SW_KERNELS))
def test_f32_kernel_matches_float64(kernel_name):
    """The f32 one-hot kernel (three bf16 passes over the split tile) is
    within 1e-6 of a float64 sum, 17 groups; on the same instance the
    hi part alone (one pass over bf16(mat2)) misses by more than 1e-4."""
    mat2, gperms, inv_gs = _instance(96, 17, 8, seed=17)
    ref = sw_ref_f64(mat2, gperms, inv_gs)
    kw = dict(tile_r=32, tile_c=32, perm_block=8)
    got = np.asarray(SW_KERNELS[kernel_name](mat2, gperms, inv_gs, **kw))
    hi = np.asarray(SW_KERNELS[kernel_name](mat2.astype(jnp.bfloat16),
                                            gperms, inv_gs, **kw))
    assert np.max(np.abs(got - ref) / np.abs(ref)) < 1e-6
    assert np.max(np.abs(hi - ref) / np.abs(ref)) > 1e-4


@pytest.mark.parametrize("dtype,passes", [(jnp.float32, 3),
                                          (jnp.bfloat16, 1)])
@pytest.mark.parametrize("kernel_name", sorted(SW_KERNELS))
def test_mxu_passes_gauge(kernel_name, dtype, passes):
    """sw.mxu_passes: the bf16 MXU passes a step of the one-hot kernel
    takes, 3 for f32 mat2 (the exact split) and 1 for bf16."""
    from repro import obs
    mat2, gperms, inv_gs = _instance(72, 3, 5, seed=6)
    obs.enable(trace=False, metrics=True)
    try:
        obs.metrics.gauge_set("sw.mxu_passes", 0)
        SW_KERNELS[kernel_name](mat2.astype(dtype), gperms, inv_gs,
                                tile_r=16, tile_c=16, perm_block=8)
        assert obs.metrics.gauge_value("sw.mxu_passes") == passes
    finally:
        obs.disable()


def test_kernels_agree_with_each_other():
    mat2, gperms, inv_gs = _instance(96, 3, 12, seed=9)
    outs = [np.asarray(ops.permanova_sw(mat2, gperms, inv_gs, variant=v,
                                        tile_r=32, tile_c=32, perm_block=4))
            for v in ops.VARIANTS]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=5e-5)


def test_kernel_plugs_into_full_test(small_study):
    import jax.numpy as jnp
    from repro.core import permanova
    dm, grouping, _, _ = small_study
    res_ref = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=19,
                        sw_impl="brute")
    res_k = permanova(jnp.asarray(dm), jnp.asarray(grouping), n_perms=19,
                      sw_fn=ops.make_sw_fn("matmul", tile_r=32, tile_c=32,
                                           perm_block=4))
    np.testing.assert_allclose(float(res_k.f_stat), float(res_ref.f_stat),
                               rtol=1e-4)
    assert float(res_k.p_value) == float(res_ref.p_value)


def test_interpret_mode_only_on_cpu(monkeypatch):
    from repro.kernels import common
    assert common.interpret_mode() is True                # CPU backend
    assert common.interpret_mode(False) is False          # described-TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.interpret_mode() is False
    with pytest.raises(ValueError, match="off on TPU"):
        common.interpret_mode(True)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError):
        common.interpret_mode()
