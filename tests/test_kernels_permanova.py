"""Pallas permanova_sw kernels vs the pure-jnp oracle: shape/dtype sweeps
in interpret mode (per-kernel allclose deliverable)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import permutations
from repro.kernels.permanova_sw import ops
from repro.kernels.permanova_sw.ref import sw_ref, sw_ref_f64

SHAPES = [
    # (n, n_groups, n_perms, tile, perm_block)
    (32, 2, 4, 16, 2),
    (48, 3, 7, 16, 4),
    (64, 5, 16, 32, 8),
    (96, 4, 6, 32, 3),
    (130, 2, 5, 32, 4),     # ragged: padding path
    (57, 7, 9, 16, 16),     # perm_block > n_perms
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
