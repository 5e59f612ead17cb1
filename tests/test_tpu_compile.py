"""Mosaic compiles of the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed here; the chip is described, not attached,
so these tests need no accelerator. Each lowers a kernel at the widths a
TPU plan uses — the paper's n=25145 padded to the 256 tile, the pipeline
registry's megakernel tiles — and compiles it in this test's own
process. A refused block shape, an unsupported layout or a kernel that
outgrows VMEM fails here instead of on the chip. Interpret mode is
switched off explicitly: the backend of this process is the CPU.
"""

import jax
import jax.numpy as jnp
import pytest

PAPER_N_PAD = 25344          # 25145 rounded up to the 256 tile
P_TOTAL = 4000               # 3999 permutations + the observed labels
N_GROUPS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    # a described-device compile cannot be read back from a persistent
    # cache without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("variant", ["matmul", "permblock", "brute"])
def test_permanova_sw_kernel_paper_width(one_chip, variant):
    from repro.engine import registry
    from repro.kernels.permanova_sw import ops
    tuning = dict(registry.get(f"pallas_{variant}").tuning)

    def fn(m2, g, w):
        return ops.permanova_sw(m2, g, w, variant=variant, interpret=False,
                                **tuning)

    txt = _compile_text(
        fn, _spec(one_chip, (PAPER_N_PAD, PAPER_N_PAD), jnp.float32),
        _spec(one_chip, (P_TOTAL, PAPER_N_PAD), jnp.int32),
        _spec(one_chip, (N_GROUPS,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_pallas_matmul_row_slab_four_chip_width(one_chip):
    """One device's row slab of the four-chip matrix path: n = 65536 rows
    over 'model' = 4, 1000 permutations (core.distributed)."""
    from repro.engine import registry
    from repro.kernels.permanova_sw import ops
    n, rows = 65536, 16384
    tuning = dict(registry.get("pallas_matmul").tuning, perm_block=64)

    def fn(m2_rows, g, w):
        return ops.sw_matmul_rows_partial(m2_rows, rows, g, w,
                                          interpret=False, **tuning)

    txt = _compile_text(fn, _spec(one_chip, (rows, n), jnp.float32),
                        _spec(one_chip, (P_TOTAL, n), jnp.int32),
                        _spec(one_chip, (N_GROUPS,), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("metric", ["braycurtis", "jaccard"])
def test_fused_megakernel_planned_tiles(one_chip, metric):
    from repro.kernels.fused_sw import ops
    from repro.pipeline import registry
    tuning = dict(registry.get_fused(f"{metric}.fusedk.pallas").tuning)
    n, d, p = 25145, 1024, 1000

    def fn(x, g, w):
        return ops.fused_sw_rows(x, x, g, g, w, 0, metric=metric,
                                 interpret=False, **tuning)

    txt = _compile_text(fn, _spec(one_chip, (n, d), jnp.float32),
                        _spec(one_chip, (p, n), jnp.int32),
                        _spec(one_chip, (N_GROUPS,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_fused_design_megakernel_planned_tiles(one_chip):
    from repro.kernels.fused_sw import ops
    from repro.pipeline import registry
    tuning = dict(registry.get_fused("braycurtis.fusedk.pallas").tuning)
    n, d, p, k = 4096, 256, 64, 5

    def fn(x, v):
        return ops.fused_sw_rows_cols(x, x, v, v, 0, metric="braycurtis",
                                      interpret=False, **tuning)

    txt = _compile_text(fn, _spec(one_chip, (n, d), jnp.float32),
                        _spec(one_chip, (p, n, k), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("metric,packed", [("braycurtis", 0),
                                           ("jaccard", 1)])
def test_distance_kernel_planned_tiles(one_chip, metric, packed):
    from repro.kernels.distance import ops
    from repro.pipeline import registry
    tuning = {k: v for k, v in
              registry.get(f"{metric}.pallas").tuning.items()
              if k != "packed"}

    def fn(x):
        return ops.pairwise_distance_rows(x[:512], x, metric=metric,
                                          packed=packed, interpret=False,
                                          **tuning)

    txt = _compile_text(fn, _spec(one_chip, (4096, 1024), jnp.float32))
    assert "tpu_custom_call" in txt
