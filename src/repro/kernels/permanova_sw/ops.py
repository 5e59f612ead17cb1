"""jit'd wrappers around the permanova_sw Pallas kernels.

Handles the padding contract, variant dispatch, and interpret-mode
selection (kernels.common.interpret_mode: the interpreter on CPU, compiled
on TPU). These wrappers are the `sw_fn` plug-ins for
core.permanova.permanova(...).

Design subsystem note: these kernels build the one-hot factor from int
labels IN-KERNEL, so they serve every LABELS-mode design — including
strata-restricted permutations, whose labels are generated outside and
arrive through the same (n_perms, n) operand. DENSE designs (covariates /
weights / multi-factor, core.design) need the per-column basis contraction
instead; the engine registry marks these impls label-only (`cols=None`)
and the planner routes dense designs to the matmul-family companions (the
fused_sw megakernel has a native dense variant, `fused_sw_cols_pallas`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import common as _c
from repro.kernels.permanova_sw import kernel as _k
from repro.obs import metrics as _metrics

VARIANTS = ("brute", "permblock", "matmul")


def _pad_inputs(mat2, groupings, *, tile, perm_block):
    n_perms, n = groupings.shape
    n_pad = _c.round_up(n, tile) - n
    p_pad = _c.round_up(n_perms, perm_block) - n_perms
    if n_pad:
        mat2 = jnp.pad(mat2, ((0, n_pad), (0, n_pad)))
        groupings = jnp.pad(groupings, ((0, 0), (0, n_pad)))
    if p_pad:
        groupings = jnp.pad(groupings, ((0, p_pad), (0, 0)), mode="edge")
    return mat2, groupings, n_perms


@functools.partial(jax.jit, static_argnames=(
    "variant", "tile_r", "tile_c", "perm_block", "interpret"))
def permanova_sw(mat2, groupings, inv_group_sizes, *, variant="matmul",
                 tile_r=256, tile_c=256, perm_block=16,
                 interpret: bool | None = None):
    """s_W for a batch of permutations via the Pallas kernel `variant`.

    mat2:            (n, n) squared distances, zero diagonal (f32 or bf16
                     for the matmul variant; accumulation is fp32).
    groupings:       (n_perms, n) int32 permuted labels.
    inv_group_sizes: (n_groups,) f32.
    Returns (n_perms,) f32.

    Tiles are Mosaic-legal: the requested tile when n is longer, else one
    full-extent block; the perm block is the whole padded perm axis or a
    multiple of 8 (brute reads its labels in blocks of 8 perms).
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    interpret = _c.interpret_mode(interpret)
    w = inv_group_sizes.astype(jnp.float32)
    if variant == "matmul":
        return _matmul_sw(mat2, groupings, groupings, w,
                          perm_block=perm_block, tile_r=tile_r,
                          tile_c=tile_c, interpret=interpret,
                          name=_k.SW_NAME, square=True)
    n = mat2.shape[0]
    n_perms = groupings.shape[0]
    tile_r = _c.pick_tile(n, tile_r)
    tile_c = _c.pick_tile(n, tile_c)
    perm_block = (8 if variant == "brute"
                  else min(perm_block, _c.round_up(n_perms, 8)))
    mat2, groupings, n_perms = _pad_inputs(
        mat2, groupings, tile=math.lcm(tile_r, tile_c), perm_block=perm_block)
    if variant == "brute":
        out = _k.sw_brute_pallas(mat2, groupings, w, tile_r=tile_r,
                                 tile_c=tile_c, interpret=interpret)
    else:
        out = _k.sw_permblock_pallas(mat2, groupings, w,
                                     perm_block=perm_block, tile_r=tile_r,
                                     tile_c=tile_c, interpret=interpret)
    return out[:n_perms, 0]


def _matmul_sw(mat2, g_rows, g_cols, w, *, perm_block, tile_r, tile_c,
               interpret, name, square=False):
    """s_W (P,) of mat2 (nr, nc) — the whole matrix or a slab of its rows
    — through sw_matmul_pallas. Pads rows, columns and permutations to
    the blocks (zero mat2 pad contributes nothing; pad permutations repeat
    the last and are sliced off) and weights the per-group totals.

    square: mat2 is the whole symmetric matrix and g_rows is g_cols, so
    the kernel visits only the upper triangle of tiles (off-diagonal ones
    weighted 2) when the tiles are square too; otherwise every tile. The
    totals are the full symmetric sum either way, hence the 0.5 below.
    The share of the tile grid the kernel visits goes to the
    `sw.tile_share` gauge when the call is traced (the host knows the
    shapes then), and the bf16 MXU passes of each step (3 for f32 mat2,
    1 for bf16: kernel.split_bf16x3) to `sw.mxu_passes`."""
    nr, nc = mat2.shape
    n_perms = g_cols.shape[0]
    tile_r = _c.pick_tile(nr, tile_r)
    tile_c = _c.pick_tile(nc, tile_c)
    perm_block = min(perm_block, _c.round_up(n_perms, 8))
    r_pad = _c.round_up(nr, tile_r) - nr
    c_pad = _c.round_up(nc, tile_c) - nc
    p_pad = _c.round_up(n_perms, perm_block) - n_perms
    if r_pad or c_pad:
        mat2 = jnp.pad(mat2, ((0, r_pad), (0, c_pad)))
    g_rows = jnp.pad(g_rows, ((0, 0), (0, r_pad)))
    g_cols = jnp.pad(g_cols, ((0, 0), (0, c_pad)))
    if p_pad:
        g_rows, g_cols = (jnp.pad(g, ((0, p_pad), (0, 0)), mode="edge")
                          for g in (g_rows, g_cols))
    triangle = square and tile_r == tile_c
    nt = mat2.shape[0] // tile_r
    _metrics.gauge_set("sw.tile_share",
                       (nt + 1) / (2 * nt) if triangle else 1.0)
    _metrics.gauge_set("sw.mxu_passes", 3 if mat2.dtype == jnp.float32 else 1)
    n_groups = w.shape[0]
    gp = _c.round_up(n_groups, 8)
    tot = _k.sw_matmul_pallas(mat2, g_rows, g_cols, triangle=triangle,
                              n_groups_pad=gp, perm_block=perm_block,
                              tile_r=tile_r, tile_c=tile_c,
                              interpret=interpret, name=name)
    w = jnp.pad(w, (0, gp - n_groups))
    return 0.5 * jnp.sum(tot[:n_perms] * w, axis=1)


@functools.partial(jax.jit, static_argnames=(
    "perm_block", "tile_r", "tile_c", "interpret"))
def sw_matmul_rows_partial(mat2_rows, row_offset, groupings,
                           inv_group_sizes, *, perm_block=16, tile_r=256,
                           tile_c=256, interpret: bool | None = None):
    """pallas_matmul's row-sharded partial: half the (i != j) same-group
    sum over rows [row_offset, row_offset + nr) of mat2, for shard_map
    bodies (the signature of fstat.sw_matmul_rows_partial); the psum over
    row shards is the global s_W."""
    nr = mat2_rows.shape[0]
    # one zero slab past the end: the last shard's window never clamps
    # back onto real rows when n is not a multiple of the shard count
    g_rows = jax.lax.dynamic_slice(
        jnp.pad(groupings, ((0, 0), (0, nr))), (0, row_offset),
        (groupings.shape[0], nr))
    return _matmul_sw(mat2_rows, g_rows, groupings,
                      inv_group_sizes.astype(jnp.float32),
                      perm_block=perm_block, tile_r=tile_r, tile_c=tile_c,
                      interpret=_c.interpret_mode(interpret),
                      name=_k.SW_ROWS_NAME)


def make_sw_fn(variant: str = "matmul", **kw):
    """Adapter producing the (mat2, groupings, inv_gs) -> s_W signature that
    core.permanova.permanova(sw_fn=...) expects."""
    def fn(mat2, groupings, inv_gs):
        return permanova_sw(mat2, groupings, inv_gs, variant=variant, **kw)
    return fn
