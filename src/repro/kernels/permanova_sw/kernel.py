"""Pallas TPU kernels for the PERMANOVA pseudo-F partial statistic s_W.

Three dataflows, mirroring the paper's study (DESIGN.md section 2):

  brute      paper Algorithm 3 (the GPU winner on MI300A): grid =
             (perm, row-tile, col-tile); each permutation re-streams the
             mat^2 tiles HBM->VMEM. VPU masked square-accumulate.
             HBM traffic ~= 4 * n^2 * n_perms bytes.

  permblock  the paper's CPU tiling insight transplanted to TPU: grid =
             (perm-block, row-tile, col-tile); ONE VMEM-resident mat^2 tile
             serves a BLOCK of P permutations (VMEM plays the role of the
             MI300A's L2). HBM traffic divided by P.

  matmul     beyond-paper MXU formulation: the grouping indicator becomes a
             one-hot matmul, so each mat^2 tile feeds a (TR,TC)x(TC,G*P)
             systolic contraction. Arithmetic intensity ~P*G/2 flop/byte —
             past the v5e ridge point for P*G >= ~512 (see DESIGN.md sec. 3).

Grid convention (TPU): the LAST grid dimension is innermost. All kernels
accumulate over the (row-tile, col-tile) inner dims into an output block
indexed only by the outer perm dim — the Pallas-safe write-once-per-block
accumulation pattern (init at first inner step via pl.when).

Layout (what Mosaic accepts): every value is 2-D. Label blocks are
(rows, tile) with the sample axis on lanes; the sample axis a mask needs
down the sublanes comes from a 2-D transpose of the block. Outputs are
2-D slabs sliced by ops.py: brute/permblock write each permutation's s_W
broadcast across one 128-lane row, matmul writes lane-dense per-(perm,
group) totals that ops.py weights by the inverse group sizes.

Padding contract (enforced by ops.py): n padded to the tile multiple with
ZERO rows/cols in mat2 (zero distances contribute nothing regardless of the
pad labels); n_perms padded to the perm-block multiple by repeating the last
permutation (excess entries sliced off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as _c

LANES = 128

# The HLO names of the s_W custom calls, set by each pallas_call's `name`.
# A device trace finds the kernels by them (bench/metrics/sw_roofline.py).
SW_NAME = "permanova_sw"
SW_ROWS_NAME = "sw_matmul_rows_partial"


def _row_weights(w_ref, g, n_groups: int):
    """w[g] elementwise, w read from SMEM (G is small and static)."""
    out = jnp.zeros(g.shape, jnp.float32)
    for k in range(n_groups):
        out = jnp.where(g == k, w_ref[0, k], out)
    return out


def _upper(i, j, tile_r, tile_c):
    rows = i * tile_r + jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 0)
    cols = j * tile_c + jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 1)
    return cols > rows


# ---------------------------------------------------------------------------
# brute: grid (n_perms, nti, ntj)
# ---------------------------------------------------------------------------

def _sw_brute_body(w_ref, g_row_ref, g_col_ref, m2_ref, o_ref, *,
                   tile_r: int, tile_c: int, n_groups: int):
    p = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    q = p % 8                                  # row of the 8-perm label block

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[pl.ds(q, 1), :] = jnp.zeros((1, LANES), jnp.float32)

    # this permutation's row labels down the sublanes: transpose the
    # (8, TR) block and keep column q
    g_rt = jnp.transpose(g_row_ref[...].astype(jnp.float32))   # (TR, 8)
    lane = jax.lax.broadcasted_iota(jnp.int32, g_rt.shape, 1)
    g_r = jnp.sum(jnp.where(lane == q, g_rt, 0.0), axis=1, keepdims=True)
    g_c = g_col_ref[pl.ds(q, 1), :].astype(jnp.float32)        # (1, TC)
    # strict upper triangle + same-group indicator (paper Alg. 3 inner ifs)
    mask = (g_r == g_c) & _upper(i, j, tile_r, tile_c)
    w_row = _row_weights(w_ref, g_r, n_groups)                  # (TR, 1)
    s = jnp.sum(jnp.where(mask, m2_ref[...] * w_row, 0.0), keepdims=True)
    o_ref[pl.ds(q, 1), :] += jnp.broadcast_to(s, (1, LANES))


def sw_brute_pallas(mat2, groupings, w, *, tile_r, tile_c, interpret):
    """groupings: (P, n) with P a multiple of 8. Returns (P, 128) f32,
    each row one permutation's s_W broadcast across the lanes."""
    n_perms, n = groupings.shape
    n_groups = w.shape[-1]
    grid = (n_perms, n // tile_r, n // tile_c)
    kernel = functools.partial(_sw_brute_body, tile_r=tile_r, tile_c=tile_c,
                               n_groups=n_groups)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((8, tile_r), lambda p, i, j: (p // 8, i)),
            pl.BlockSpec((8, tile_c), lambda p, i, j: (p // 8, j)),
            pl.BlockSpec((tile_r, tile_c), lambda p, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((8, LANES), lambda p, i, j: (p // 8, 0)),
        out_shape=jax.ShapeDtypeStruct((n_perms, LANES), jnp.float32),
        interpret=interpret,
        name=SW_NAME,
    )(w.reshape(1, -1), groupings, groupings, mat2)


# ---------------------------------------------------------------------------
# permblock: grid (n_perm_blocks, nti, ntj); PB perms share each mat2 tile
# ---------------------------------------------------------------------------

def _sw_permblock_body(w_ref, g_row_ref, g_col_ref, m2_ref, o_ref, acc_ref,
                       *, tile_r: int, tile_c: int, n_groups: int,
                       nti: int, ntj: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m2 = m2_ref[...]                                             # (TR, TC)
    upper = _upper(i, j, tile_r, tile_c)
    g_r = g_row_ref[...]                                         # (PB, TR)
    g_rt = jnp.transpose(g_r.astype(jnp.float32))                # (TR, PB)
    w_rt = jnp.transpose(_row_weights(w_ref, g_r, n_groups))     # (TR, PB)
    g_c = g_col_ref[...].astype(jnp.float32)                     # (PB, TC)
    for q in range(g_r.shape[0]):      # the block's perms reuse the tile
        mask = (g_rt[:, q:q + 1] == g_c[q:q + 1, :]) & upper
        acc_ref[q:q + 1, :] += jnp.sum(
            jnp.where(mask, m2 * w_rt[:, q:q + 1], 0.0), axis=0,
            keepdims=True)

    @pl.when((i == nti - 1) & (j == ntj - 1))
    def _flush():
        o_ref[...] = jnp.broadcast_to(
            jnp.sum(acc_ref[...], axis=1, keepdims=True), o_ref.shape)


def sw_permblock_pallas(mat2, groupings, w, *, perm_block, tile_r, tile_c,
                        interpret):
    """Returns (P, 128) f32, each row one permutation's s_W broadcast
    across the lanes."""
    n_perms, n = groupings.shape
    nti, ntj = n // tile_r, n // tile_c
    kernel = functools.partial(_sw_permblock_body, tile_r=tile_r,
                               tile_c=tile_c, n_groups=w.shape[-1],
                               nti=nti, ntj=ntj)
    return pl.pallas_call(
        kernel,
        grid=(n_perms // perm_block, nti, ntj),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((perm_block, tile_r), lambda p, i, j: (p, i)),
            pl.BlockSpec((perm_block, tile_c), lambda p, i, j: (p, j)),
            pl.BlockSpec((tile_r, tile_c), lambda p, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((perm_block, LANES), lambda p, i, j: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((n_perms, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((perm_block, tile_c), jnp.float32)],
        interpret=interpret,
        name=SW_NAME,
    )(w.reshape(1, -1), groupings, groupings, mat2)


# ---------------------------------------------------------------------------
# matmul: grid (n_perm_blocks, nti, ntj); MXU one-hot contraction
# ---------------------------------------------------------------------------

def _sw_matmul_body(g_row_ref, g_col_ref, m2_ref, o_ref, acc_ref, *,
                    n_groups_pad: int, nti: int, ntj: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m2 = m2_ref[...]                                             # (TR, TC)
    e_r = _c.onehot_t(g_row_ref[...], n_groups_pad)              # (W, TR)
    e_c = _c.onehot_t(g_col_ref[...], n_groups_pad).astype(m2.dtype)
    # MXU: (W, TC) x (TR, TC)^T -> (W, TR), W = perm_block * groups
    acc_ref[...] += _c.dot(e_c, m2, _c.DOT_NT) * e_r

    @pl.when((i == nti - 1) & (j == ntj - 1))
    def _flush():
        o_ref[...] = jnp.sum(acc_ref[...], axis=1, keepdims=True).T[None]


def sw_matmul_pallas(mat2, g_rows, g_cols, *, n_groups_pad, perm_block,
                     tile_r, tile_c, interpret, name):
    """Per-(perm, group) same-group sums over the (i != j) entries of mat2.

    mat2 (nr, nc) is the whole matrix or a slab of its rows; g_rows
    (P, nr) and g_cols (P, nc) are the permuted labels of those rows and
    of all columns. mat2 may be bf16 (accumulation is always fp32).
    Returns (P, n_groups_pad) f32 totals: s_W is 0.5 * totals @
    inv_group_sizes (the zero diagonal makes the halved symmetric sum
    exact; row slabs' totals add up to the whole matrix's). `name` is the
    custom call's HLO name (SW_NAME or SW_ROWS_NAME)."""
    n_perms = g_cols.shape[0]
    nti, ntj = mat2.shape[0] // tile_r, mat2.shape[1] // tile_c
    npb = n_perms // perm_block
    width = perm_block * n_groups_pad
    kernel = functools.partial(_sw_matmul_body, n_groups_pad=n_groups_pad,
                               nti=nti, ntj=ntj)
    out = pl.pallas_call(
        kernel,
        grid=(npb, nti, ntj),
        in_specs=[
            pl.BlockSpec((perm_block, tile_r), lambda p, i, j: (p, i)),
            pl.BlockSpec((perm_block, tile_c), lambda p, i, j: (p, j)),
            pl.BlockSpec((tile_r, tile_c), lambda p, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, width), lambda p, i, j: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((npb, 1, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((width, tile_r), jnp.float32)],
        interpret=interpret,
        name=name,
    )(g_rows, g_cols, mat2)
    return out.reshape(n_perms, n_groups_pad)
