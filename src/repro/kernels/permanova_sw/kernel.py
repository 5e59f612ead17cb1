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
             Passes: the one-hot is 0/1, exact in bf16; an f32 tile is
             split exactly into three bf16 parts, so a step is three bf16
             MXU passes (Precision.HIGHEST would take six), a bf16 tile
             one.
             The whole matrix runs grid = (perm-block, listed tile pair)
             over the tile pairs j >= i only (Algorithm 3's cols > rows
             bound at tile granularity); a row slab runs (perm-block,
             row-tile, col-tile) over every tile.

Grid convention (TPU): the LAST grid dimension is innermost. All kernels
accumulate over the inner tile dims into an output block indexed only by
the outer perm dim — the Pallas-safe write-once-per-block accumulation
pattern (init at first inner step via pl.when).

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
import numpy as np
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
# matmul: grid (n_perm_blocks, triangle tile pairs) or (n_perm_blocks, nti,
# ntj); MXU one-hot contraction
# ---------------------------------------------------------------------------

def triangle_pairs(nt: int):
    """The (row tile, column tile, weight) step tables of the upper
    triangle of an nt x nt tile grid: the pairs j >= i, row-major (the row
    label block keeps its index across a row), weight 2 off the diagonal
    and 1 on it. D^2 is symmetric and both sides carry the same labels,
    so tile (j, i) totals what tile (i, j) does."""
    ti, tj = (np.asarray(v, np.int32) for v in np.triu_indices(nt))
    return ti, tj, np.where(ti == tj, 1, 2).astype(np.int32)


def split_bf16x3(x):
    """f32 x -> bf16 (hi, mid, lo) with hi + mid + lo == x exactly for
    every normal f32 (three 8-bit significands cover f32's 24): each part
    is the rounding of what the parts before it leave."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _sw_matmul_body(*refs, n_groups_pad: int, listed: bool, ntj: int,
                    n_steps: int):
    if listed:        # grid (perm block, listed pair); tables lead the refs
        _, _, wt_ref, g_row_ref, g_col_ref, m2_ref, o_ref, acc_ref = refs
        t = pl.program_id(1)
        # the pair's weight rides on the row one-hot, which is built anyway
        on = wt_ref[t].astype(jnp.float32)
    else:             # grid (perm block, row tile, column tile)
        g_row_ref, g_col_ref, m2_ref, o_ref, acc_ref = refs
        t = pl.program_id(1) * ntj + pl.program_id(2)
        on = None

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e_r = _c.onehot_t(g_row_ref[...], n_groups_pad, on=on)       # (W, TR)
    # 0/1 is exact in bf16, so the column one-hot is a one-pass operand
    e_c = _c.onehot_t(g_col_ref[...], n_groups_pad).astype(jnp.bfloat16)
    # MXU: (W, TC) x (k TR, TC)^T -> (W, k TR), W = perm_block * groups,
    # the tile's k bf16 parts stacked down the rows: k passes, each
    # product exact (HIGHEST would split both operands, six passes); the
    # parts' (W, TR) sums then add up
    m2 = m2_ref[...]
    parts = split_bf16x3(m2) if m2.dtype == jnp.float32 else (m2,)
    tr = m2.shape[0]
    full = _c.dot(e_c, jnp.concatenate(parts), _c.DOT_NT)
    prod = full[:, :tr]
    for k in range(1, len(parts)):
        prod += full[:, k * tr:(k + 1) * tr]
    acc_ref[...] += prod * e_r

    @pl.when(t == n_steps - 1)
    def _flush():
        o_ref[...] = jnp.sum(acc_ref[...], axis=1, keepdims=True).T[None]


def sw_matmul_pallas(mat2, g_rows, g_cols, *, triangle, n_groups_pad,
                     perm_block, tile_r, tile_c, interpret, name):
    """Per-(perm, group) same-group sums over the (i != j) entries of mat2.

    mat2 (nr, nc) is the whole matrix or a slab of its rows; g_rows
    (P, nr) and g_cols (P, nc) are the permuted labels of those rows and
    of all columns. mat2 may be bf16 (accumulation is always fp32).
    Passes: the column one-hot is built in bf16 (0/1, exact); an f32
    mat2 tile is split exactly into bf16 hi + mid + lo (split_bf16x3),
    stacked down the rows and contracted in one bf16 dot, three passes,
    whose three f32 slices are summed: every product is HIGHEST's and
    only the order of the f32 sums differs. A bf16 mat2 takes one pass.

    triangle (whole matrix, g_rows the same as g_cols, square tiles): the
    grid is (perm block, listed tile pair) over triangle_pairs, whose
    tables ride in SMEM as scalar prefetch and steer the block index
    maps; off-diagonal tiles weigh 2 for the mirror tile they stand for.
    Otherwise the grid is (perm block, row tile, column tile) over every
    tile, weight 1 (a listed step costs ~1-2 % more on v5e, so a full
    sweep keeps the plain grid).
    Returns (P, n_groups_pad) f32 totals: s_W is 0.5 * totals @
    inv_group_sizes (the zero diagonal makes the halved symmetric sum
    exact; row slabs' totals add up to the whole matrix's). `name` is the
    custom call's HLO name (SW_NAME or SW_ROWS_NAME)."""
    n_perms = g_cols.shape[0]
    nti, ntj = mat2.shape[0] // tile_r, mat2.shape[1] // tile_c
    npb = n_perms // perm_block
    width = perm_block * n_groups_pad
    blocks = [((perm_block, tile_r), lambda p, i, j: (p, i)),
              ((perm_block, tile_c), lambda p, i, j: (p, j)),
              ((tile_r, tile_c), lambda p, i, j: (i, j))]
    out_block = ((1, 1, width), lambda p, i, j: (p, 0, 0))
    scratch = [pltpu.VMEM((width, tile_r), jnp.float32)]
    if triangle:
        steps = tuple(jnp.asarray(v) for v in triangle_pairs(nti))
        n_steps = steps[0].shape[0]

        def at(f):    # a listed step t stands for the pair (ti[t], tj[t])
            return lambda p, t, ti, tj, wt: f(p, ti[t], tj[t])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(steps), grid=(npb, n_steps),
            in_specs=[pl.BlockSpec(b, at(f)) for b, f in blocks],
            out_specs=pl.BlockSpec(out_block[0], at(out_block[1])),
            scratch_shapes=scratch)
    else:
        steps, n_steps = (), nti * ntj
        grid_spec = pl.GridSpec(
            grid=(npb, nti, ntj),
            in_specs=[pl.BlockSpec(b, f) for b, f in blocks],
            out_specs=pl.BlockSpec(*out_block), scratch_shapes=scratch)
    kernel = functools.partial(_sw_matmul_body, n_groups_pad=n_groups_pad,
                               listed=triangle, ntj=ntj, n_steps=n_steps)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npb, 1, width), jnp.float32),
        interpret=interpret,
        name=name,
    )(*steps, g_rows, g_cols, mat2)
    return out.reshape(n_perms, n_groups_pad)
