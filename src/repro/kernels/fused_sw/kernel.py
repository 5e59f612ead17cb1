"""Single-pass Pallas megakernel: feature slabs → D² tiles → s_W partials.

PR 2's fused bridge avoids the (n, n) matrix but still round-trips every D²
row slab through HBM between the distance kernel and the s_W contraction.
This kernel closes that gap: a (tile_r, tile_c) squared-distance tile is
built from feature slabs and contracted into per-permutation s_W partials
(the one-hot matmul form) inside the same kernel, so D² tiles live only in
VMEM scratch and never touch HBM. The Gower row-sum marginals for s_T are
accumulated in the same sweep — one pass over the feature table yields
everything `fstat` needs.

Grid: (row-tile i, col-tile j, t) where the innermost t axis runs TWO
phases per (i, j) tile pair:

  t in [0, nk)        feature phase — accumulate the metric's running
                      sums over feature blocks into VMEM scratch; on the
                      last step finalize the masked D² tile (diagonal,
                      pad rows/cols zeroed by GLOBAL index) and bank the
                      Gower row sums
  t in [nk, nk+npb)   permutation phase — contract the resident D² tile
                      with one permutation block per step on the MXU,
                      accumulating the per-(perm, group) totals in a VMEM
                      scratch slab

Index maps clamp the out-of-phase block indices, so the feature operands
simply stay resident during the permutation phase and vice versa. The s_W
accumulator is flushed to HBM once, at the final grid step.

Metrics: euclidean (Gram trick — the accumulator IS D²), braycurtis
(|xi-xj| / (xi+xj) running sums), jaccard (presence/absence matmul form:
|A∩B| via the MXU, |A∪B| from cardinality sums). Aitchison rides the
euclidean body over clr-prepared features (ops layer maps it).

Row slabs are shardable: `row_offset` arrives as a traced SMEM scalar, so
a shard_map body can pass `axis_index('model') * rows_per_shard` and each
device sweeps only its row slab; summing the per-shard s_W partials (psum
over 'model') reconstructs the global statistic exactly (full i != j
symmetric sum, halved, zero diagonal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as _c

FUSED_METRICS = ("euclidean", "braycurtis", "jaccard")
FEAT_MODES = ("dense", "fp8", "packed")

# The HLO names of the megakernel's custom calls, set by the pallas_call's
# `name`; a device trace finds the kernel by them
# (bench/metrics/fusedk_roofline.py).
ROWS_NAME = "fused_sw_rows"
ROWS_COLS_NAME = "fused_sw_rows_cols"


def _fused_body(off_ref, scale_ref, xr_ref, xc_ref, opr_ref, opc_ref,
                o_sw_ref, o_rs_ref, a_ref, b_ref, d2_ref, sw_ref, swr_ref, *,
                metric, feat_mode, nk, npb, nti, ntj, tile_r, tile_c,
                n_valid, nr_valid, expand):
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)
    # Sharded row slabs can end with fully-dead tiles (every global row
    # past n_valid): skip their feature accumulation and perm contraction.
    # Finalize still runs — a/b are zero-initialized and the validity
    # mask zeroes the whole tile, so the banked row sums stay 0.
    row_live = off_ref[0, 0] + i * tile_r < n_valid

    @pl.when((i == 0) & (j == 0) & (t == 0))
    def _init_sw():
        sw_ref[...] = jnp.zeros_like(sw_ref)

    @pl.when(t == 0)
    def _init_acc():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when((j == 0) & (t == 0))
    def _init_row_sw():
        swr_ref[...] = jnp.zeros_like(swr_ref)

    @pl.when((t < nk) & row_live)
    def _feature_phase():
        _c.accumulate(metric, feat_mode, scale_ref[0, 0], xr_ref[...],
                      xc_ref[...], a_ref, b_ref)

    @pl.when(t == nk - 1)
    def _finalize():
        row_off = off_ref[0, 0]
        rows_l = i * tile_r + jax.lax.broadcasted_iota(
            jnp.int32, (tile_r, tile_c), 0)
        rows_g = row_off + rows_l
        cols_g = j * tile_c + jax.lax.broadcasted_iota(
            jnp.int32, (tile_r, tile_c), 1)
        # slab pad rows (local id past the slab's true row count), global
        # pad cols, and the exact diagonal contribute nothing — the
        # contraction and row sums below both consume the masked tile
        valid = ((rows_l < nr_valid) & (rows_g < n_valid)
                 & (cols_g < n_valid) & (rows_g != cols_g))
        d2 = jnp.where(valid, _c.finalize_d2(metric, a_ref[...], b_ref[...]),
                       0.0)
        d2_ref[...] = d2
        rs = jnp.sum(d2, axis=1, keepdims=True).T       # (1, TR)

        @pl.when(j == 0)
        def _rs_init():
            o_rs_ref[...] = rs

        @pl.when(j > 0)
        def _rs_acc():
            o_rs_ref[...] += rs

    @pl.when((t >= nk) & row_live)
    def _perm_phase():
        u_r = expand(opr_ref[...])                      # (W, TR)
        u_c = expand(opc_ref[...])                      # (W, TC)
        # MXU: (W, TC) x (TR, TC)^T -> (W, TR), then the row-side factor
        y = _c.dot(u_c, d2_ref[...], _c.DOT_NT)
        tot = jnp.sum(y * u_r, axis=1, keepdims=True).T  # (1, W)
        swr_ref[pl.ds(t - nk, 1), :] += tot

    # Two-level f32 sum: tile totals add into a per-row-tile partial,
    # which adds into the study total once per row tile. One running sum
    # over all nti * ntj tiles drifts by ~eps * sqrt(nti * ntj) / 6
    # relative (4e-6 at n = 25145 with 128-wide tiles, enough to move a
    # p-value); sums of ntj and then nti terms drift by ~sqrt(2) times
    # the drift of one axis instead.
    @pl.when((j == ntj - 1) & (t == nk + npb - 1))
    def _row_sw():
        sw_ref[...] += swr_ref[...]

    @pl.when((i == nti - 1) & (j == ntj - 1) & (t == nk + npb - 1))
    def _flush():
        o_sw_ref[...] = sw_ref[...]


def _launch(row_offset, xr, xc, opr, opc, *, metric, n_valid, nr_valid,
            tile_r, tile_c, feat_block, perm_block, width, expand,
            feat_mode, feat_scale, interpret, name):
    if metric not in FUSED_METRICS:
        raise ValueError(f"unknown fused metric {metric!r}; "
                         f"one of {FUSED_METRICS}")
    if feat_mode not in FEAT_MODES:
        raise ValueError(f"unknown feat_mode {feat_mode!r}; "
                         f"one of {FEAT_MODES}")
    if feat_scale is None:
        feat_scale = jnp.ones((1, 1), jnp.float32)
    nr, d = xr.shape
    nc = xc.shape[1]
    p_pad = opc.shape[0]
    nti, ntj = nr // tile_r, nc // tile_c
    nk, npb = d // feat_block, p_pad // perm_block
    tail = (0,) * (opc.ndim - 2)
    op_block = (perm_block,) + opc.shape[1:-1]
    kernel = functools.partial(
        _fused_body, metric=metric, feat_mode=feat_mode, nk=nk, npb=npb,
        nti=nti, ntj=ntj, tile_r=tile_r, tile_c=tile_c, n_valid=n_valid,
        nr_valid=nr_valid, expand=expand)

    def op_map(axis_block):
        return lambda i, j, t: ((jnp.clip(t - nk, 0, npb - 1),) + tail
                                + (axis_block(i, j),))

    out_sw, out_rs = pl.pallas_call(
        kernel,
        grid=(nti, ntj, nk + npb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tile_r, feat_block),
                         lambda i, j, t: (i, jnp.minimum(t, nk - 1))),
            pl.BlockSpec((feat_block, tile_c),
                         lambda i, j, t: (jnp.minimum(t, nk - 1), j)),
            pl.BlockSpec(op_block + (tile_r,), op_map(lambda i, j: i)),
            pl.BlockSpec(op_block + (tile_c,), op_map(lambda i, j: j)),
        ],
        out_specs=[
            pl.BlockSpec((npb, width), lambda i, j, t: (0, 0)),
            pl.BlockSpec((1, tile_r), lambda i, j, t: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npb, width), jnp.float32),
            jax.ShapeDtypeStruct((1, nr), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_r, tile_c), jnp.float32),   # metric accum a
            pltpu.VMEM((tile_r, tile_c), jnp.float32),   # metric accum b
            pltpu.VMEM((tile_r, tile_c), jnp.float32),   # masked D² tile
            pltpu.VMEM((npb, width), jnp.float32),       # contraction totals
            pltpu.VMEM((npb, width), jnp.float32),       # row-tile partial
        ],
        interpret=interpret,
        name=name,
    )(row_offset, feat_scale, xr, xc, opr, opc)
    return out_sw, out_rs[0]


def fused_sw_pallas(row_offset, xr, xc, g_rows, g_cols, *, n_groups_pad,
                    metric, n_valid, nr_valid, tile_r, tile_c, feat_block,
                    perm_block, feat_mode, feat_scale, interpret):
    """Launch the megakernel over pre-padded operands.

    row_offset: (1, 1) int32 — global index of xr's first row (traced OK).
    xr:      (nr_pad, d_pad) prepared row-slab features (f32/bf16 dense,
             float8_e4m3fn for feat_mode='fp8', int32 words for 'packed').
    xc:      (d_pad, nc_pad) prepared full feature table, FEATURE-major.
    g_rows:  (p_pad, nr_pad) int32 permuted labels at the slab's rows.
    g_cols:  (p_pad, nc_pad) int32 permuted labels over all samples.
    n_groups_pad: group count rounded up to a multiple of 8.
    feat_scale: (1, 1) f32 fp8 calibration scalar (ignored otherwise).
    Returns (totals (p_pad, n_groups_pad) f32 with
    totals[p, k] = sum_ij D²[i, j] [g_p(i) = k][g_p(j) = k] over the slab's
    rows, row_sums (nr_pad,) f32) — pad entries zero. s_W is
    0.5 * totals @ inv_group_sizes."""
    out, rs = _launch(
        row_offset, xr, xc, g_rows, g_cols, metric=metric, n_valid=n_valid,
        nr_valid=nr_valid, tile_r=tile_r, tile_c=tile_c,
        feat_block=feat_block, perm_block=perm_block,
        width=perm_block * n_groups_pad,
        expand=functools.partial(_c.onehot_t, n_groups_pad=n_groups_pad),
        feat_mode=feat_mode, feat_scale=feat_scale, interpret=interpret,
        name=ROWS_NAME)
    return out.reshape(-1, n_groups_pad), rs


# ---------------------------------------------------------------------------
# Dense-design variant: the perm phase contracts PERMUTED BASIS blocks
# (hat-matrix factor columns, core.design) instead of building one-hot
# factors from labels. Feature phase, D² scratch residency and Gower row
# sums are identical; the output keeps the per-column axis so the host can
# slice per-term partial statistics.
# ---------------------------------------------------------------------------

def fused_sw_cols_pallas(row_offset, xr, xc, v_rows, v_cols, *, metric,
                         n_valid, nr_valid, tile_r, tile_c, feat_block,
                         perm_block, feat_mode, feat_scale, interpret):
    """Launch the dense-design megakernel over pre-padded operands.

    v_rows: (p_pad, K, nr_pad) f32 permuted basis at the slab's rows,
            column-major per permutation (K a multiple of 8).
    v_cols: (p_pad, K, nc_pad) f32 permuted basis over all samples.
    Other operands as in fused_sw_pallas.
    Returns (totals (p_pad, K) f32 with totals[p, k] =
    sum_ij D²[i, j] v_p[i, k] v_p[j, k], row_sums (nr_pad,) f32) — pad
    entries zero (zero basis rows/cols contribute exactly nothing, which
    is what keeps ragged studies exact)."""
    k_cols = v_cols.shape[1]

    def expand(v):                       # (PB, K, T) -> (PB * K, T)
        return v.reshape(v.shape[0] * k_cols, v.shape[2])

    out, rs = _launch(
        row_offset, xr, xc, v_rows, v_cols, metric=metric, n_valid=n_valid,
        nr_valid=nr_valid, tile_r=tile_r, tile_c=tile_c,
        feat_block=feat_block, perm_block=perm_block,
        width=perm_block * k_cols, expand=expand, feat_mode=feat_mode,
        feat_scale=feat_scale, interpret=interpret, name=ROWS_COLS_NAME)
    return out.reshape(-1, k_cols), rs
