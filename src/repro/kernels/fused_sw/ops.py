"""jit'd wrapper for the fused distance→s_W megakernel (with padding).

`fused_sw_rows` is the streaming unit the pipeline's fused-kernel bridge
consumes: s_W partials + Gower row sums for one permutation chunk over one
row slab, with the D² tiles never leaving VMEM. The slab is the whole table
in the single-host case (the kernel tiles rows internally) and a 'model'-
axis shard in the distributed case (`row_offset` is traced, so one compiled
program serves every shard).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common as _c
from repro.kernels.fused_sw import kernel as _k

# aitchison is euclidean geometry over clr-prepared features
KERNEL_METRIC = {"euclidean": "euclidean", "braycurtis": "braycurtis",
                 "jaccard": "jaccard", "aitchison": "euclidean"}


def _feature_mode(metric, feat_bf16, feat_fp8, feat_packed) -> str:
    """Resolve the precision knobs to a kernel feat_mode (validating)."""
    if int(bool(feat_bf16)) + int(bool(feat_fp8)) + int(bool(feat_packed)) \
            > 1:
        raise ValueError(
            "feat_bf16 / feat_fp8 / feat_packed are mutually exclusive")
    if feat_packed:
        if metric != "jaccard":
            raise ValueError(
                "feat_packed=1 requires the jaccard kernel body "
                f"(got metric={metric!r})")
        return "packed"
    return "fp8" if feat_fp8 else "dense"


def _quantize_slabs(x_rows, x, metric, mode, feat_bf16, feat_scale):
    """Represent the prepared slabs at the requested precision.

    Returns (xr, xc, scale (1,1) f32). packed -> presence words viewed as
    int32 (feature axis becomes words); fp8 -> float8_e4m3fn at the calibration
    scale (computed from the FULL table when not supplied, so every row
    slab of one study quantizes identically); dense -> f32/bf16."""
    from repro.core import distance as _dist
    if mode == "packed":
        def words(v):
            return jax.lax.bitcast_convert_type(
                _dist.pack_presence_bits(v), jnp.int32)
        return words(x_rows), words(x), jnp.ones((1, 1), jnp.float32)
    if mode == "fp8":
        s = (_dist.fp8_metric_scale(x, metric) if feat_scale is None
             else jnp.asarray(feat_scale, jnp.float32))
        s = jnp.reshape(s, ())
        xr = (x_rows.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
        xc = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
        return xr, xc, s.reshape(1, 1)
    dt = jnp.bfloat16 if feat_bf16 else jnp.float32
    return x_rows.astype(dt), x.astype(dt), jnp.ones((1, 1), jnp.float32)


def _slabs(x_rows, x, *, metric, mode, feat_bf16, feat_scale, tile_r,
           tile_c, feat_block):
    """Quantize and pad the feature slabs for the kernel's layout: the row
    slab sample-major (nr_pad, d_pad), the column table feature-major
    (d_pad, nc_pad). Tiles are Mosaic-legal: the cap, or one full-extent
    block when the axis is shorter."""
    xr, xc, scale = _quantize_slabs(x_rows, x, metric, mode, feat_bf16,
                                    feat_scale)
    nr, d = xr.shape                     # words when packed, else features
    n = xc.shape[0]
    tile_r = _c.pick_tile(nr, tile_r)
    tile_c = _c.pick_tile(n, tile_c)
    feat_block = _c.pick_tile(d, feat_block)
    r_pad = _c.round_up(nr, tile_r) - nr
    c_pad = _c.round_up(n, tile_c) - n
    d_pad = _c.round_up(d, feat_block) - d
    xr = jnp.pad(xr, ((0, r_pad), (0, d_pad)))
    xc = jnp.pad(xc, ((0, c_pad), (0, d_pad))).T
    return xr, xc, scale, tile_r, tile_c, feat_block, r_pad, c_pad


def _pad_perms(ops_, p, perm_block):
    """Pad every (P, ..., T) operand's permutation axis to the perm block
    (edge mode: excess rows are sliced off). The block is the whole padded
    axis or a multiple of 8, so Mosaic accepts it at any P."""
    perm_block = min(perm_block, _c.round_up(p, 8))
    p_pad = _c.round_up(p, perm_block) - p
    if p_pad:
        ops_ = [jnp.pad(o, ((0, p_pad),) + ((0, 0),) * (o.ndim - 1),
                        mode="edge") for o in ops_]
    return ops_, perm_block


@functools.partial(jax.jit, static_argnames=(
    "metric", "n_valid", "tile_r", "tile_c", "feat_block", "perm_block",
    "feat_bf16", "feat_fp8", "feat_packed", "interpret"))
def fused_sw_rows(x_rows, x, g_rows, g_cols, inv_gs, row_offset, *,
                  metric="braycurtis", n_valid=None, tile_r=128, tile_c=128,
                  feat_block=128, perm_block=16, feat_bf16: int = 0,
                  feat_fp8: int = 0, feat_packed: int = 0, feat_scale=None,
                  interpret: bool | None = None):
    """Fused s_W partial for one (row slab × permutation chunk) cell.

    x_rows:   (nr, d) prepared features of the slab's rows.
    x:        (n, d) prepared features of ALL samples (columns).
    g_rows:   (P, nr) int32 permuted labels at the slab's GLOBAL rows.
    g_cols:   (P, n) int32 permuted labels over all samples.
    inv_gs:   (G,) f32 inverse group sizes.
    row_offset: scalar global index of x_rows[0] (python int or traced).
    n_valid:  global sample count n (pad masking); defaults to x.shape[0].

    Precision knobs (the planner/autotune family; mutually exclusive):
    feat_bf16:   1 = bf16 feature slabs — halves HBM feature traffic,
                 fp32 accumulation; ~1e-2 rel drift on distances.
    feat_fp8:    1 = float8_e4m3fn slabs — quarters feature traffic.
                 Slabs are scaled by one per-study calibration scalar
                 (max|x|/448, computed once during prepare or passed as
                 feat_scale) and dequantized in-register; fp32
                 accumulation; ~1e-2 rel tolerance on F.
    feat_packed: 1 = packed presence words (jaccard only) — 32x
                 feature-traffic cut, popcount tile bodies, the same
                 results as the f32 matmul form.
    feat_scale:  optional traced f32 scalar pinning the fp8 calibration
                 (drivers compute it once per study, not per chunk).

    Returns (s_W (P,) f32, row_sums (nr,) f32). Summing the partials over
    disjoint row slabs reconstructs the full-statistic / full row sums.
    """
    metric = KERNEL_METRIC.get(metric, metric)
    mode = _feature_mode(metric, feat_bf16, feat_fp8, feat_packed)
    nr = x_rows.shape[0]
    p = g_cols.shape[0]
    if n_valid is None:
        n_valid = x.shape[0]
    xr, xc, scale, tile_r, tile_c, feat_block, r_pad, c_pad = _slabs(
        x_rows, x, metric=metric, mode=mode, feat_bf16=feat_bf16,
        feat_scale=feat_scale, tile_r=tile_r, tile_c=tile_c,
        feat_block=feat_block)
    # pad labels with 0s (masked D² zeroes those tiles' contributions)
    (gr, gc), perm_block = _pad_perms(
        [jnp.pad(g_rows, ((0, 0), (0, r_pad))),
         jnp.pad(g_cols, ((0, 0), (0, c_pad)))], p, perm_block)
    n_groups = inv_gs.shape[0]
    gp = _c.round_up(n_groups, 8)
    w = jnp.pad(inv_gs.astype(jnp.float32), (0, gp - n_groups))
    off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    tot, rs = _k.fused_sw_pallas(
        off, xr, xc, gr, gc, n_groups_pad=gp, metric=metric,
        n_valid=int(n_valid), nr_valid=nr, tile_r=tile_r, tile_c=tile_c,
        feat_block=feat_block, perm_block=perm_block, feat_mode=mode,
        feat_scale=scale, interpret=_c.interpret_mode(interpret))
    return 0.5 * jnp.sum(tot[:p] * w, axis=1), rs[:nr]


@functools.partial(jax.jit, static_argnames=(
    "metric", "n_valid", "tile_r", "tile_c", "feat_block", "perm_block",
    "feat_bf16", "feat_fp8", "feat_packed", "interpret"))
def fused_sw_rows_cols(x_rows, x, v_rows, v_cols, row_offset, *,
                       metric="braycurtis", n_valid=None, tile_r=128,
                       tile_c=128, feat_block=128, perm_block=16,
                       feat_bf16: int = 0, feat_fp8: int = 0,
                       feat_packed: int = 0, feat_scale=None,
                       interpret: bool | None = None):
    """Dense-design fused partial: per-COLUMN quadratic forms for one
    (row slab × permutation chunk) cell (core.design hat-matrix blocks
    replacing the one-hot labels; the megakernel's MXU contraction
    consumes permuted basis blocks directly).

    v_rows: (P, nr, K) f32 permuted basis rows at the slab's GLOBAL rows.
    v_cols: (P, n, K) f32 permuted basis over all samples.
    feat_bf16/feat_fp8/feat_packed/feat_scale: feature-slab precision
    knobs, as documented on fused_sw_rows.
    Returns (s_cols (P, K) f32, row_sums (nr,) f32); summing partials
    over disjoint row slabs reconstructs the global per-column statistic.
    K is padded to a multiple of 8 internally — zero basis columns
    contribute exactly zero and are sliced off.
    """
    metric = KERNEL_METRIC.get(metric, metric)
    mode = _feature_mode(metric, feat_bf16, feat_fp8, feat_packed)
    nr = x_rows.shape[0]
    p, _, k = v_cols.shape
    if n_valid is None:
        n_valid = x.shape[0]
    xr, xc, scale, tile_r, tile_c, feat_block, r_pad, c_pad = _slabs(
        x_rows, x, metric=metric, mode=mode, feat_bf16=feat_bf16,
        feat_scale=feat_scale, tile_r=tile_r, tile_c=tile_c,
        feat_block=feat_block)
    k_pad = _c.round_up(k, 8) - k

    def basis(v, pad):                   # (P, T, K) -> (P, K_pad, T_pad)
        v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad), (0, k_pad)))
        return jnp.swapaxes(v, 1, 2)

    (vr, vc), perm_block = _pad_perms(
        [basis(v_rows, r_pad), basis(v_cols, c_pad)], p, perm_block)
    off = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    tot, rs = _k.fused_sw_cols_pallas(
        off, xr, xc, vr, vc, metric=metric, n_valid=int(n_valid),
        nr_valid=nr, tile_r=tile_r, tile_c=tile_c, feat_block=feat_block,
        perm_block=perm_block, feat_mode=mode, feat_scale=scale,
        interpret=_c.interpret_mode(interpret))
    return 0.5 * tot[:p, :k], rs[:nr]
