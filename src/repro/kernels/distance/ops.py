"""jit'd wrappers for the pairwise-distance Pallas kernels (with padding).

Two entry points:

  pairwise_distance       (n, n) dense matrix from (n, d) features
  pairwise_distance_rows  (block, n) row slab — the streaming unit the
                          pipeline subsystem consumes to build D² blockwise
                          without materializing the full matrix
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import distance as _dist
from repro.kernels import common as _c
from repro.kernels.distance import kernel as _k

PALLAS_METRICS = _k.METRICS


def _check(metric, packed):
    if metric not in PALLAS_METRICS:
        raise ValueError(f"unknown metric {metric!r}")
    if packed and metric != "jaccard":
        raise ValueError(
            f"packed=1 requires metric='jaccard' (got {metric!r})")


def _rows(x_rows, x, *, metric, packed, tile_r, tile_c, feat_block,
          interpret):
    """Quantize, pad and launch; returns the padded distance slab."""
    _check(metric, packed)
    if packed:
        def quant(v):
            return jax.lax.bitcast_convert_type(
                _dist.pack_presence_bits(v), jnp.int32)
    else:
        def quant(v):
            return v.astype(jnp.float32)
    xr_q, xc_q = quant(x_rows), quant(x)
    b, d = xr_q.shape
    n = xc_q.shape[0]
    tile_r = _c.pick_tile(b, tile_r)
    tile_c = _c.pick_tile(n, tile_c)
    feat_block = _c.pick_tile(d, feat_block)
    d_pad = _c.round_up(d, feat_block) - d
    xr = jnp.pad(xr_q, ((0, _c.round_up(b, tile_r) - b), (0, d_pad)))
    xc = jnp.pad(xc_q, ((0, _c.round_up(n, tile_c) - n), (0, d_pad))).T
    return _k.distance_pallas(xr, xc, metric=metric, packed=bool(packed),
                              tile_r=tile_r, tile_c=tile_c,
                              feat_block=feat_block,
                              interpret=_c.interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("metric", "tile_r", "tile_c",
                                             "feat_block", "packed",
                                             "interpret"))
def pairwise_distance(x, *, metric="braycurtis", tile_r=128, tile_c=128,
                      feat_block=128, packed: int = 0,
                      interpret: bool | None = None):
    """(n, n) distance matrix from (n, d) features via the Pallas kernels.

    Pads n/d to tile multiples; zero-padded features are exact for every
    metric (|0-0| = 0, zero presence bits intersect/union nothing; pad
    rows are sliced off). Jaccard expects presence/absence floats
    (distance.presence_prepare) — the registry's prepare supplies them.
    packed=1 (jaccard only) packs presence into 32-bit words and runs the
    popcount tile body — the same distances, 32x fewer feature bytes
    (feat_block then counts words).
    """
    n = x.shape[0]
    out = _rows(x, x, metric=metric, packed=packed, tile_r=tile_r,
                tile_c=tile_c, feat_block=feat_block,
                interpret=interpret)[:n, :n]
    return _dist._zero_diag(out)                      # exact zero diagonal


@functools.partial(jax.jit, static_argnames=("metric", "tile_r", "tile_c",
                                             "feat_block", "packed",
                                             "interpret"))
def pairwise_distance_rows(x_rows, x, *, metric="braycurtis", tile_r=128,
                           tile_c=128, feat_block=128, packed: int = 0,
                           interpret: bool | None = None):
    """(block, n) distances of a row slab against the full table.

    NOTE: no diagonal zeroing — the slab does not know its global row
    offset; the streaming consumer masks the (global_row == col) entries
    (repro.pipeline.streaming does this while squaring into D²).
    packed=1: as in pairwise_distance (jaccard popcount word slabs).
    """
    out = _rows(x_rows, x, metric=metric, packed=packed, tile_r=tile_r,
                tile_c=tile_c, feat_block=feat_block, interpret=interpret)
    return out[:x_rows.shape[0], :x.shape[0]]
