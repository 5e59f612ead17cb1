"""Pallas kernels for pairwise distance-matrix construction.

The distance matrix is the PERMANOVA input (the paper consumed a UniFrac
matrix computed elsewhere; Bray-Curtis/Euclidean are the standard in-framework
metrics). Tiling: grid (row-tile, col-tile, feature-block); the feature axis
is innermost so numerator/denominator accumulate in VMEM and the final
divide/sqrt happens once on the last feature step.

The kernels are rectangular: `xr` (nr, d) rows against `xc` (nc, d) columns.
The dense (n, n) matrix is the xr is xc special case; the pipeline's
streaming builder feeds row slabs (block, d) against the full table so the
distance stage can produce `D²` row blocks without ever materializing the
square matrix (repro.pipeline.streaming).

Euclidean uses the MXU (gram-trick inside the tile); Bray-Curtis is a pure
VPU streaming kernel (|xi - xj| has no matmul form). Jaccard is the
presence/absence matmul form: on 0/1 features the float product IS the set
intersection, so |A ∩ B| accumulates on the MXU and |A ∪ B| falls out of
the cardinality sums — every registered metric has a tiled stage-1 impl.

The tile bodies are the fused megakernel's (kernels.common.accumulate):
the row operand is sample-major (nr, d), the column operand feature-major
(d, nc). Packed jaccard feeds int32 presence words through the same body
(feat_block then counts words).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as _c

METRICS = ("braycurtis", "euclidean", "jaccard")
NAME = "pairwise_distance"          # the custom call's HLO name


def _distance_body(xr_ref, xc_ref, out_ref, a_ref, b_ref, *, metric,
                   feat_mode, n_feat_blocks):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    _c.accumulate(metric, feat_mode, 1.0, xr_ref[...], xc_ref[...], a_ref,
                  b_ref)

    @pl.when(k == n_feat_blocks - 1)
    def _finish():
        out_ref[...] = _c.finalize_d(metric, a_ref[...], b_ref[...])


def distance_pallas(xr, xc, *, metric, packed, tile_r, tile_c, feat_block,
                    interpret):
    """(nr, nc) distances of xr (nr, d) rows against xc (d, nc) columns,
    both padded to tile multiples. packed=True: int32 presence words
    (jaccard only)."""
    nr, d = xr.shape
    nc = xc.shape[1]
    grid = (nr // tile_r, nc // tile_c, d // feat_block)
    kernel = functools.partial(
        _distance_body, metric=metric,
        feat_mode="packed" if packed else "dense", n_feat_blocks=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, feat_block), lambda i, j, k: (i, k)),
            pl.BlockSpec((feat_block, tile_c), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nr, nc), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_r, tile_c), jnp.float32),
                        pltpu.VMEM((tile_r, tile_c), jnp.float32)],
        interpret=interpret,
        name=NAME,
    )(xr, xc)
