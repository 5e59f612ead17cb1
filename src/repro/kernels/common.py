"""Pieces every Pallas kernel package shares.

  interpret_mode   the one place that decides whether a kernel runs
                   compiled (TPU) or in the Pallas interpreter (CPU tests)
  pick_tile        a block edge Mosaic accepts: the cap when the axis is
                   longer, else the whole axis rounded up to `align`
  onehot_t         labels -> transposed one-hot, the MXU operand of the
                   s_W contractions
  accumulate / finalize_d2
                   the metric tile bodies shared by the distance kernels
                   and the fused megakernel

Layout contract of the tile bodies: the row operand is sample-major
(TR, FB) and the column operand feature-major (FB, TC), so a feature's
row values are a lane-slice column of one and its column values a
sublane-slice row of the other, and the Gram-type products are plain
(TR, FB) x (FB, TC) matmuls. Every value stays 2-D.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel entry's `interpret` argument.

    The interpreter is on only on the CPU backend. On TPU a kernel always
    runs compiled: asking for the interpreter there is an error, never a
    silent slow path. `interpret=False` is honoured on the CPU too, so a
    test can lower the real Mosaic kernel for a described TPU device.
    Other backends have no path for these kernels."""
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError("Pallas interpret mode is off on TPU; kernels "
                             "run compiled there")
        return False
    if backend == "cpu":
        return True if interpret is None else bool(interpret)
    raise RuntimeError(f"no Pallas TPU kernel path on backend {backend!r}")


def round_up(v: int, m: int) -> int:
    return -(-int(v) // m) * m


def pick_tile(v: int, cap: int, align: int = 8) -> int:
    """Block edge for an axis of length v: `cap` when the axis is longer
    (cap must then satisfy the caller's Mosaic tiling rule), else one block
    covering the whole axis rounded up to `align` (a full-extent block,
    which Mosaic accepts at any size)."""
    v = max(int(v), 1)
    return int(cap) if v > cap else round_up(v, align)


def onehot_t(g, n_groups_pad: int, on=None):
    """(PB, T) int32 labels -> (PB * n_groups_pad, T) f32 one-hot whose row
    p * n_groups_pad + k is [g[p] == k] (or `on` where it is 1, a scalar
    folded in at no extra pass). n_groups_pad is a multiple of 8, so the
    collapse of the two leading axes is tile aligned."""
    pb, t = g.shape
    k = jax.lax.broadcasted_iota(jnp.int32, (pb, n_groups_pad, t), 1)
    hit = g[:, None, :] == k
    e = (hit.astype(jnp.float32) if on is None
         else jnp.where(hit, on, jnp.float32(0.0)))
    return e.reshape(pb * n_groups_pad, t)


def dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """MXU contraction with f32 accumulation; f32 operands keep full f32
    precision (no single-pass bf16 truncation)."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            and b.dtype == jnp.float32 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dimension_numbers=dims,
                               preferred_element_type=jnp.float32,
                               precision=prec)


# contract the lane axes of both operands: (M, K) x (N, K) -> (M, N)
DOT_NT = (((1,), (1,)), ((), ()))


def accumulate(metric, feat_mode, scale, xr, xc, a_ref, b_ref):
    """One feature block's contribution to the metric's running sums.

    xr: (TR, FB) row block, xc: (FB, TC) feature-major column block.
    feat_mode selects the slab representation (static):

      dense   f32 or bf16 slabs; the MXU consumes them directly with fp32
              accumulation, elementwise paths cast up first
      fp8     float8_e4m3fn slabs + one calibration scalar; dequantized in
              registers so the running sums stay in real units
      packed  int32 presence words (jaccard only); |A∩B| via popcount(AND)
              and cardinalities via popcount sums: exact integer counts,
              the same values as the f32 matmul form

    The accumulators a_ref/b_ref are (TR, TC) f32."""
    if feat_mode == "packed":
        if metric != "jaccard":  # pragma: no cover - ops validates
            raise ValueError("packed slabs require the jaccard body")
        inter = jnp.zeros(a_ref.shape, jnp.int32)
        for w in range(xr.shape[1]):
            inter = inter + jax.lax.population_count(
                xr[:, w:w + 1] & xc[w:w + 1, :])
        a_ref[...] += inter.astype(jnp.float32)
        card_r = jnp.sum(jax.lax.population_count(xr), axis=1, keepdims=True)
        card_c = jnp.sum(jax.lax.population_count(xc), axis=0, keepdims=True)
        b_ref[...] += (card_r.astype(jnp.float32)
                       + card_c.astype(jnp.float32))
        return
    if feat_mode == "fp8":
        xr = xr.astype(jnp.float32) * scale
        xc = xc.astype(jnp.float32) * scale
    xr32 = xr.astype(jnp.float32)
    xc32 = xc.astype(jnp.float32)
    if metric == "euclidean":
        sq_r = jnp.sum(xr32 * xr32, axis=1, keepdims=True)
        sq_c = jnp.sum(xc32 * xc32, axis=0, keepdims=True)
        a_ref[...] += sq_r + sq_c - 2.0 * dot(xr, xc)   # accumulator IS D²
    elif metric == "braycurtis":
        acc = a_ref[...]
        for f in range(xr32.shape[1]):                   # VPU |x - y| sweep
            acc = acc + jnp.abs(xr32[:, f:f + 1] - xc32[f:f + 1, :])
        a_ref[...] = acc
        b_ref[...] += (jnp.sum(xr32, axis=1, keepdims=True)
                       + jnp.sum(xc32, axis=0, keepdims=True))
    elif metric == "jaccard":
        a_ref[...] += dot(xr, xc)                        # |A ∩ B| on the MXU
        b_ref[...] += (jnp.sum(xr32, axis=1, keepdims=True)
                       + jnp.sum(xc32, axis=0, keepdims=True))
    else:  # pragma: no cover - ops validates
        raise ValueError(metric)


def finalize_d(metric, a, b):
    """Distance tile from the completed running sums."""
    if metric == "euclidean":
        return jnp.sqrt(jnp.maximum(a, 0.0))
    if metric == "braycurtis":
        return a / jnp.maximum(b, 1e-30)
    # jaccard: union = card_r + card_c - inter
    return 1.0 - a / jnp.maximum(b - a, 1.0)


def finalize_d2(metric, a, b):
    """Squared distance tile from the completed running sums."""
    if metric == "euclidean":
        return jnp.maximum(a, 0.0)
    d = finalize_d(metric, a, b)
    return d * d
