"""Distance-matrix construction — the substrate feeding PERMANOVA.

The paper's input was an Unweighted-UniFrac matrix over EMP data (computed by
a separate tool, ref [9]); the PERMANOVA code path consumes an arbitrary
symmetric zero-diagonal matrix. We provide the standard ecology metrics on
abundance tables in a factored form the pipeline subsystem composes:

  prepare(x)        one-off (n, d) feature transform (clr for Aitchison,
                    presence/absence cast for Jaccard; identity otherwise)
  rows(xb, xprep)   distances for a block of rows against ALL samples —
                    the unit both the dense builders and the pipeline's
                    streaming / fused paths consume

Dense metrics (`euclidean`, `braycurtis`, ...) remain the public API and are
now thin drivers over the row primitives, so a 100k-sample table can stream
in row blocks instead of materializing (n, n, d) intermediates, and the
pipeline registry (repro.pipeline.registry) exposes the same math behind
dense / blocked / Pallas implementations with capability metadata.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array

# Gram-trick distances cancel (|x|² + |y|² - 2x·y): full f32 on the MXU.
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Row primitives: rows(xb, xprep) -> (block, n) distances.
# ---------------------------------------------------------------------------

def _identity_prepare(x: Array) -> Array:
    return jnp.asarray(x, dtype=jnp.float32)


def clr_prepare(x: Array, *, pseudocount: float = 0.5) -> Array:
    """Centered log-ratio transform (Aitchison geometry on compositions)."""
    logx = jnp.log(jnp.asarray(x, jnp.float32) + pseudocount)
    return logx - jnp.mean(logx, axis=-1, keepdims=True)


def presence_prepare(x: Array) -> Array:
    """Presence/absence cast for binary metrics (kept float32 so the same
    row kernels and Pallas tiles apply)."""
    return (jnp.asarray(x) > 0).astype(jnp.float32)


def euclidean_rows(xb: Array, x: Array) -> Array:
    """(block, n) Euclidean distances via the Gram trick (MXU-friendly)."""
    sq_b = jnp.sum(xb * xb, axis=-1)[:, None]
    sq = jnp.sum(x * x, axis=-1)[None, :]
    d2 = sq_b + sq - 2.0 * jnp.matmul(xb, x.T, precision=_HIGHEST)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def braycurtis_rows(xb: Array, x: Array) -> Array:
    """(block, n) Bray-Curtis: sum|xi-xj| / sum(xi+xj)."""
    num = jnp.sum(jnp.abs(xb[:, None, :] - x[None, :, :]), axis=-1)
    den = jnp.sum(xb[:, None, :] + x[None, :, :], axis=-1)
    return num / jnp.maximum(den, 1e-30)


def jaccard_rows(xb: Array, x: Array) -> Array:
    """(block, n) binary Jaccard on presence/absence (prepare casts x > 0;
    float multiply = AND, so the same kernel shape works on the MXU)."""
    inter = jnp.matmul(xb, x.T, precision=_HIGHEST)    # |A & B|
    card_b = jnp.sum(xb, axis=-1)[:, None]
    card = jnp.sum(x, axis=-1)[None, :]
    union = card_b + card - inter                      # |A | B|
    return 1.0 - inter / jnp.maximum(union, 1.0)


# ---------------------------------------------------------------------------
# Precision helpers: fp8 (e4m3) feature-slab quantization and packed-bit
# presence words. These feed the fused megakernel's precision knobs
# (feat_fp8 / feat_packed) and the XLA reference round-trips.
# ---------------------------------------------------------------------------

FP8_MAX = 448.0            # largest finite float8_e4m3fn magnitude


def fp8_scale(xprep: Array) -> Array:
    """Per-slab calibration scale so max|x|/scale hits the e4m3 range.

    Computed ONCE on the prepared feature table (the megakernel driver
    calls this before its chunk loop); a scalar f32."""
    amax = jnp.max(jnp.abs(jnp.asarray(xprep, jnp.float32)))
    return jnp.maximum(amax / FP8_MAX, 1e-12).astype(jnp.float32)


def fp8_metric_scale(xprep: Array, metric: str) -> Array:
    """Metric-aware calibration: presence/absence slabs (jaccard) are
    {0, 1} — exact in fp8 at scale 1 — everything else calibrates to the
    slab's max magnitude."""
    if metric == "jaccard":
        return jnp.float32(1.0)
    return fp8_scale(xprep)


def fp8_roundtrip(xprep: Array, scale: Array | None = None) -> Array:
    """Quantize to float8_e4m3fn and dequantize back to f32 — the exact
    value path the fp8 kernel sees (scale-down, cast, scale-up with fp32
    accumulation). Used by the XLA ref/onepass paths for parity."""
    x = jnp.asarray(xprep, jnp.float32)
    s = fp8_scale(x) if scale is None else jnp.asarray(scale, jnp.float32)
    q = (x / s).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * s


def pack_presence_bits(xprep: Array) -> Array:
    """Pack a presence/absence slab into uint32 words along features.

    (n, d) floats -> (n, ceil(d/32)) uint32; bit k of word w is
    1[x[:, 32*w + k] > 0]. Pad features are zero words, so popcount
    tiles over padded word blocks stay exact. 32x feature-traffic cut."""
    x = jnp.asarray(xprep)
    n, d = x.shape
    pad = (-d) % 32
    bits = (x > 0).astype(jnp.uint32)
    if pad:
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits.reshape(n, -1, 32) << shifts, axis=-1,
                   dtype=jnp.uint32)


class MetricDef(NamedTuple):
    """Factored metric: one-off feature transform + row-block kernel."""
    prepare: Callable[[Array], Array]
    rows: Callable[[Array, Array], Array]


ROW_METRICS: dict[str, MetricDef] = {
    "euclidean": MetricDef(_identity_prepare, euclidean_rows),
    "braycurtis": MetricDef(_identity_prepare, braycurtis_rows),
    "jaccard": MetricDef(presence_prepare, jaccard_rows),
    "aitchison": MetricDef(clr_prepare, euclidean_rows),
}


# ---------------------------------------------------------------------------
# Dense metrics (public API) — drivers over the row primitives.
# ---------------------------------------------------------------------------

def euclidean(x: Array) -> Array:
    """Pairwise Euclidean via the Gram trick (single full-matrix form)."""
    xp = _identity_prepare(x)
    return _zero_diag(euclidean_rows(xp, xp))


def braycurtis(x: Array, *, block: int = 256) -> Array:
    """Bray-Curtis dissimilarity, blocked over rows (bounds peak memory)."""
    xp = _identity_prepare(x)
    return _zero_diag(_blocked_rows(braycurtis_rows, xp, block))


def jaccard(x: Array, *, block: int = 256) -> Array:
    """Binary Jaccard distance on presence/absence (x > 0)."""
    xp = presence_prepare(x)
    return _zero_diag(_blocked_rows(jaccard_rows, xp, block))


def aitchison(x: Array, *, pseudocount: float = 0.5) -> Array:
    """Aitchison distance: Euclidean over clr-transformed compositions."""
    xp = clr_prepare(x, pseudocount=pseudocount)
    return _zero_diag(euclidean_rows(xp, xp))


METRICS: dict[str, Callable] = {
    "euclidean": euclidean,
    "braycurtis": braycurtis,
    "jaccard": jaccard,
    "aitchison": aitchison,
}


def distance_matrix(x: Array, metric: str = "braycurtis", **kw) -> Array:
    return METRICS[metric](x, **kw)


def _zero_diag(d: Array) -> Array:
    # an iota compare fuses into the consumer; an (n, n) eye would be a
    # second matrix-sized buffer
    i = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    return jnp.where(i == j, jnp.zeros((), d.dtype), d)


def _blocked_rows(row_fn: Callable, x: Array, block: int) -> Array:
    """Apply row_fn to row blocks via scan (bounds peak memory)."""
    n = x.shape[0]
    block = min(block, n)
    pad = (-n) % block
    if pad:
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        xp = jnp.pad(x, widths)
    else:
        xp = x

    def body(_, xb):
        return None, row_fn(xb, x)

    _, rows = jax.lax.scan(body, None, xp.reshape(-1, block, *x.shape[1:]))
    return rows.reshape(-1, n)[:n]


def validate_distance_matrix(d: Array, *, atol: float = 1e-5) -> dict:
    """Structural checks the PERMANOVA engine relies on."""
    sym = float(jnp.max(jnp.abs(d - d.T)))
    diag = float(jnp.max(jnp.abs(jnp.diagonal(d))))
    neg = float(jnp.min(d))
    ok = sym <= atol and diag <= atol and neg >= -atol
    return {"symmetric_maxerr": sym, "diag_maxabs": diag,
            "min_value": neg, "ok": ok}
