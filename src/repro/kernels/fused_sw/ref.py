"""Pure-jnp oracle for the fused distance→s_W megakernel.

Same contract as ops.fused_sw_rows, written the slow/obvious way: build the
dense distance slab from the core row primitives, mask by global index,
square, contract with the one-hot factors. Tests compare the kernel against
this at odd tile sizes, prime n, and ragged group counts.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import distance as _dist
from repro.core import fstat

ROWS_FNS = {"euclidean": _dist.euclidean_rows,
            "braycurtis": _dist.braycurtis_rows,
            "jaccard": _dist.jaccard_rows}


def fused_sw_ref(x_rows, x, g_rows, g_cols, inv_gs, row_offset, *,
                 metric="braycurtis", n_valid=None, feat_bf16=0, feat_fp8=0,
                 feat_packed=0, feat_scale=None):
    """(s_W (P,), row_sums (nr,)) for one row slab — the test oracle.

    The precision knobs mirror ops.fused_sw_rows by ROUND-TRIPPING the
    prepared features through the kernel's representation before the
    dense math: bf16/fp8 quantize-dequantize, packed is an exact no-op
    on presence data (the float matmul over round-tripped presence
    features IS the bit-exact packed oracle)."""
    metric = {"aitchison": "euclidean"}.get(metric, metric)
    nr = x_rows.shape[0]
    n = x.shape[0]
    if n_valid is None:
        n_valid = n
    xr = jnp.asarray(x_rows, jnp.float32)
    xc = jnp.asarray(x, jnp.float32)
    if feat_bf16:
        xr = xr.astype(jnp.bfloat16).astype(jnp.float32)
        xc = xc.astype(jnp.bfloat16).astype(jnp.float32)
    elif feat_fp8:
        s = (_dist.fp8_metric_scale(xc, metric) if feat_scale is None
             else feat_scale)
        xr = _dist.fp8_roundtrip(xr, s)
        xc = _dist.fp8_roundtrip(xc, s)
    elif feat_packed:
        xr = (xr > 0).astype(jnp.float32)
        xc = (xc > 0).astype(jnp.float32)
    d = ROWS_FNS[metric](xr, xc)
    rows_g = row_offset + jnp.arange(nr)[:, None]
    cols_g = jnp.arange(n)[None, :]
    valid = (rows_g < n_valid) & (cols_g < n_valid) & (rows_g != cols_g)
    m2 = jnp.where(valid, d * d, 0.0)
    n_groups = inv_gs.shape[0]
    e = fstat.onehot_perm_factors(g_cols, n_groups, m2.dtype)    # (P, n, G)
    e_rows = fstat.onehot_perm_factors(g_rows, n_groups, m2.dtype)
    return (fstat.sw_matmul_contract(m2, e, e_rows, inv_gs),
            jnp.sum(m2, axis=1))
