"""STREAM bandwidth-probe kernels (the paper's Appendix A2 methodology).

The paper calibrates its roofline with a GPU-aware STREAM variant
(copy/scale/add/triad). We carry the same probe as Pallas kernels so the
framework can measure achievable HBM bandwidth on the target chip and feed
the measured (rather than datasheet) bandwidth into the roofline model —
exactly what the paper does with its 3.0 TB/s (GPU) / 0.2 TB/s (CPU) numbers
against the 5.3 TB/s datasheet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stream_body(a_ref, b_ref, s_ref, o_ref, *, op: str):
    a = a_ref[...]
    s = s_ref[0]
    if op == "copy":
        o_ref[...] = a
    elif op == "scale":
        o_ref[...] = s * a
    elif op == "add":
        o_ref[...] = a + b_ref[...]
    elif op == "triad":
        o_ref[...] = a + s * b_ref[...]
    else:  # pragma: no cover
        raise ValueError(op)


def stream_pallas(a, b, s, *, op: str, block: int, interpret: bool):
    (n,) = a.shape
    grid = (n // block,)
    kernel = functools.partial(_stream_body, op=op)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), a.dtype),
        interpret=interpret,
        name=f"stream_{op}",
    )(a, b, jnp.asarray([s], a.dtype))
