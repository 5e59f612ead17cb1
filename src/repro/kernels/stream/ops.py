"""jit'd wrappers for the STREAM kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common as _c
from repro.kernels.stream import kernel as _k

OPS = ("copy", "scale", "add", "triad")

# moved bytes per element, per STREAM convention (read + write)
BYTES_PER_ELEM = {"copy": 2, "scale": 2, "add": 3, "triad": 3}


@functools.partial(jax.jit, static_argnames=("op", "block", "interpret"))
def stream_op(a, b, s=3.0, *, op="triad", block=65536,
              interpret: bool | None = None):
    n = a.shape[0]
    block = min(block, n)
    pad = (-n) % block
    if pad:
        a = jnp.pad(a, (0, pad))
        b = jnp.pad(b, (0, pad))
    out = _k.stream_pallas(a, b, s, op=op, block=block,
                           interpret=_c.interpret_mode(interpret))
    return out[:n]
