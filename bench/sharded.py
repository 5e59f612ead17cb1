"""D born row-sharded over the chips, by the benchmark's own code.

A distance matrix larger than one chip never exists in one place: chip k
of the 'model' axis builds rows [k * rows, (k + 1) * rows) against all n
samples, with exactly the Bray-Curtis formula of `data.distances`, block
by block. The counts are whole numbers, so every sum is exact and each
distance is the same single rounding of the same quotient: the result
equals `data.distances` bit for bit, with zero rows past n to pad the
last chip's share.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from bench import data


def mesh(model_ways: int) -> Mesh:
    """('data', 'model') = (1, m) over the first `model_ways` devices, or
    over every device where there are fewer (the CPU tests' one)."""
    devices = np.array(jax.devices()[:model_ways])
    return Mesh(devices.reshape(1, -1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


@functools.partial(jax.jit, static_argnames=("mesh", "metric"))
def distances(x, *, mesh: Mesh, metric: str):
    """(n_pad, n) float32 Bray-Curtis distances of whole-number counts,
    sharded P('model', None); n_pad is n rounded up to the 'model' width
    and the pad rows are zero."""
    if metric != "braycurtis":
        raise ValueError(f"no row-sharded builder for metric {metric!r}")
    n, d = x.shape
    ways = mesh.shape["model"]
    rows = -(-n // ways)
    block = min(data._row_block(d), rows)
    tot = jnp.sum(x, axis=1)
    pad = rows * ways - n
    x_rows = jnp.pad(x, ((0, pad), (0, 0)))
    tot_rows = jnp.pad(tot, (0, pad))

    def shard(x_rows, tot_rows, xt, tot):
        first = jax.lax.axis_index("model") * rows

        def block_rows(lo):
            xb = jax.lax.dynamic_slice_in_dim(x_rows, lo, block, 0)
            num = jnp.sum(jnp.abs(xb[:, :, None] - xt[None, :, :]), axis=1)
            den = jax.lax.dynamic_slice_in_dim(tot_rows, lo, block)[:, None] \
                + tot[None, :]
            real = (first + lo + jnp.arange(block) < n)[:, None]
            return jnp.where(real, num / den, 0.0)

        def body(i, out):
            lo = jnp.minimum(i * block, rows - block)   # last block overlaps
            return jax.lax.dynamic_update_slice_in_dim(out, block_rows(lo),
                                                       lo, 0)

        out = jax.lax.pcast(jnp.zeros((rows, n), jnp.float32), "model",
                            to="varying")
        return jax.lax.fori_loop(0, -(-rows // block), body, out)

    return jax.shard_map(shard, mesh=mesh,
                         in_specs=(P("model", None), P("model"), P(), P()),
                         out_specs=P("model", None))(x_rows, tot_rows, x.T,
                                                     tot)
