"""Device-mesh construction: ("data", "model") meshes, or any named
axes, with Auto axis types.

Functions, never module-level constants — importing this module must not
touch jax device state (a subprocess may set XLA_FLAGS before first jax
init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests, small hosts) with Auto axis types."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model_ways: int = 1) -> Mesh:
    """Mesh over whatever devices exist locally (examples/benchmarks)."""
    n = len(jax.devices())
    data = max(n // model_ways, 1)
    return make_mesh((data, model_ways), ("data", "model"))
