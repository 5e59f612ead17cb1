"""Device-mesh construction for the production topology.

Single pod:  (16, 16)      -> ("data", "model")   = 256 chips
Multi-pod:   (2, 16, 16)   -> ("pod", "data", "model") = 512 chips

Functions, never module-level constants — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests, small hosts) with Auto axis types."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model_ways: int = 1) -> Mesh:
    """Mesh over whatever devices exist locally (examples/benchmarks)."""
    n = len(jax.devices())
    data = max(n // model_ways, 1)
    return make_mesh((data, model_ways), ("data", "model"))
