"""Unified s_W implementation registry.

The paper's central finding is that the optimal s_W dataflow is
*hardware-dependent*: the MI300A CPU cores want the cache-tiled Algorithm 2
while the GPU cores prefer brute force. Before this module existed the repo
hard-coded implementation choice in three disconnected places (`SW_IMPLS` in
core/permanova.py, `VARIANTS` in kernels/permanova_sw/ops.py, impl strings
in core/distributed.py). The registry is the single source of truth: every
implementation sits behind one batch interface

    fn(mat2, groupings, inv_group_sizes) -> (n_perms,) s_W

with capability metadata (performant backends, working-set model, padding
contract, row-sharded companion) that the planner consumes to pick the right
dataflow for the hardware at hand.

Registered implementations:

  brute / tiled / matmul          pure-jnp forms from core.fstat
  pallas_brute / pallas_permblock / pallas_matmul
                                  the Pallas TPU kernels (interpreted on the
                                  CPU), via kernels.permanova_sw.ops
  brute / matmul / pallas_matmul `.sharded`
                                  row-sharded partials for shard_map
                                  distribution (core.fstat.sw_*_rows_partial,
                                  kernels.permanova_sw.ops)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Mapping, Optional, Tuple

from repro.core import fstat

JNP_IMPLS = ("brute", "tiled", "matmul")
PALLAS_IMPLS = ("pallas_brute", "pallas_permblock", "pallas_matmul")


@dataclasses.dataclass(frozen=True)
class SwImpl:
    """One s_W implementation plus the metadata the planner dispatches on.

    make(**tuning) binds tuning knobs and returns the batch callable
    fn(mat2, groupings, inv_group_sizes) -> (n_perms,) float32.
    """
    name: str
    kind: str                      # 'jnp' | 'pallas'
    make: Callable[..., Callable]
    backends: Tuple[str, ...]      # backends where this dataflow is the
                                   # *performant* choice (all impls run
                                   # correctly on every backend)
    tuning: Mapping[str, int]      # default tuning knobs accepted by make()
    pad_contract: str              # 'none' (any n accepted as-is) |
                                   # 'internal' (pads n to a tile multiple
                                   # with a sentinel/zero region itself)
    description: str = ""
    sharded: Optional[Callable] = None
    # row-sharded companion with signature
    # (mat2_rows, row_offset, groupings, inv_group_sizes, **tuning) -> (P,)
    cols: Optional[Callable] = None
    # design-basis companion for DENSE designs (core.design): signature
    # (mat2, vperms (P, n, K)) -> (P, K) per-column quadratic forms.
    # Label-mode designs (single categorical factor, with or without
    # strata=) need no companion — every impl consumes permuted labels
    # unchanged. Impls whose dataflow is label-equality-specific (tiled,
    # the Pallas label kernels) leave this None; the planner falls back
    # to a matmul-family companion for dense designs. (The row-sharded
    # dense partial lives in fstat.sw_cols_rows_partial for shard_map
    # callers; matrix-resident dense sharding is a ROADMAP item.)

    def bound(self, **overrides) -> Callable:
        """Resolve tuning (defaults <- overrides) and build the callable.

        Bound callables are memoized per (impl, tuning): the scheduler's
        jitted step keys on the callable's identity, so a stable object
        means repeat run() calls reuse the compiled program instead of
        retracing (and the jit cache stays bounded)."""
        kw = {k: v for k, v in {**self.tuning, **overrides}.items()
              if k in self.tuning}
        cache_key = (self.name, tuple(sorted(kw.items())))
        fn = _BOUND_CACHE.get(cache_key)
        if fn is None:
            fn = _BOUND_CACHE[cache_key] = self.make(**kw)
        return fn


_REGISTRY: dict = {}
_BOUND_CACHE: dict = {}


def register(impl: SwImpl) -> SwImpl:
    if impl.name in _REGISTRY:
        raise ValueError(f"duplicate s_W impl {impl.name!r}")
    _REGISTRY[impl.name] = impl
    return impl


def get(name: str) -> SwImpl:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown s_W impl {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def names(*, backend: Optional[str] = None, kind: Optional[str] = None):
    """Registered impl names, optionally filtered by performant backend."""
    out = []
    for n, impl in _REGISTRY.items():
        if backend is not None and backend not in impl.backends:
            continue
        if kind is not None and impl.kind != kind:
            continue
        out.append(n)
    return sorted(out)


def get_sharded(name: str) -> Callable:
    """Row-sharded partial for `name`, falling back to the nearest family
    member (tiled, pallas_brute -> brute rows; pallas_permblock -> matmul
    rows) when the exact impl has no shard-map companion."""
    impl = get(name)
    if impl.sharded is not None:
        return impl.sharded
    fallback = "matmul" if ("matmul" in name or "permblock" in name) \
        else "brute"
    return get(fallback).sharded


def resolve_cols(name: str) -> Tuple[str, Callable]:
    """(impl name, dense-design companion) for `name`, falling back to the
    jnp matmul form when the exact impl is label-only (tiled and the
    Pallas label kernels route dense designs there — the contraction is
    the same tiled matmul against mat2 either way)."""
    impl = get(name)
    if impl.cols is not None:
        return name, impl.cols
    return "matmul", get("matmul").cols


def bound_cols(name: str, **overrides) -> Callable:
    """Dense-design companion for `name` with tuning bound (memoized, so
    the scheduler's jitted step sees a stable callable — same contract as
    SwImpl.bound)."""
    resolved, fn = resolve_cols(name)
    impl = get(resolved)
    kw = {k: v for k, v in overrides.items() if k in impl.tuning}
    cache_key = ("cols", resolved, tuple(sorted(kw.items())))
    bound = _BOUND_CACHE.get(cache_key)
    if bound is None:
        bound = _BOUND_CACHE[cache_key] = (
            functools.partial(fn, **kw) if kw else fn)
    return bound


# ---------------------------------------------------------------------------
# Registration.
# ---------------------------------------------------------------------------

def _make_jnp(fn):
    def make(**tuning):
        return functools.partial(fn, **tuning) if tuning else fn
    return make


def _make_pallas(variant):
    def make(**tuning):
        from repro.kernels.permanova_sw import ops  # deferred: pallas import
        return ops.make_sw_fn(variant, **tuning)
    return make


def _pallas_matmul_rows_partial(*args, **kw):
    from repro.kernels.permanova_sw import ops  # deferred: pallas import
    return ops.sw_matmul_rows_partial(*args, **kw)


register(SwImpl(
    name="brute", kind="jnp", make=_make_jnp(fstat.sw_brute),
    backends=("gpu",), tuning={"block": 32}, pad_contract="none",
    description="paper Algorithm 3 dataflow: every perm re-streams mat2 "
                "(the MI300A GPU winner)",
    sharded=fstat.sw_rows_partial,
    cols=fstat.sw_cols_brute,
))
register(SwImpl(
    name="tiled", kind="jnp", make=_make_jnp(fstat.sw_tiled),
    backends=("cpu",), tuning={"tile": 64, "block": 8}, pad_contract="internal",
    description="paper Algorithm 2 dataflow: cache-tiled loop nest "
                "(the MI300A CPU winner)",
))
register(SwImpl(
    name="matmul", kind="jnp", make=_make_jnp(fstat.sw_matmul),
    backends=("cpu", "tpu"), tuning={"perm_block": 64}, pad_contract="none",
    description="beyond-paper one-hot matmul reformulation (MXU/BLAS-native; "
                "amortizes each mat2 byte over perm_block*G columns)",
    sharded=fstat.sw_matmul_rows_partial,
    cols=fstat.sw_cols_matmul,
))
register(SwImpl(
    name="pallas_brute", kind="pallas", make=_make_pallas("brute"),
    backends=("tpu",), tuning={"tile_r": 256, "tile_c": 256},
    pad_contract="internal",
    description="Pallas transcription of Algorithm 3 (VPU masked "
                "square-accumulate, per-perm mat2 restream)",
))
register(SwImpl(
    name="pallas_permblock", kind="pallas", make=_make_pallas("permblock"),
    backends=("tpu",),
    tuning={"tile_r": 256, "tile_c": 256, "perm_block": 16},
    pad_contract="internal",
    description="paper's CPU tiling insight transplanted to TPU: one "
                "VMEM-resident mat2 tile serves a block of perms",
))
register(SwImpl(
    name="pallas_matmul", kind="pallas", make=_make_pallas("matmul"),
    backends=("tpu",),
    tuning={"tile_r": 256, "tile_c": 256, "perm_block": 16},
    pad_contract="internal",
    description="Pallas MXU one-hot contraction (highest arithmetic "
                "intensity; past the v5e ridge for perm_block*G >= ~512)",
    sharded=_pallas_matmul_rows_partial,
))
