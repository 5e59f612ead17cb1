"""Unified distance-implementation registry (stage 1 of the pipeline).

Mirrors `repro.engine.registry` for the distance stage: every way this repo
can turn an (n, d) abundance table into pairwise distances sits behind one
interface with capability metadata the pipeline planner dispatches on.

Three kinds per metric (where available):

  dense     single full-matrix jnp form (Gram trick / broadcast) — lowest
            latency while the O(n^2)..O(block*n*d) transients fit
  blocked   row-streaming jnp driver over the same row primitives — the
            cache-friendly CPU form, and the only dense-free producer for
            the pipeline's stream/fused materializations
  pallas    the tiled TPU kernels (interpreted on the CPU) — rectangular,
            so they serve both dense construction and row slabs

Every impl exposes BOTH a dense builder and a row-slab builder (the dense
matrix is just the all-rows slab), so the planner's materialization choice
(dense / stream / fused) is orthogonal to the impl choice — exactly like
the s_W registry keeps dataflow orthogonal to scheduling.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Mapping, Optional, Tuple

import jax.numpy as jnp

from repro.core import distance as _dist

Array = object


# ---------------------------------------------------------------------------
# Residency tiers: one bandwidth model for every level features can live at.
# The planner's working-set arithmetic is the single source of truth from
# VMEM down to disk — the MI300A unified-memory residency argument extended
# one tier below HBM (out-of-core slab streaming).
# ---------------------------------------------------------------------------

RESIDENCY_TIERS = ("vmem", "hbm", "host", "disk")

# Model bandwidths (B/s). vmem: TPU-class on-chip SRAM order of magnitude;
# hbm is the device's HBM peak (repro.hw, keyed by device_kind); host:
# DDR-class staging the prefetcher reads through; disk: NVMe-class
# sequential read. $REPRO_TIER_GBPS_<TIER> overrides any of them (GB/s).
_VMEM_BPS = 22e12
_HOST_BPS = 64e9
_DISK_BPS = 2e9


def tier_bandwidth_gbps(tier: str, device_kind: Optional[str] = None) -> float:
    """Modelled bandwidth of one residency tier in GB/s ('hbm' is the
    device's HBM peak from the repro.hw table; an unknown device raises)."""
    if tier not in RESIDENCY_TIERS:
        raise ValueError(f"unknown residency tier {tier!r}; "
                         f"one of {RESIDENCY_TIERS}")
    override = os.environ.get(f"REPRO_TIER_GBPS_{tier.upper()}")
    if override:
        return float(override)
    if tier == "vmem":
        return _VMEM_BPS / 1e9
    if tier == "host":
        return _HOST_BPS / 1e9
    if tier == "disk":
        return _DISK_BPS / 1e9
    from repro import hw
    return hw.device_spec(device_kind).hbm_bandwidth / 1e9


def residency_tier(feature_bytes: float, *, device_budget_bytes: float,
                   host_budget_bytes: float) -> str:
    """Where the feature table LIVES during the sweep: 'hbm' while its f32
    form fits the device budget (stream the cache once, then run the
    in-memory bridges), 'host'/'disk' otherwise (out-of-core slab
    streaming; the tiers differ only in the bandwidth the traffic model
    charges — page-cache-warm vs cold reads)."""
    if feature_bytes <= device_budget_bytes:
        return "hbm"
    if feature_bytes <= host_budget_bytes:
        return "host"
    return "disk"


def ooc_disk_traffic_bytes(n_slabs: int, disk_bytes: float) -> float:
    """Modelled bytes read from the slab cache for ONE full OOC sweep: per
    row slab, the row operand plus the entire column stream — (n_slabs+1)
    passes over the on-disk table. Independent of n_perms: every
    permutation chunk consumes the LIVE assembled row slab, so the
    permutation axis adds no disk traffic (that is the whole point of
    fusing the sweep into the stream)."""
    return float(disk_bytes) * (int(n_slabs) + 1)


@dataclasses.dataclass(frozen=True)
class DistanceImpl:
    """One distance implementation plus planner-facing metadata.

    make_prepare(**tuning) -> prepare(x) -> xprep        one-off transform
    make_rows(**tuning)    -> rows(xb, xprep) -> (b, n)  row-slab builder
    make_dense(**tuning)   -> dense(x) -> (n, n)         full matrix
    """
    name: str                      # "<metric>.<kind>"
    metric: str
    kind: str                      # 'dense' | 'blocked' | 'pallas'
    backends: Tuple[str, ...]      # backends where this form is performant
    tuning: Mapping[str, int]
    make_prepare: Callable[..., Callable]
    make_rows: Callable[..., Callable]
    make_dense: Callable[..., Callable]
    workset_bytes: Callable[[int, int, int], int]
    # (n, d, row_block) -> peak TRANSIENT bytes beyond inputs/outputs
    max_n: Optional[int] = None    # None = unbounded
    description: str = ""

    def bound(self, **overrides):
        """(prepare, rows, dense) callables with tuning resolved."""
        kw = {k: v for k, v in {**self.tuning, **overrides}.items()
              if k in self.tuning}
        key = (self.name, tuple(sorted(kw.items())))
        fns = _BOUND_CACHE.get(key)
        if fns is None:
            fns = _BOUND_CACHE[key] = (self.make_prepare(**kw),
                                       self.make_rows(**kw),
                                       self.make_dense(**kw))
        return fns


_REGISTRY: dict = {}
_BOUND_CACHE: dict = {}


def register(impl: DistanceImpl) -> DistanceImpl:
    if impl.name in _REGISTRY:
        raise ValueError(f"duplicate distance impl {impl.name!r}")
    _REGISTRY[impl.name] = impl
    return impl


def get(name: str) -> DistanceImpl:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown distance impl {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def names(*, metric: Optional[str] = None, backend: Optional[str] = None,
          kind: Optional[str] = None):
    """Registered impl names, optionally filtered by capability."""
    out = []
    for n, impl in _REGISTRY.items():
        if metric is not None and impl.metric != metric:
            continue
        if backend is not None and backend not in impl.backends:
            continue
        if kind is not None and impl.kind != kind:
            continue
        out.append(n)
    return sorted(out)


def metrics():
    return sorted({impl.metric for impl in _REGISTRY.values()})


# ---------------------------------------------------------------------------
# Registration.
# ---------------------------------------------------------------------------

def _const(fn):
    def make(**_tuning):
        return fn
    return make


def _make_true_dense(metric):
    """Single-shot full-matrix form: all rows against all rows in one call
    (the GPU-brute analogue — maximum parallel width, O(n*n[*d]) transients
    exactly as the workset model charges)."""
    mdef = _dist.ROW_METRICS[metric]

    def make(**_tuning):
        def dense(x):
            xp = mdef.prepare(x)
            return _dist._zero_diag(mdef.rows(xp, xp))
        return dense
    return make


def _make_dense_from_rows(metric):
    mdef = _dist.ROW_METRICS[metric]

    def make(**tuning):
        block = tuning.get("block", 256)

        def dense(x):
            xp = mdef.prepare(x)
            return _dist._zero_diag(
                _dist._blocked_rows(mdef.rows, xp, block))
        return dense
    return make


def _make_pallas_rows(metric):
    kmetric = "euclidean" if metric == "aitchison" else metric

    def make(**tuning):
        from repro.kernels.distance import ops  # deferred: pallas import

        def rows(xb, xprep):
            return ops.pairwise_distance_rows(xb, xprep, metric=kmetric,
                                              **tuning)
        return rows
    return make


def _make_pallas_dense(metric):
    def make(**tuning):
        from repro.kernels.distance import ops  # deferred: pallas import
        prep = _dist.ROW_METRICS[metric].prepare
        kmetric = "euclidean" if metric == "aitchison" else metric

        def dense(x):
            return ops.pairwise_distance(prep(x), metric=kmetric, **tuning)
        return dense
    return make


def _ws_dense_gram(n, d, _block):
    # full Gram product + squared-distance intermediate
    return 8 * n * n


def _ws_dense_broadcast(n, d, block):
    # (block, n, d) broadcast intermediates inside the scan body (x2: |.|, +)
    return 8 * block * n * d


def _ws_rows_gram(n, d, block):
    return 8 * block * n


def _ws_rows_broadcast(n, d, block):
    return 8 * block * n * d


def _ws_pallas(n, d, block):
    # output slab plus padded feature copies (kept conservative)
    return 12 * min(block, n) * n


def _register_metric(metric, *, rows_ws, dense_ws, pallas_ok,
                     dense_backends, blocked_backends):
    mdef = _dist.ROW_METRICS[metric]
    register(DistanceImpl(
        name=f"{metric}.dense", metric=metric, kind="dense",
        backends=dense_backends, tuning={},
        make_prepare=_const(mdef.prepare), make_rows=_const(mdef.rows),
        make_dense=_make_true_dense(metric),
        workset_bytes=dense_ws,
        description=f"single full-matrix jnp {metric} (GPU-brute analogue: "
                    "maximum parallel width, largest transients)",
    ))
    register(DistanceImpl(
        name=f"{metric}.blocked", metric=metric, kind="blocked",
        backends=blocked_backends, tuning={"block": 256},
        make_prepare=_const(mdef.prepare), make_rows=_const(mdef.rows),
        make_dense=_make_dense_from_rows(metric),
        workset_bytes=rows_ws,
        description=f"row-streaming jnp {metric} (CPU-tiled analogue: "
                    "bounded working set; feeds stream/fused plans)",
    ))
    if pallas_ok:
        register(DistanceImpl(
            name=f"{metric}.pallas", metric=metric, kind="pallas",
            backends=("tpu",),
            tuning={"tile_r": 128, "tile_c": 128, "feat_block": 128},
            make_prepare=_const(mdef.prepare),
            make_rows=_make_pallas_rows(metric),
            make_dense=_make_pallas_dense(metric),
            workset_bytes=_ws_pallas,
            description=f"Pallas tiled {metric} kernel (VMEM-resident "
                        "accumulators; interpreted on the CPU)",
        ))


# euclidean / aitchison: Gram-trick forms are BLAS/MXU-native everywhere.
_register_metric("euclidean", rows_ws=_ws_rows_gram, dense_ws=_ws_dense_gram,
                 pallas_ok=True, dense_backends=("cpu", "gpu", "tpu"),
                 blocked_backends=("cpu", "gpu", "tpu"))
_register_metric("aitchison", rows_ws=_ws_rows_gram, dense_ws=_ws_dense_gram,
                 pallas_ok=True, dense_backends=("cpu", "gpu", "tpu"),
                 blocked_backends=("cpu", "gpu", "tpu"))
# braycurtis: broadcast form has (block, n, d) transients — blocked is the
# CPU winner, dense the GPU one (paper Fig. 1 transplanted to stage 1).
_register_metric("braycurtis", rows_ws=_ws_rows_broadcast,
                 dense_ws=_ws_dense_broadcast, pallas_ok=True,
                 dense_backends=("gpu",), blocked_backends=("cpu", "gpu"))
# jaccard: presence/absence matmul form — the Pallas tile accumulates
# |A ∩ B| on the MXU, so every registered metric now has a tiled impl.
_register_metric("jaccard", rows_ws=_ws_rows_gram, dense_ws=_ws_dense_gram,
                 pallas_ok=True, dense_backends=("cpu", "gpu", "tpu"),
                 blocked_backends=("cpu", "gpu", "tpu"))
# packed=1 switches jaccard.pallas to uint32 presence words + popcount
# tiles (bit-identical distances, 32x fewer feature bytes)
_REGISTRY["jaccard.pallas"] = dataclasses.replace(
    _REGISTRY["jaccard.pallas"],
    tuning={**_REGISTRY["jaccard.pallas"].tuning, "packed": 0})


# ---------------------------------------------------------------------------
# Precision knobs shared by the fused megakernel and the traffic models.
# ---------------------------------------------------------------------------

PRECISIONS = ("f32", "bf16", "fp8", "packed")


def precision_tag(tuning) -> str:
    """Canonical precision tag of a fused tuning dict (cache-key /
    reporting vocabulary; packed > fp8 > bf16 > f32)."""
    t = tuning or {}
    if t.get("feat_packed"):
        return "packed"
    if t.get("feat_fp8"):
        return "fp8"
    if t.get("feat_bf16"):
        return "bf16"
    return "f32"


def precision_tuning(tag: str) -> dict:
    """The fused tuning-knob dict selecting a precision tag."""
    if tag not in PRECISIONS:
        raise ValueError(f"unknown precision {tag!r}; one of {PRECISIONS}")
    return {"feat_bf16": int(tag == "bf16"), "feat_fp8": int(tag == "fp8"),
            "feat_packed": int(tag == "packed")}


def feat_element_bytes(tuning) -> float:
    """Bytes moved per FEATURE element at the tuning dict's precision
    (packed: 32 presence bits per uint32 word = 1/8 byte each)."""
    return {"f32": 4.0, "bf16": 2.0, "fp8": 1.0,
            "packed": 0.125}[precision_tag(tuning)]


# ---------------------------------------------------------------------------
# Fused-kernel (single-pass distance→s_W) implementation registry.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedImpl:
    """One single-pass distance→s_W implementation (the fused-kernel
    materialization bridge) plus planner-facing metadata.

    Unlike DistanceImpl, a fused impl produces no distance operand at all:
    it executes the whole features→s_W sweep (pipeline.streaming's
    `fused_kernel_sw` dispatches on `kind`). `workset_bytes` models the
    peak DEVICE residency beyond the (n, d) features and (chunk, n)
    labels as a function of (n, d, chunk, n_groups, row_block) — for the
    Pallas megakernel that is a handful of VMEM tiles, independent of n.
    """
    name: str                      # "<metric>.fusedk.<kind>"
    metric: str
    kind: str                      # 'pallas' | 'xla'
    backends: Tuple[str, ...]      # backends where this form is performant
    tuning: Mapping[str, int]
    workset_bytes: Callable[[int, int, int, int, int], int]
    kernel_metric: str             # megakernel body (aitchison→euclidean)
    description: str = ""


_FUSED_REGISTRY: dict = {}


def register_fused(impl: FusedImpl) -> FusedImpl:
    if impl.name in _FUSED_REGISTRY:
        raise ValueError(f"duplicate fused impl {impl.name!r}")
    _FUSED_REGISTRY[impl.name] = impl
    return impl


def get_fused(name: str) -> FusedImpl:
    try:
        return _FUSED_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown fused impl {name!r}; "
            f"registered: {sorted(_FUSED_REGISTRY)}") from None


def fused_names(*, metric: Optional[str] = None,
                backend: Optional[str] = None,
                kind: Optional[str] = None):
    """Registered fused-kernel impl names, filtered by capability."""
    out = []
    for n, impl in _FUSED_REGISTRY.items():
        if metric is not None and impl.metric != metric:
            continue
        if backend is not None and backend not in impl.backends:
            continue
        if kind is not None and impl.kind != kind:
            continue
        out.append(n)
    return sorted(out)


def _ws_fused_pallas(n, d, chunk, n_groups, row_block):
    # 4 VMEM scratch tiles + the (chunk,) accumulator — independent of n²
    tr = tc = 128
    return 16 * tr * tc + 4 * chunk


def _ws_fused_xla(n, d, chunk, n_groups, row_block):
    # one (row_block, n) D² slab + the (chunk, n, G) one-hot factor
    return 4 * row_block * n + 4 * chunk * n * (n_groups + 1)


for _metric in ("euclidean", "aitchison", "braycurtis", "jaccard"):
    _kmetric = "euclidean" if _metric == "aitchison" else _metric
    # The precision-knob family (mutually exclusive; planner/autotune
    # values land in the persisted cache entry's tuning dict alongside
    # tile sizes): feat_bf16 halves HBM feature traffic, feat_fp8
    # quarters it (per-study scale calibration, fp32 accumulation),
    # feat_packed (jaccard only) cuts it 32x via uint32 presence words
    # with bit-identical results.
    _prec = {"feat_bf16": 0, "feat_fp8": 0}
    if _kmetric == "jaccard":
        _prec["feat_packed"] = 0
    register_fused(FusedImpl(
        name=f"{_metric}.fusedk.pallas", metric=_metric, kind="pallas",
        backends=("tpu",),
        tuning={"tile_r": 128, "tile_c": 128, "feat_block": 128,
                "perm_block": 16, **_prec},
        workset_bytes=_ws_fused_pallas, kernel_metric=_kmetric,
        description=f"Pallas megakernel: {_metric} D² tiles built and "
                    "contracted in VMEM; D² never touches HBM "
                    "(feat_bf16/feat_fp8/feat_packed shrink feature-slab "
                    "traffic 2x/4x/32x)",
    ))
    register_fused(FusedImpl(
        name=f"{_metric}.fusedk.xla", metric=_metric, kind="xla",
        backends=("cpu", "gpu", "tpu"),
        tuning=dict(_prec),
        workset_bytes=_ws_fused_xla, kernel_metric=_kmetric,
        description=f"one-jit {_metric} scan-of-scans: the megakernel "
                    "dataflow as a single XLA program (no per-cell host "
                    "sync; the off-TPU fused-kernel form; precision knobs "
                    "round-trip the feature slabs)",
    ))


def fused_feat_traffic_bytes(spec: FusedImpl, n: int, d: int, tuning=None,
                             row_block: int = 256) -> float:
    """Modelled HBM feature-slab bytes for ONE permutation chunk's sweep
    at the tuning dict's precision.

    Pallas megakernel: each (i, j) tile pair re-reads a (tile_r, d) and a
    (tile_c, d) slab at the slab's element width, so traffic ≈
    bpe*d*n*(n/tile_r + n/tile_c). XLA one-pass: each row block re-reads
    the full table once ≈ 4*d*n*(n/row_block + 1) — its precision knobs
    are value-parity round-trips (the slabs stream as f32), so no traffic
    credit. This is the planner's per-precision reporting model
    (plan.explain), not a hardware counter."""
    t = {**dict(spec.tuning), **(tuning or {})}
    if spec.kind == "pallas":
        bpe = feat_element_bytes(t)
        tr = int(t.get("tile_r", 128))
        tc = int(t.get("tile_c", 128))
        nti = -(-n // tr)
        ntj = -(-n // tc)
        return bpe * d * nti * ntj * (tr + tc)
    return 4.0 * d * n * (-(-n // max(int(row_block), 1)) + 1)


def fused_workset_bytes(spec: FusedImpl, n: int, d: int, chunk: int,
                        n_groups: int, row_block: int,
                        tuning=None) -> float:
    """Precision-aware peak-residency model: the base workset_bytes plus
    the resident feature tiles at the selected element width (the base
    FusedImpl.workset_bytes signature is frozen; this module-level form
    adds the precision term)."""
    base = spec.workset_bytes(n, d, chunk, n_groups, row_block)
    t = {**dict(spec.tuning), **(tuning or {})}
    if spec.kind == "pallas":
        bpe = feat_element_bytes(t)
        tr = int(t.get("tile_r", 128))
        tc = int(t.get("tile_c", 128))
        fb = int(t.get("feat_block", 128))
        return base + bpe * (tr + tc) * fb
    # xla: an active precision knob materializes one round-tripped f32
    # copy of the table during prepare
    return base + (4.0 * n * d if precision_tag(t) != "f32" else 0.0)
