"""Joint two-stage planner: distance construction + s_W under ONE plan.

PR 1's engine planner picks the s_W dataflow from the paper's Fig. 1 result
(CPU-tiled vs GPU-brute). On the full features→p-value pipeline that choice
is only half the problem: for large n the distance stage dominates wall
clock (ROADMAP), and — as the MI300A unified-memory literature stresses —
whole-pipeline DATAFLOW (what gets materialized, and where) decides whether
memory-heavy codes win on APU-class hardware. So this planner decides, in
one place:

  stage 1   which distance impl (dense / blocked / Pallas per backend and
            transient-memory model, mirroring tiled-vs-brute), and its
            row-block size
  bridge    the materialization strategy: 'dense' (D then mat2 — two (n,n)
            transients), 'stream' (square row blocks into ONE mat2 buffer;
            never resident twice), 'fused' (no (n,n) array at all; row
            slabs feed permutation chunks directly), or 'fused-kernel'
            (single-pass: distance tiles built AND contracted inside one
            program — the Pallas megakernel on TPU, a one-jit XLA sweep
            elsewhere — so D² slabs never round-trip through HBM)
  stage 2   the engine Plan (impl + tuning + streaming chunk) for s_W,
            delegated to repro.engine.planner — including its persisted
            autotune measurements

The fused-kernel plan is joint across every knob: tile_r/tile_c/feat_block/
perm_block come from the fused registry's defaults overlaid with persisted
autotune measurements (`autotune_stage1` / `autotune_fused` time candidates
on the real operands and park the winners in the same per-host cache the
engine planner uses, keyed by (backend, metric, impl)).

`plan_pipeline()` is pure shape/backend arithmetic, like `engine.plan()`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax

from repro.engine import planner as _eplanner
from repro.pipeline import registry as _dreg

# Matrix-residency budget for the bridge decision. Distinct from the engine's
# label budget: this one governs the O(n^2) distance operands.
DEFAULT_MATRIX_BUDGET_BYTES = 1024 * 1024 ** 2
# Transient slab budget for picking the row block (and the dense/blocked
# stage-1 cut on CPU, standing in for the paper's LLC argument).
DEFAULT_SLAB_BUDGET_BYTES = 128 * 1024 ** 2
MIN_ROW_BLOCK = 8
MAX_ROW_BLOCK = 4096
PALLAS_MIN_N = 256
# Residency budgets for the out-of-core decision: the f32 feature table must
# fit the device budget to run the resident bridges, and the host budget
# only grades the bandwidth model (page-cache-warm vs cold disk reads).
DEFAULT_DEVICE_BUDGET_BYTES = 2 * 1024 ** 3
DEFAULT_HOST_BUDGET_BYTES = 32 * 1024 ** 3

MATERIALIZE_MODES = ("dense", "stream", "fused", "fused-kernel")


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """A resolved features→p-value execution plan."""
    metric: str
    dist_impl: str                # distance registry name
    dist_tuning: Dict[str, int]
    materialize: str              # 'dense' | 'stream' | 'fused' |
                                  # 'fused-kernel'
    row_block: int
    sw: _eplanner.Plan            # stage-2 engine plan
    backend: str
    reason: str
    fused_impl: Optional[str] = None      # fused registry name when the
                                          # bridge is 'fused-kernel'
    fused_tuning: Dict[str, int] = dataclasses.field(default_factory=dict)
    n: int = 0                            # problem shape (for explain())
    d: int = 0
    n_groups: int = 0
    residency: str = "hbm"                # where the features LIVE during
                                          # the sweep (registry tier)
    slab_rows: int = 0                    # on-disk slab height when the
                                          # features come from a slab cache
    disk_bytes: int = 0                   # slab-cache on-disk footprint

    def explain(self) -> str:
        """describe() plus the residency-tier bandwidth table (when the
        features stream from a slab cache) and the precision-aware
        memory-traffic model: the predicted feature-slab HBM bytes and
        peak workset per precision choice for the planned fused impl,
        with the planned one marked."""
        lines = [self.describe()]
        if self.residency != "hbm" and self.slab_rows and self.n:
            n_slabs = -(-self.n // self.slab_rows)
            traffic = _dreg.ooc_disk_traffic_bytes(n_slabs, self.disk_bytes)
            gbps = _dreg.tier_bandwidth_gbps(self.residency)
            lines.append(
                f"residency: {self.residency} (features "
                f"{4 * self.n * self.d / 2**20:.0f} MiB f32 exceed the "
                f"device budget; {n_slabs} slabs x {self.slab_rows} rows)")
            lines.append("tier bandwidth model (GB/s): " + ", ".join(
                f"{t}={_dreg.tier_bandwidth_gbps(t):.1f}"
                for t in _dreg.RESIDENCY_TIERS))
            lines.append(
                f"predicted slab-cache traffic per sweep: "
                f"{traffic / 2**20:.1f} MiB ({n_slabs + 1} passes over "
                f"{self.disk_bytes / 2**20:.1f} MiB on disk, independent "
                f"of n_perms), ~{traffic / (gbps * 1e9) * 1e3:.1f} ms at "
                f"the {self.residency} tier")
        if self.materialize != "fused-kernel" or not self.fused_impl \
                or not self.n:
            return "\n".join(lines)
        spec = _dreg.get_fused(self.fused_impl)
        planned = _dreg.precision_tag(self.fused_tuning)
        lines.append(
            f"predicted feature-slab HBM traffic per permutation chunk "
            f"(n={self.n}, d={self.d}, {spec.kind} kind):")
        for tag in _dreg.PRECISIONS:
            if tag == "packed" and spec.kernel_metric != "jaccard":
                continue
            t = {**self.fused_tuning, **_dreg.precision_tuning(tag)}
            traffic = _dreg.fused_feat_traffic_bytes(
                spec, self.n, self.d, t, self.row_block)
            workset = _dreg.fused_workset_bytes(
                spec, self.n, self.d, self.sw.chunk, self.n_groups,
                self.row_block, t)
            mark = "  <- planned" if tag == planned else ""
            lines.append(f"  {tag:>6}: {traffic/2**20:9.2f} MiB feat "
                         f"traffic, {workset/2**20:8.3f} MiB workset{mark}")
        return "\n".join(lines)

    def describe_stage1(self) -> str:
        """Stage 1 + bridge only — what the pipeline itself executes. The
        dense/stream bridges delegate stage 2 to engine.run, whose own plan
        record is authoritative there (autotune may override ours)."""
        if self.materialize == "fused-kernel":
            t = ",".join(f"{k}={v}"
                         for k, v in sorted(self.fused_tuning.items()))
            return (f"{self.fused_impl}[{t}] -> fused-kernel"
                    f"(rows={self.row_block})")
        t = ",".join(f"{k}={v}" for k, v in sorted(self.dist_tuning.items()))
        return (f"{self.dist_impl}[{t}] -> {self.materialize}"
                f"(rows={self.row_block})")

    def describe(self) -> str:
        return (f"{self.describe_stage1()} -> {self.sw.describe()}"
                f" | {self.reason}")


def _pick_dist_impl(metric: str, backend: str, n: int, d: int,
                    slab_budget: float):
    """Stage-1 impl by capability + transient model (Fig. 1 transplanted:
    bounded-working-set forms on CPU, widest forms on GPU, tiles on TPU).
    A persisted stage-1 shoot-out on this host overrides the model."""
    if metric not in _dreg.metrics():
        raise KeyError(f"unknown metric {metric!r}; "
                       f"registered: {_dreg.metrics()}")
    measured = measured_stage1(backend, metric, n)
    if measured is not None:
        return measured, ("persisted stage-1 autotune measurement "
                          f"({_eplanner.autotune_cache_path()})")
    if backend == "tpu" and n >= PALLAS_MIN_N and \
            _dreg.names(metric=metric, kind="pallas"):
        return (f"{metric}.pallas",
                "tiled Pallas kernel past the tile-viability point")
    dense = _dreg.get(f"{metric}.dense")
    # respect the registry's capability metadata: only consider the dense
    # form where it is registered as performant for this backend
    dense_ok = backend in dense.backends
    dense_ws = dense.workset_bytes(n, d, n)
    if dense_ok and backend == "gpu" and dense_ws <= slab_budget:
        return (f"{metric}.dense",
                "GPU prefers the widest form (Fig. 1 brute analogue)")
    if dense_ok and dense_ws <= min(slab_budget, _eplanner.CPU_LLC_BYTES):
        return (f"{metric}.dense",
                f"dense transients {dense_ws/2**20:.0f}MiB fit the cache "
                "model; single full-matrix form")
    why = (f"dense transients {dense_ws/2**20:.0f}MiB spill the slab/cache "
           "budget" if dense_ok else
           f"dense form not registered for backend {backend!r}")
    # blocked is the universal fallback: correct on every backend (its
    # `backends` field records where it is the PERFORMANT choice, not the
    # only places it runs), with the only bounded working set.
    return (f"{metric}.blocked",
            f"{why}; row-streaming form (Fig. 1 tiled analogue)")


def _pick_materialize(n: int, matrix_budget: float, metric: str):
    dense_bytes = 8 * n * n      # D + mat2 both live transiently
    mat2_bytes = 4 * n * n
    if dense_bytes <= matrix_budget:
        return "dense", (f"D+mat2 {dense_bytes/2**20:.0f}MiB fit the "
                         "matrix budget")
    if mat2_bytes <= matrix_budget:
        return "stream", (f"mat2 {mat2_bytes/2**20:.0f}MiB fits but D+mat2 "
                          "would not; stream row blocks into one buffer")
    why = (f"even one (n,n) buffer {mat2_bytes/2**20:.0f}MiB exceeds the "
           "matrix budget")
    if _dreg.fused_names(metric=metric):
        return "fused-kernel", (f"{why}; single-pass sweep (distance tiles "
                                "contracted in-kernel, D² never resident)")
    return "fused", (f"{why}; fuse row slabs into the permutation sweep")


def _pick_fused_impl(metric: str, backend: str, n: int,
                     tuning: Optional[Dict[str, int]] = None
                     ) -> Tuple[str, str]:
    """Fused-kernel impl: persisted shoot-out winner (at the requested
    precision), else the Pallas megakernel on TPU and the one-jit XLA
    sweep everywhere else."""
    measured = measured_fused(backend, metric, n, tuning)
    if measured is not None:
        return measured, "persisted fused-kernel autotune measurement"
    pallas = _dreg.fused_names(metric=metric, kind="pallas")
    if backend == "tpu" and n >= PALLAS_MIN_N and pallas:
        return pallas[0], "Pallas megakernel past the tile-viability point"
    xla = _dreg.fused_names(metric=metric, kind="xla")
    if not xla:  # pragma: no cover - every metric registers an xla form
        raise KeyError(f"no fused-kernel impl for metric {metric!r}")
    return xla[0], "one-jit XLA sweep (no kernel path on this backend)"


def _pick_row_block(n: int, d: int, impl: _dreg.DistanceImpl,
                    slab_budget: float) -> int:
    """Largest power-of-two row block whose transient working set fits."""
    block = MAX_ROW_BLOCK
    while block > MIN_ROW_BLOCK and \
            impl.workset_bytes(n, d, block) > slab_budget:
        block //= 2
    return max(MIN_ROW_BLOCK, min(block, n))


def plan_slab_rows(n: int, d: int, *,
                   device_budget_bytes: Optional[float] = None) -> int:
    """Slab height for BUILDING a cache destined for the OOC sweep: the
    largest power-of-two block whose live device footprint — one feature
    row slab + one column slab in flight plus the assembled (slab, n) m2
    row slab — stays a small fraction of the device budget, leaving the
    rest to the permutation chunks."""
    budget = (DEFAULT_DEVICE_BUDGET_BYTES if device_budget_bytes is None
              else device_budget_bytes)
    per_slab = budget / 16.0
    block = MAX_ROW_BLOCK
    while block > MIN_ROW_BLOCK and 4.0 * block * (2 * d + n) > per_slab:
        block //= 2
    return max(MIN_ROW_BLOCK, min(block, n))


def plan_pipeline(n: int, d: int, n_perms: int, n_groups: int, *,
                  metric: str = "braycurtis",
                  backend: Optional[str] = None,
                  dist_impl: Optional[str] = None,
                  materialize: Optional[str] = None,
                  row_block: Optional[int] = None,
                  matrix_budget_bytes: Optional[float] = None,
                  slab_budget_bytes: Optional[float] = None,
                  memory_budget_bytes: Optional[float] = None,
                  sw_impl: Optional[str] = None,
                  chunk: Optional[int] = None,
                  sw_tuning: Optional[Dict[str, int]] = None,
                  fused_impl: Optional[str] = None,
                  fused_tuning: Optional[Dict[str, int]] = None,
                  design_cols: Optional[int] = None,
                  features_on_disk: bool = False,
                  slab_rows: Optional[int] = None,
                  features_disk_bytes: Optional[int] = None,
                  device_budget_bytes: Optional[float] = None,
                  host_budget_bytes: Optional[float] = None
                  ) -> PipelinePlan:
    """Resolve the full two-stage plan for one problem.

    n_perms counts TOTAL permutation slots (requested + 1 observed), same
    convention as engine.plan(). Caller-pinned fields (dist_impl,
    materialize, row_block, sw_impl, chunk) are respected; the planner
    fills in the rest.

    design_cols: the dense-design basis width K (covariate/weighted/
    multi-factor designs) — the permutation-state workset models are
    sized for K design columns instead of G groups, and the engine plan
    is restricted to the matmul-family dense companions.

    features_on_disk: the features come from a slab cache (slab_rows is
    its build-time slab height, features_disk_bytes its on-disk size).
    The planner grades the residency tier from the f32 footprint against
    the device/host budgets; below 'hbm' it forces the out-of-core sweep:
    a fused bridge with row_block == slab_rows (the slab IS the row
    block) and the one-jit XLA form (the megakernel needs resident
    features).
    """
    backend = backend or _eplanner.default_backend()
    matrix_budget = (DEFAULT_MATRIX_BUDGET_BYTES
                     if matrix_budget_bytes is None else matrix_budget_bytes)
    slab_budget = (DEFAULT_SLAB_BUDGET_BYTES
                   if slab_budget_bytes is None else slab_budget_bytes)

    residency = "hbm"
    if features_on_disk:
        if not slab_rows:
            raise ValueError("features_on_disk=True requires slab_rows "
                             "(the cache's build-time slab height)")
        residency = _dreg.residency_tier(
            4.0 * n * d,
            device_budget_bytes=(DEFAULT_DEVICE_BUDGET_BYTES
                                 if device_budget_bytes is None
                                 else device_budget_bytes),
            host_budget_bytes=(DEFAULT_HOST_BUDGET_BYTES
                               if host_budget_bytes is None
                               else host_budget_bytes))
    ooc = residency != "hbm"
    if ooc:
        if materialize not in (None, "auto", "fused", "fused-kernel"):
            raise ValueError(
                f"features exceed the device budget (residency="
                f"{residency!r}); the {materialize!r} bridge needs a "
                "resident (n,n) operand — use materialize='auto'/'fused'/"
                "'fused-kernel' or raise device_budget_bytes")
        ooc_auto = materialize in (None, "auto")
        if ooc_auto:
            materialize = "fused-kernel"
        # The disk slab IS the unit of streaming: the sweep assembles one
        # (slab_rows, n) m2 row slab at a time, so the row block is not a
        # free knob out of core.
        row_block = int(slab_rows)

    if dist_impl is None or dist_impl == "auto":
        dname, dreason = _pick_dist_impl(metric, backend, n, d, slab_budget)
    else:
        dname = dist_impl if "." in dist_impl else f"{metric}.{dist_impl}"
        dreason = "caller-pinned distance impl"
    dspec = _dreg.get(dname)
    if dspec.metric != metric:
        raise ValueError(f"distance impl {dname!r} computes "
                         f"{dspec.metric!r}, not {metric!r}")
    if dspec.max_n is not None and n > dspec.max_n:
        raise ValueError(f"{dname!r} capped at n={dspec.max_n}, got {n}")

    mat_pinned = materialize not in (None, "auto")
    if not mat_pinned:
        mat, mreason = _pick_materialize(n, matrix_budget, metric)
    else:
        if materialize not in MATERIALIZE_MODES:
            raise ValueError(f"materialize={materialize!r}; expected one of "
                             f"{MATERIALIZE_MODES}")
        mat, mreason = materialize, "caller-pinned materialization"
        if ooc and ooc_auto:
            mreason = (f"features exceed the device budget (residency="
                       f"{residency}); out-of-core slab sweep")

    if row_block is None:
        # Size the row block against the ROWS working set: the stream/fused
        # bridges consume make_rows, whose transients scale with the block,
        # unlike a dense-kind impl's block-independent full-matrix model
        # (which would collapse the block to the minimum for nothing).
        rows_spec = (dspec if dspec.kind != "dense"
                     else _dreg.get(f"{metric}.blocked"))
        row_block = _pick_row_block(n, d, rows_spec, slab_budget)
    row_block = max(1, min(int(row_block), n))

    # Stage 2 via the engine planner (shares its persisted autotune state).
    # Both fused bridges compute s_W themselves in the one-hot matmul form,
    # so pin the engine plan to 'matmul' there — its chunk/budget arithmetic
    # still sizes the label blocks. A caller-pinned sw_impl that a fused
    # bridge cannot honor is a hard error when the bridge was pinned too,
    # and a downgrade to 'stream' when the bridge choice was ours.
    fused_modes = ("fused", "fused-kernel")
    pinned_sw = sw_impl if sw_impl not in (None, "auto") else None
    if mat in fused_modes and pinned_sw not in (None, "matmul"):
        if mat_pinned:
            raise ValueError(
                f"the {mat} bridge computes s_W in the one-hot matmul form "
                f"and cannot honor sw_impl={pinned_sw!r}; use "
                "sw_impl='auto'/'matmul' or materialize='stream'")
        mat = "stream"
        mreason += (f"; downgraded fused->stream to honor "
                    f"sw_impl={pinned_sw!r} (over matrix budget)")
    if mat in fused_modes and pinned_sw is None:
        pinned_sw = "matmul"
    if mat in fused_modes and chunk is None:
        # The fused step's working set is the one-hot block (chunk, n, G)
        # plus its (n, chunk*G) reshape — G-fold larger per permutation
        # than the engine's label-only model. Size the chunk against the
        # label budget with that factor so the fused sweep honors the same
        # memory contract. Dense designs swap G for the basis width K.
        budget = (_eplanner.DEFAULT_STREAM_BUDGET_BYTES
                  if memory_budget_bytes is None else memory_budget_bytes)
        cols = n_groups if design_cols is None else design_cols
        per_perm = 4.0 * n * (2 * cols + 1)
        chunk = int(max(1, min(budget // per_perm, n_perms)))
    sw = _eplanner.plan(n, n_perms, n_groups, backend=backend,
                        impl=pinned_sw,
                        memory_budget_bytes=memory_budget_bytes,
                        chunk=chunk, tuning=sw_tuning,
                        n_cols=design_cols)

    # Fused-kernel: resolve which single-pass impl runs the sweep and its
    # joint tile tuning (registry defaults <- persisted measurements <-
    # caller overrides).
    f_impl = None
    f_tuning: Dict[str, int] = {}
    if mat == "fused-kernel":
        if ooc and fused_impl in (None, "auto"):
            # The Pallas megakernel reads the whole resident feature table;
            # out of core only the one-jit XLA sweep applies (it consumes
            # the assembled m2 row slab).
            xla = _dreg.fused_names(metric=metric, kind="xla")
            if not xla:  # pragma: no cover - every metric registers one
                raise KeyError(f"no XLA fused impl for metric {metric!r}")
            f_impl, freason = xla[0], "one-jit XLA sweep over disk slabs"
        elif fused_impl in (None, "auto"):
            f_impl, freason = _pick_fused_impl(metric, backend, n,
                                               fused_tuning)
        else:
            f_impl = (fused_impl if "." in fused_impl
                      else f"{metric}.fusedk.{fused_impl}")
            freason = "caller-pinned fused impl"
        fspec = _dreg.get_fused(f_impl)
        if fspec.metric != metric:
            raise ValueError(f"fused impl {f_impl!r} computes "
                             f"{fspec.metric!r}, not {metric!r}")
        if ooc and fspec.kind != "xla":
            raise ValueError(
                f"fused impl {f_impl!r} ({fspec.kind} kind) needs the "
                "resident feature table; out-of-core sweeps require the "
                "XLA form")
        # Resolution order: registry defaults <- caller PRECISION knobs
        # (they select which measured entry applies) <- persisted tile
        # measurement at that precision <- caller tile overrides.
        f_tuning = dict(fspec.tuning)
        caller = ({k: v for k, v in fused_tuning.items() if k in f_tuning}
                  if fused_tuning else {})
        f_tuning.update(caller)
        entry = _eplanner.measured_entry(
            _fused_key(backend, metric, f_impl, f_tuning))
        if entry and isinstance(entry.get("tuning"), dict):
            f_tuning.update({k: int(v) for k, v in entry["tuning"].items()
                             if k in f_tuning})
        f_tuning.update(caller)
        mreason += f"; {freason}"

    # The planned row block IS the blocked impls' working-set knob — thread
    # it into the resolved tuning so every bridge (including dense, whose
    # builder scans the same row primitives) honors the slab budget.
    dist_tuning = dict(dspec.tuning)
    if "block" in dist_tuning:
        dist_tuning["block"] = row_block
    if ooc and _dreg.precision_tag(f_tuning) != "f32":
        raise ValueError(
            "out-of-core sweeps run f32 only: the reduced-precision slabs "
            "need a global calibration pass over the resident table")
    return PipelinePlan(
        metric=metric, dist_impl=dname, dist_tuning=dist_tuning,
        materialize=mat, row_block=row_block, sw=sw, backend=backend,
        reason=f"{dreason}; {mreason}", fused_impl=f_impl,
        fused_tuning=f_tuning, n=n, d=d, n_groups=n_groups,
        residency=residency, slab_rows=int(slab_rows or 0),
        disk_bytes=int(features_disk_bytes or 0))


# ---------------------------------------------------------------------------
# Persisted stage-1 / fused-kernel autotuning. Candidate timings live in the
# SAME per-host cache as the engine's s_W shoot-outs, one entry per
# (backend, metric, impl) key, so a serving host measures each candidate
# once ever and plan_pipeline() reads the winners back as its defaults.
# ---------------------------------------------------------------------------

def _stage1_key(backend: str, metric: str, impl: str) -> str:
    return f"dist|{backend}|{metric}|{impl}"


def _fused_key(backend: str, metric: str, impl: str,
               tuning: Optional[Dict[str, int]] = None) -> str:
    """Fused-kernel cache key. The precision knobs are part of the key —
    an fp8 timing must never feed an f32 plan — but the default (f32)
    precision keeps the historical untagged format so same-schema entries
    recorded before the precision knobs existed stay addressable."""
    tag = _dreg.precision_tag(tuning)
    base = f"fusedk|{backend}|{metric}|{impl}"
    return base if tag == "f32" else f"{base}|{tag}"


def _stage1_candidates(metric: str, backend: str):
    names = _dreg.names(metric=metric, kind="dense") + \
        _dreg.names(metric=metric, kind="blocked")
    if backend == "tpu":  # interpreted tiles are not a real candidate
        names += _dreg.names(metric=metric, kind="pallas")
    return names


def _argmin_measured(keys_by_name, n: int):
    """Winner among candidates whose persisted entry matches n's bucket.
    Requires EVERY candidate measured — a partial shoot-out must not
    short-circuit the heuristics."""
    bucket = _eplanner._bucket(n)
    times = {}
    for name, key in keys_by_name.items():
        entry = _eplanner.measured_entry(key)
        if not entry or entry.get("bucket") != bucket \
                or "us" not in entry:
            return None
        times[name] = entry["us"]
    return min(times, key=times.get) if times else None


def measured_stage1(backend: str, metric: str, n: int) -> Optional[str]:
    """Persisted stage-1 winner for this (backend, metric, n-bucket)."""
    cands = _stage1_candidates(metric, backend)
    return _argmin_measured(
        {c: _stage1_key(backend, metric, c) for c in cands}, n)


def measured_fused(backend: str, metric: str, n: int,
                   tuning: Optional[Dict[str, int]] = None) -> Optional[str]:
    """Persisted fused-kernel winner for this (backend, metric, n-bucket)
    at the precision the tuning knobs select (default f32)."""
    cands = [c for c in _dreg.fused_names(metric=metric)
             if backend in _dreg.get_fused(c).backends]
    return _argmin_measured(
        {c: _fused_key(backend, metric, c, tuning) for c in cands}, n)


def _time_call(fn, *args, **kw) -> float:
    jax.block_until_ready(fn(*args, **kw))   # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args, **kw))
    return time.perf_counter() - t0


def autotune_stage1(x, metric: str, *, backend: Optional[str] = None) -> str:
    """Time each stage-1 candidate's dense build on the real operands and
    persist one entry per (backend, metric, impl). Returns the winner."""
    import jax.numpy as jnp  # local: keep module import-light
    backend = backend or _eplanner.default_backend()
    x = jnp.asarray(x)
    n, d = (int(s) for s in x.shape)
    best, best_t = None, float("inf")
    for name in _stage1_candidates(metric, backend):
        spec = _dreg.get(name)
        _, _, dense_fn = spec.bound()
        try:
            t = _time_call(jax.jit(dense_fn), x)
        except Exception as exc:  # noqa: BLE001 — counted and logged
            _eplanner.record_candidate_failure("autotune_stage1", name, exc)
            continue
        _eplanner.record_entry(_stage1_key(backend, metric, name), {
            "impl": name, "us": round(t * 1e6, 1), "n": n, "d": d,
            "bucket": _eplanner._bucket(n)})
        if t < best_t:
            best, best_t = name, t
    if best is None:
        raise RuntimeError("autotune_stage1: no candidate ran successfully")
    return best


def autotune_fused(x, grouping, *, metric: str = "braycurtis",
                   backend: Optional[str] = None,
                   n_groups: Optional[int] = None,
                   sample_perms: int = 8,
                   key=None) -> str:
    """Time each fused-kernel candidate on a small permutation sample of
    the real operands; persist per-impl entries (timing + the tuning that
    achieved it) and return the winner."""
    import jax.numpy as jnp
    from repro.core import permutations as _perms
    from repro.pipeline import streaming as _streaming
    backend = backend or _eplanner.default_backend()
    x = jnp.asarray(x)
    grouping = jnp.asarray(grouping, jnp.int32)
    n, d = (int(s) for s in x.shape)
    if n_groups is None:
        n_groups = int(grouping.max()) + 1
    if key is None:
        key = jax.random.key(0)
    inv_gs = _perms.inv_group_sizes(grouping, n_groups)
    from repro.core import distance as _dist
    mdef = _dist.ROW_METRICS[metric]
    xprep = mdef.prepare(x)
    row_block = _pick_row_block(n, d, _dreg.get(f"{metric}.blocked"),
                                DEFAULT_SLAB_BUDGET_BYTES)
    best, best_t = None, float("inf")
    for name in _dreg.fused_names(metric=metric):
        spec = _dreg.get_fused(name)
        if backend not in spec.backends:
            continue
        tuning = dict(spec.tuning)

        def run():
            return _streaming.fused_kernel_sw(
                xprep, mdef.rows, grouping, inv_gs, key, sample_perms,
                impl=spec.kind, kernel_metric=spec.kernel_metric,
                row_block=row_block, chunk=sample_perms, tuning=tuning)

        try:
            run()                  # compile + warm (drivers host-sync)
            t0 = time.perf_counter()
            run()
            t = time.perf_counter() - t0
        except Exception as exc:  # noqa: BLE001 — counted and logged
            _eplanner.record_candidate_failure("autotune_fused", name, exc)
            continue
        _eplanner.record_entry(_fused_key(backend, metric, name, tuning), {
            "impl": name, "us": round(t * 1e6, 1), "n": n, "d": d,
            "bucket": _eplanner._bucket(n), "tuning": tuning})
        if t < best_t:
            best, best_t = name, t
    if best is None:
        raise RuntimeError("autotune_fused: no candidate ran successfully")
    return best
