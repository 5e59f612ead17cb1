"""Pipeline entry points: raw abundance table → F statistic and p-value.

pipeline()        one study: (n, d) features + (n,) labels, all the way to
                  the permutation p-value under one PipelinePlan.
pipeline_many()   stacked studies through ONE plan (the serving scenario):
                  (S, n, d) features + (S, n) labels.

Both route stage 2 through the hardware-aware engine; stage 1 and the
bridge (dense / stream / fused) come from this package. `permanova()`
delegates here when handed features instead of a matrix, and the launch
CLI exposes it as `--from-features`.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro import engine
from repro import obs as _obs
from repro.core import design as _design
from repro.data import slabcache as _slabcache
from repro.core import permutations
from repro.core.permanova import (PermanovaResult, f_from_sw,
                                  p_value_from_null)
from repro.pipeline import ordination as _ordination
from repro.pipeline import planner as _planner
from repro.pipeline import registry as _registry
from repro.pipeline import streaming as _streaming

Array = jax.Array


@functools.lru_cache(maxsize=None)
def _jit_dense(fn):
    """jit the registry's (memoized, so hashable-stable) dense distance
    callable. Eager execution re-traces any lax.map/scan inside it on
    EVERY call — the obs retrace counter flagged exactly that on warm
    dense-bridge runs."""
    return jax.jit(fn)


def _stage1_attrs(pl, dspec, n: int, d: int, bridge: str):
    """Span attrs for the distance stage: predicted traffic from the
    registry's workset model (the dense form builds one full matrix; the
    streaming form re-runs its per-slab workset once per row block), plus
    the 4n² mat2 write. None while tracing is off — the disabled path
    allocates nothing."""
    if not _obs.trace_enabled():
        return None
    block = n if bridge == "dense" else int(min(pl.row_block, n))
    n_blocks = -(-n // block)
    predicted = (float(dspec.workset_bytes(n, d, block)) * n_blocks
                 + 4.0 * n * n)
    _obs.metrics.inc("pipeline.predicted_bytes", predicted)
    return {"bridge": bridge, "impl": pl.dist_impl,
            "predicted_bytes": predicted}


def _fused_attrs(pl, n: int, d: int, n_groups: int, n_total: int, *,
                 fspec=None, studies: int = 1):
    """Span attrs for the fused bridges. The fused (two-stage) sweep
    rebuilds every D² row slab once per permutation chunk and streams the
    (chunk, n, G+1)-equivalent label state per (slab, chunk) pair; the
    fused-kernel sweep's feature traffic comes from the registry's
    precision-aware model (fp8/packed slabs shrink it)."""
    if not _obs.trace_enabled():
        return None
    block = int(min(pl.row_block, n))
    n_blocks = -(-n // block)
    ch = int(max(1, min(pl.sw.chunk, n_total)))
    n_chunks = -(-n_total // ch)
    if fspec is not None:
        predicted = (
            _registry.fused_feat_traffic_bytes(
                fspec, n, d, pl.fused_tuning, block) * n_chunks
            + 4.0 * ch * n * (n_groups + 1) * n_chunks)
        bridge, impl = "fused-kernel", fspec.name
    else:
        predicted = (4.0 * n * n
                     + n_blocks * n_chunks * 4.0 * ch * n * (n_groups + 1))
        bridge, impl = "fused", pl.sw.impl
    predicted *= studies
    _obs.metrics.inc("pipeline.predicted_bytes", predicted)
    attrs = {"bridge": bridge, "impl": impl, "predicted_bytes": predicted}
    if studies > 1:
        attrs["studies"] = studies
    return attrs


def pipeline(x: Array, grouping: Array, *, metric: str = "braycurtis",
             n_perms: int = 999, key: Optional[jax.Array] = None,
             n_groups: Optional[int] = None,
             dist_impl: str = "auto", sw_impl: str = "auto",
             materialize: str = "auto", row_block: Optional[int] = None,
             chunk: Optional[int] = None,
             memory_budget_bytes: Optional[float] = None,
             matrix_budget_bytes: Optional[float] = None,
             slab_budget_bytes: Optional[float] = None,
             dist_tuning: Optional[Dict[str, int]] = None,
             sw_tuning: Optional[Dict[str, int]] = None,
             fused_impl: str = "auto",
             fused_tuning: Optional[Dict[str, int]] = None,
             backend: Optional[str] = None,
             mesh=None,
             ordination: Optional[int] = None,
             covariates=None, strata=None, weights=None,
             autotune: bool = False,
             device_budget_bytes: Optional[float] = None,
             host_budget_bytes: Optional[float] = None,
             trace=None) -> PermanovaResult:
    """Full features→p-value PERMANOVA under one joint plan.

    x:           (n, d) abundance table (raw features, NOT distances) — or
                 a data.SlabCache (or its directory path): the feature
                 table stays on DISK and the planner grades its residency
                 tier against device_budget_bytes; below 'hbm' the sweep
                 runs out of core (async double-buffered slab prefetch
                 into the fused contraction), F/p equal to the in-memory
                 bridges at the same slab boundaries up to f32 rounding
                 of the distance tiles.
    materialize: 'auto' | 'dense' | 'stream' | 'fused' | 'fused-kernel' —
                 whether the (n, n) matrix is built outright, streamed into
                 a single buffer, never materialized at all, or (fused-
                 kernel) swept in a single pass with distance tiles
                 contracted in-kernel.
    fused_impl:  'auto' | 'pallas' | 'xla' (or a fused registry name) —
                 which single-pass implementation runs a fused-kernel plan.
    mesh:        optional jax.sharding.Mesh with a 'model' axis — runs the
                 fused-kernel sweep multi-device (row slabs over 'model',
                 permutations over the remaining axes, psum-reduced).
                 Implies materialize='fused-kernel'.
    ordination:  optional k — also compute the top-k PCoA axes into
                 `result.ordination` (coords, eigvals, explained
                 variance). The path rides the bridge's residency
                 contract: dense eigendecomposes the Gower matrix
                 outright, stream runs the implicit-operator subspace
                 iteration against the SAME resident mat2 (no second
                 (n, n) array), and the fused bridges re-stream
                 squared-distance slabs from the features (nothing
                 (n, n)-shaped, ever).
    covariates / strata / weights: design columns (see core.design and
    core.permanova.permanova) — any of them routes through the design
    path: same joint stage-1/bridge planning, with the permutation sweep
    contracting hat-matrix basis blocks (dense designs) or strata-
    restricted labels, and per-term statistics in `result.terms`.
    `grouping` may also be a prebuilt core.design.Design.

    trace:       telemetry for this call — True enables scoped span
                 tracing + metrics (obs.session), a string additionally
                 exports the Chrome trace_event JSON to that path on
                 return; None/False (default) leaves telemetry exactly as
                 the process had it (zero overhead when off). Inspect with
                 obs.report() / obs.trace.stage_table() afterwards.

    Remaining knobs mirror engine.run(); budgets split per stage
    (matrix/slab for distances, memory_budget_bytes for s_W labels).
    For a fixed key every materialization produces the same F and p-value
    (to fp32 accumulation order).
    """
    if trace:
        with _obs.session(trace if isinstance(trace, str) else None):
            return pipeline(
                x, grouping, metric=metric, n_perms=n_perms, key=key,
                n_groups=n_groups, dist_impl=dist_impl, sw_impl=sw_impl,
                materialize=materialize, row_block=row_block, chunk=chunk,
                memory_budget_bytes=memory_budget_bytes,
                matrix_budget_bytes=matrix_budget_bytes,
                slab_budget_bytes=slab_budget_bytes,
                dist_tuning=dist_tuning, sw_tuning=sw_tuning,
                fused_impl=fused_impl, fused_tuning=fused_tuning,
                backend=backend, mesh=mesh, ordination=ordination,
                covariates=covariates, strata=strata, weights=weights,
                autotune=autotune,
                device_budget_bytes=device_budget_bytes,
                host_budget_bytes=host_budget_bytes, trace=None)
    if key is None:
        key = jax.random.key(0)
    if isinstance(x, (str, os.PathLike)):
        x = _slabcache.SlabCache.open(x)
    if isinstance(x, _slabcache.SlabCache):
        return _pipeline_ooc(
            x, grouping, metric=metric, n_perms=n_perms, key=key,
            n_groups=n_groups, dist_impl=dist_impl, sw_impl=sw_impl,
            materialize=materialize, row_block=row_block, chunk=chunk,
            memory_budget_bytes=memory_budget_bytes,
            matrix_budget_bytes=matrix_budget_bytes,
            slab_budget_bytes=slab_budget_bytes, dist_tuning=dist_tuning,
            sw_tuning=sw_tuning, fused_impl=fused_impl,
            fused_tuning=fused_tuning, backend=backend, mesh=mesh,
            ordination=ordination, covariates=covariates, strata=strata,
            weights=weights, autotune=autotune,
            device_budget_bytes=device_budget_bytes,
            host_budget_bytes=host_budget_bytes)
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"features must be (n, d); got shape {x.shape}")
    n, d = x.shape
    design = None
    if isinstance(grouping, _design.Design):
        if covariates is not None or strata is not None \
                or weights is not None:
            raise ValueError("pass covariates/strata/weights either to "
                             "pipeline() or inside the Design, not both")
        design = grouping
    elif covariates is not None or strata is not None or weights is not None:
        design = _design.build(
            grouping=None if grouping is None else
            jnp.asarray(grouping, jnp.int32),
            covariates=covariates, strata=strata, weights=weights,
            n_groups=n_groups, n=int(n))
    if design is not None and design.is_plain_labels:
        grouping, n_groups, design = (design.grouping, design.n_groups,
                                      None)
    if design is not None:
        return _pipeline_design(
            x, design, metric=metric, n_perms=n_perms, key=key,
            dist_impl=dist_impl, sw_impl=sw_impl, materialize=materialize,
            row_block=row_block, chunk=chunk,
            memory_budget_bytes=memory_budget_bytes,
            matrix_budget_bytes=matrix_budget_bytes,
            slab_budget_bytes=slab_budget_bytes, dist_tuning=dist_tuning,
            sw_tuning=sw_tuning, fused_impl=fused_impl,
            fused_tuning=fused_tuning, backend=backend, mesh=mesh,
            ordination=ordination, autotune=autotune)
    grouping = jnp.asarray(grouping, dtype=jnp.int32)
    if n_groups is None:
        n_groups = int(jnp.max(grouping)) + 1
    n_total = n_perms + 1

    if mesh is not None:
        if materialize not in ("auto", "fused-kernel"):
            raise ValueError(
                "mesh execution is fused-kernel only; use "
                "materialize='auto'/'fused-kernel' (or core.distributed "
                "for matrix-resident sharding)")
        materialize = "fused-kernel"

    def _plan():
        return _planner.plan_pipeline(
            n, d, n_total, n_groups, metric=metric, backend=backend,
            dist_impl=dist_impl, materialize=materialize,
            row_block=row_block, matrix_budget_bytes=matrix_budget_bytes,
            slab_budget_bytes=slab_budget_bytes,
            memory_budget_bytes=memory_budget_bytes,
            sw_impl=sw_impl, chunk=chunk, sw_tuning=sw_tuning,
            fused_impl=fused_impl, fused_tuning=fused_tuning)

    pl = _plan()
    if autotune:
        # measure only what the resolved plan actually executes; winners
        # persist per host, so replanning afterwards reads them back
        if pl.materialize == "fused-kernel" and fused_impl == "auto":
            fused_impl = _planner.autotune_fused(
                x, grouping, metric=metric, backend=backend,
                n_groups=n_groups)
            pl = _plan()
        elif pl.materialize in ("dense", "stream") and dist_impl == "auto":
            # never for 'fused': the stage-1 shoot-out builds full dense
            # matrices, exactly the allocation that bridge exists to avoid
            dist_impl = _planner.autotune_stage1(x, metric, backend=backend)
            pl = _plan()
    dspec = _registry.get(pl.dist_impl)
    # planner-resolved tuning (row block folded in) <- caller overrides
    prepare, rows_fn, dense_fn = dspec.bound(
        **{**pl.dist_tuning, **(dist_tuning or {})})

    ordn = None
    if pl.materialize == "dense":
        with _obs.span(f"stage1.{metric}",
                       _stage1_attrs(pl, dspec, n, d, "dense")):
            dm = _obs.maybe_block(_jit_dense(dense_fn)(x))
        res = engine.run(dm, grouping, n_perms=n_perms, key=key,
                         n_groups=n_groups, impl=sw_impl,
                         memory_budget_bytes=memory_budget_bytes,
                         chunk=chunk, autotune=autotune, backend=backend,
                         tuning=sw_tuning)
        if ordination is not None:
            # the dense bridge already budgets (n, n) transients; the
            # centered matrix + eigh is the exact path
            with _obs.span("pipeline.pcoa"):
                ordn = _ordination.pcoa_eigh(dm * dm, ordination)
    elif pl.materialize == "stream":
        with _obs.span(f"stage1.{metric}",
                       _stage1_attrs(pl, dspec, n, d, "stream")):
            mat2, gower = _streaming.build_mat2_streaming(
                prepare(x), rows_fn, block=pl.row_block)
            mat2_dev = jnp.asarray(mat2)
        del mat2   # free the host buffer: ONE sustained (n, n) resident
                   # (the handoff copy itself is transiently 2x; the fused
                   # bridge is the option that never holds (n, n) at all)
        res = engine.run(mat2_dev, grouping, n_perms=n_perms,
                         key=key, n_groups=n_groups, impl=sw_impl,
                         memory_budget_bytes=memory_budget_bytes,
                         chunk=chunk, autotune=autotune, backend=backend,
                         tuning=sw_tuning, squared=True, s_t=gower.s_t)
        if ordination is not None:
            # implicit centered operator against the SAME resident mat2 +
            # the marginals the streaming pass already accumulated — the
            # Gower matrix itself is never materialized (one (n, n) array
            # stays the bridge's contract)
            with _obs.span("pipeline.pcoa"):
                ordn = _ordination.pcoa_subspace(mat2_dev, ordination,
                                                 stats=gower)
    elif pl.materialize == "fused":
        if autotune:
            warnings.warn(
                "autotune=True ignored: the fused bridge computes s_W in "
                "its one-hot matmul form (use materialize='stream'/'dense' "
                "to let measurements pick the s_W impl, or "
                "materialize='fused-kernel' for the measured single-pass "
                "candidates)", stacklevel=2)
        inv_gs = permutations.inv_group_sizes(grouping, n_groups)
        xprep = prepare(x)
        with _obs.span("bridge.fused",
                       _fused_attrs(pl, n, d, n_groups, n_total)):
            s_w, s_t, stats = _streaming.fused_sw(
                xprep, rows_fn, grouping, inv_gs, key, n_total,
                row_block=pl.row_block, chunk=pl.sw.chunk)
            s_w = _obs.maybe_block(s_w)
        f_all = f_from_sw(jnp.asarray(s_w, jnp.float32),
                          jnp.float32(s_t), n, n_groups)
        res = PermanovaResult(
            f_stat=f_all[0], p_value=p_value_from_null(f_all),
            s_t=jnp.float32(s_t), s_w=jnp.asarray(s_w[0], jnp.float32),
            f_perms=f_all, n_objects=n, n_groups=n_groups, n_perms=n_perms,
            method="pipeline[fused]",
            plan=(f"rows={stats.row_block}x{stats.n_row_blocks} "
                  f"chunks={stats.n_chunks} slab="
                  f"{stats.peak_slab_bytes/2**20:.1f}MiB"))
    elif pl.materialize == "fused-kernel":
        inv_gs = permutations.inv_group_sizes(grouping, n_groups)
        fspec = _registry.get_fused(pl.fused_impl)
        xprep = prepare(x)
        with _obs.span("bridge.fused-kernel",
                       _fused_attrs(pl, n, d, n_groups, n_total,
                                    fspec=fspec)):
            if mesh is not None:
                if fspec.kind != "xla" and fused_impl not in (None, "auto"):
                    warnings.warn(
                        f"mesh execution runs the XLA fused sweep; pinned "
                        f"fused_impl={fused_impl!r} is not used",
                        stacklevel=2)
                s_w, s_t, stats = _streaming.fused_sw_sharded(
                    mesh, xprep, rows_fn, grouping, inv_gs, key, n_total,
                    row_block=pl.row_block, chunk=pl.sw.chunk)
            else:
                s_w, s_t, stats = _streaming.fused_kernel_sw(
                    xprep, rows_fn, grouping, inv_gs, key, n_total,
                    impl=fspec.kind, kernel_metric=fspec.kernel_metric,
                    row_block=pl.row_block, chunk=pl.sw.chunk,
                    tuning=pl.fused_tuning)
            s_w = _obs.maybe_block(s_w)
        f_all = f_from_sw(jnp.asarray(s_w, jnp.float32),
                          jnp.float32(s_t), n, n_groups)
        res = PermanovaResult(
            f_stat=f_all[0], p_value=p_value_from_null(f_all),
            s_t=jnp.float32(s_t), s_w=jnp.asarray(s_w[0], jnp.float32),
            f_perms=f_all, n_objects=n, n_groups=n_groups, n_perms=n_perms,
            method="pipeline[fused-kernel]",
            plan=(f"{stats.impl}{'+mesh' if mesh is not None else ''} "
                  f"rows={stats.row_block} chunks={stats.n_chunks} "
                  f"slab={stats.peak_slab_bytes/2**20:.2f}MiB "
                  f"labels={stats.peak_label_bytes/2**20:.2f}MiB"))
    else:  # pragma: no cover - planner validates
        raise ValueError(pl.materialize)

    if ordination is not None and ordn is None:
        # fused bridges: every matvec of the subspace iteration re-streams
        # squared-distance row slabs from the feature table — ordination
        # inherits the fused contract (nothing (n, n)-shaped ever exists);
        # xprep was bound by the fused branch above
        with _obs.span("pipeline.pcoa"):
            ordn = _ordination.pcoa_features(xprep, rows_fn, ordination,
                                             row_block=pl.row_block)

    if pl.materialize in ("fused", "fused-kernel"):
        # the fused bridge IS stage 2; the joint plan string is authoritative
        executed_sw = pl.sw.impl
        plan_str = f"{pl.describe()} :: {res.plan}"
    else:
        # engine.run planned stage 2 (autotune may have overridden ours) —
        # report its record once instead of a possibly-contradicting copy
        executed_sw = (res.method.split("[", 1)[1].rstrip("]")
                       if "[" in res.method else pl.sw.impl)
        plan_str = f"{pl.describe_stage1()} | {pl.reason} :: {res.plan}"
    return dataclasses.replace(
        res,
        method=f"pipeline[{pl.dist_impl}->{pl.materialize}->{executed_sw}]",
        plan=plan_str, ordination=ordn)


def _pipeline_ooc(cache: "_slabcache.SlabCache", grouping, *, metric: str,
                  n_perms: int, key, n_groups, dist_impl, sw_impl,
                  materialize, row_block, chunk, memory_budget_bytes,
                  matrix_budget_bytes, slab_budget_bytes, dist_tuning,
                  sw_tuning, fused_impl, fused_tuning, backend, mesh,
                  ordination, covariates, strata, weights, autotune,
                  device_budget_bytes, host_budget_bytes
                  ) -> PermanovaResult:
    """pipeline() when the features live in a slab cache.

    The planner grades the residency tier from the f32 footprint: 'hbm'
    loads the cache once and reruns the ordinary in-memory path (same
    plan, same programs); 'host'/'disk' run the out-of-core sweep — the
    async prefetcher stages slab k+1 while slab k's distance tiles are
    assembled and contracted by the UNCHANGED fused steps, so F/p match
    the in-memory bridges at row_block == slab_rows up to the f32
    rounding of the tile-wise distance step (Gram-form metrics block
    their feature sums by tile shape).
    """
    n, d = cache.n, cache.d
    n_total = n_perms + 1
    if mesh is not None:
        raise ValueError("slab-cache features run single-device; mesh "
                         "execution needs the resident table")
    if cache.fmt == "csr" and metric != "jaccard":
        raise ValueError(
            f"csr slab caches store presence structure only; metric "
            f"{metric!r} needs the dense format (jaccard reads it)")

    design = None
    if isinstance(grouping, _design.Design):
        if covariates is not None or strata is not None \
                or weights is not None:
            raise ValueError("pass covariates/strata/weights either to "
                             "pipeline() or inside the Design, not both")
        design = grouping
    elif covariates is not None or strata is not None or weights is not None:
        design = _design.build(
            grouping=None if grouping is None else
            jnp.asarray(grouping, jnp.int32),
            covariates=covariates, strata=strata, weights=weights,
            n_groups=n_groups, n=n)
    if design is not None and design.is_plain_labels:
        grouping, n_groups, design = (design.grouping, design.n_groups,
                                      None)
    dense_mode = design is not None and design.mode == _design.MODE_DENSE
    k = design.k_cols if dense_mode else None
    if design is None:
        grouping = jnp.asarray(grouping, jnp.int32)
        if n_groups is None:
            n_groups = int(jnp.max(grouping)) + 1
        n_groups_plan = n_groups
    else:
        if design.n != n:
            raise ValueError(f"design is for n={design.n}, cache is "
                             f"({n}, {d})")
        n_groups_plan = (design.n_groups if design.n_groups is not None
                         else design.rank)

    pl = _planner.plan_pipeline(
        n, d, n_total, n_groups_plan, metric=metric, backend=backend,
        dist_impl=dist_impl, materialize=materialize, row_block=row_block,
        matrix_budget_bytes=matrix_budget_bytes,
        slab_budget_bytes=slab_budget_bytes,
        memory_budget_bytes=memory_budget_bytes, sw_impl=sw_impl,
        chunk=chunk, sw_tuning=sw_tuning, fused_impl=fused_impl,
        fused_tuning=fused_tuning, design_cols=k, features_on_disk=True,
        slab_rows=cache.slab_rows, features_disk_bytes=cache.disk_bytes,
        device_budget_bytes=device_budget_bytes,
        host_budget_bytes=host_budget_bytes)

    if pl.residency == "hbm":
        # the f32 table fits the device budget: stream the cache into
        # memory ONCE and run the ordinary resident path
        res = pipeline(
            cache.to_array(), grouping if design is None else design,
            metric=metric, n_perms=n_perms, key=key, n_groups=n_groups,
            dist_impl=dist_impl, sw_impl=sw_impl, materialize=materialize,
            row_block=row_block, chunk=chunk,
            memory_budget_bytes=memory_budget_bytes,
            matrix_budget_bytes=matrix_budget_bytes,
            slab_budget_bytes=slab_budget_bytes, dist_tuning=dist_tuning,
            sw_tuning=sw_tuning, fused_impl=fused_impl,
            fused_tuning=fused_tuning, backend=backend,
            ordination=ordination, autotune=autotune)
        return dataclasses.replace(
            res, plan=f"{res.plan} | features=slab-cache(residency=hbm)")

    if ordination is not None:
        raise ValueError(
            "ordination needs resident features; raise "
            "device_budget_bytes (residency must reach 'hbm') or run it "
            "separately on a subsample")
    if autotune:
        warnings.warn(
            "autotune=True ignored out of core: the shoot-outs run on "
            "resident operands", stacklevel=3)

    dspec = _registry.get(pl.dist_impl)
    prepare, rows_fn, _ = dspec.bound(
        **{**pl.dist_tuning, **(dist_tuning or {})})
    onepass = pl.materialize == "fused-kernel"

    span_attrs = None
    if _obs.trace_enabled():
        predicted = _registry.ooc_disk_traffic_bytes(cache.n_slabs,
                                                     cache.disk_bytes)
        _obs.metrics.inc("pipeline.predicted_bytes", predicted)
        span_attrs = {"bridge": f"ooc-{pl.materialize}",
                      "residency": pl.residency,
                      "predicted_bytes": predicted}
    with _obs.span("bridge.ooc", span_attrs):
        if design is None:
            inv_gs = permutations.inv_group_sizes(grouping, n_groups)
            s_w, s_t, ost = _streaming.fused_sw_ooc(
                cache, rows_fn, prepare, grouping, inv_gs, key, n_total,
                chunk=pl.sw.chunk, onepass=onepass)
        elif dense_mode:
            s_cols, s_t, ost = _streaming.fused_sw_ooc_design(
                cache, rows_fn, prepare, design, key, n_total,
                chunk=pl.sw.chunk, onepass=onepass)
        else:
            inv_gs = permutations.inv_group_sizes(design.grouping,
                                                  design.n_groups)
            s_w, s_t, ost = _streaming.fused_sw_ooc(
                cache, rows_fn, prepare, design.grouping, inv_gs, key,
                n_total, chunk=pl.sw.chunk, strata=design.strata,
                onepass=onepass)
        if span_attrs is not None:
            # the span's attrs merge at __exit__, so the measured overlap
            # evidence lands in the trace artifact
            span_attrs["stall_ms"] = round(ost.stall_s * 1e3, 3)
            span_attrs["disk_bytes_read"] = ost.disk_bytes_read

    sweep = (f"residency={pl.residency} slabs={ost.n_slabs}"
             f"x{ost.slab_rows} chunks={ost.n_chunks} "
             f"read={ost.disk_bytes_read/2**20:.1f}MiB "
             f"stall={ost.stall_s*1e3:.1f}ms/{ost.sweep_s*1e3:.0f}ms")
    if design is None:
        f_all = f_from_sw(jnp.asarray(s_w, jnp.float32),
                          jnp.float32(s_t), n, n_groups)
        res = PermanovaResult(
            f_stat=f_all[0], p_value=p_value_from_null(f_all),
            s_t=jnp.float32(s_t), s_w=jnp.asarray(s_w[0], jnp.float32),
            f_perms=f_all, n_objects=n, n_groups=n_groups,
            n_perms=n_perms, method=f"pipeline[ooc-{pl.materialize}]",
            plan=sweep)
    elif dense_mode:
        res = engine.design_result(
            jnp.asarray(s_cols, jnp.float32), design, n_objects=n,
            n_perms=n_perms,
            method=f"pipeline-design[ooc-{pl.materialize}]", plan=sweep)
    else:
        res = engine.api.label_design_result(
            jnp.asarray(s_w, jnp.float32), jnp.float32(s_t), design,
            n_objects=n, n_perms=n_perms,
            method=f"pipeline[ooc-{pl.materialize}+strata]",
            plan=f"{sweep} strata")
    return dataclasses.replace(res, plan=f"{pl.describe()} :: {res.plan}")


def _pipeline_design(x: Array, design: "_design.Design", *, metric: str,
                     n_perms: int, key, dist_impl: str, sw_impl: str,
                     materialize: str, row_block, chunk,
                     memory_budget_bytes, matrix_budget_bytes,
                     slab_budget_bytes, dist_tuning, sw_tuning,
                     fused_impl, fused_tuning, backend, mesh, ordination,
                     autotune: bool) -> PermanovaResult:
    """features→per-term p-values for a non-plain design.

    Every materialization bridge keeps its residency contract: dense and
    stream hand the (squared-)distance matrix to engine.run_design;
    the fused bridges contract basis blocks (dense designs) or
    strata-restricted labels against D² row slabs exactly as the label
    sweep does — nothing about the memory-bound dataflow changes, only
    the right-hand-side operand.
    """
    n, d = (int(v) for v in x.shape)
    if design.n != n:
        raise ValueError(f"design is for n={design.n}, features are "
                         f"({n}, {d})")
    if mesh is not None:
        raise ValueError(
            "single-study mesh execution supports plain single-factor "
            "designs only; shard design studies over the 'data' axis via "
            "pipeline_many/permanova_many instead")
    n_total = n_perms + 1
    dense_mode = design.mode == _design.MODE_DENSE
    k = design.k_cols if dense_mode else None
    n_groups_plan = (design.n_groups if design.n_groups is not None
                     else design.rank)

    def _plan():
        return _planner.plan_pipeline(
            n, d, n_total, n_groups_plan, metric=metric, backend=backend,
            dist_impl=dist_impl, materialize=materialize,
            row_block=row_block, matrix_budget_bytes=matrix_budget_bytes,
            slab_budget_bytes=slab_budget_bytes,
            memory_budget_bytes=memory_budget_bytes,
            sw_impl=sw_impl, chunk=chunk, sw_tuning=sw_tuning,
            fused_impl=fused_impl, fused_tuning=fused_tuning,
            design_cols=k)

    pl = _plan()
    if autotune and pl.materialize in ("dense", "stream") \
            and dist_impl == "auto":
        dist_impl = _planner.autotune_stage1(x, metric, backend=backend)
        pl = _plan()
    dspec = _registry.get(pl.dist_impl)
    prepare, rows_fn, dense_fn = dspec.bound(
        **{**pl.dist_tuning, **(dist_tuning or {})})

    ordn = None
    xprep = None
    if pl.materialize == "dense":
        with _obs.span(f"stage1.{metric}",
                       _stage1_attrs(pl, dspec, n, d, "dense")):
            dm = _obs.maybe_block(_jit_dense(dense_fn)(x))
        res = engine.run_design(
            dm, design, n_perms=n_perms, key=key, impl=sw_impl,
            memory_budget_bytes=memory_budget_bytes, chunk=chunk,
            backend=backend, tuning=sw_tuning)
        if ordination is not None:
            with _obs.span("pipeline.pcoa"):
                ordn = _ordination.pcoa_eigh(dm * dm, ordination)
    elif pl.materialize == "stream":
        with _obs.span(f"stage1.{metric}",
                       _stage1_attrs(pl, dspec, n, d, "stream")):
            mat2, gower = _streaming.build_mat2_streaming(
                prepare(x), rows_fn, block=pl.row_block)
            mat2_dev = jnp.asarray(mat2)
        del mat2
        res = engine.run_design(
            mat2_dev, design, n_perms=n_perms, key=key, impl=sw_impl,
            memory_budget_bytes=memory_budget_bytes, chunk=chunk,
            backend=backend, tuning=sw_tuning, squared=True,
            s_t=gower.s_t)
        if ordination is not None:
            ordn = _ordination.pcoa_subspace(mat2_dev, ordination,
                                             stats=gower)
    elif pl.materialize == "fused":
        xprep = prepare(x)
        if dense_mode:
            with _obs.span("bridge.fused",
                           _fused_attrs(pl, n, d, n_groups_plan, n_total)):
                s_cols, _, stats = _streaming.fused_sw_design(
                    xprep, rows_fn, design, key, n_total,
                    row_block=pl.row_block, chunk=pl.sw.chunk)
            res = engine.design_result(
                jnp.asarray(s_cols, jnp.float32), design, n_objects=n,
                n_perms=n_perms, method="pipeline-design[fused]",
                plan=(f"rows={stats.row_block}x{stats.n_row_blocks} "
                      f"chunks={stats.n_chunks} cols={k}"))
        else:
            inv_gs = permutations.inv_group_sizes(design.grouping,
                                                  design.n_groups)
            with _obs.span("bridge.fused",
                           _fused_attrs(pl, n, d, n_groups_plan, n_total)):
                s_w, s_t, stats = _streaming.fused_sw(
                    xprep, rows_fn, design.grouping, inv_gs, key, n_total,
                    row_block=pl.row_block, chunk=pl.sw.chunk,
                    strata=design.strata)
            res = engine.api.label_design_result(
                jnp.asarray(s_w, jnp.float32), jnp.float32(s_t), design,
                n_objects=n, n_perms=n_perms,
                method="pipeline[fused+strata]",
                plan=(f"rows={stats.row_block}x{stats.n_row_blocks} "
                      f"chunks={stats.n_chunks} strata"))
    elif pl.materialize == "fused-kernel":
        fspec = _registry.get_fused(pl.fused_impl)
        xprep = prepare(x)
        if dense_mode:
            with _obs.span("bridge.fused-kernel",
                           _fused_attrs(pl, n, d, n_groups_plan, n_total,
                                        fspec=fspec)):
                s_cols, _, stats = _streaming.fused_kernel_sw_design(
                    xprep, rows_fn, design, key, n_total, impl=fspec.kind,
                    kernel_metric=fspec.kernel_metric,
                    row_block=pl.row_block, chunk=pl.sw.chunk,
                    tuning=pl.fused_tuning)
            res = engine.design_result(
                jnp.asarray(s_cols, jnp.float32), design, n_objects=n,
                n_perms=n_perms,
                method=f"pipeline-design[fused-kernel:{stats.impl}]",
                plan=(f"{stats.impl} rows={stats.row_block} "
                      f"chunks={stats.n_chunks} cols={k}"))
        else:
            inv_gs = permutations.inv_group_sizes(design.grouping,
                                                  design.n_groups)
            with _obs.span("bridge.fused-kernel",
                           _fused_attrs(pl, n, d, n_groups_plan, n_total,
                                        fspec=fspec)):
                s_w, s_t, stats = _streaming.fused_kernel_sw(
                    xprep, rows_fn, design.grouping, inv_gs, key, n_total,
                    impl=fspec.kind, kernel_metric=fspec.kernel_metric,
                    row_block=pl.row_block, chunk=pl.sw.chunk,
                    tuning=pl.fused_tuning, strata=design.strata)
            res = engine.api.label_design_result(
                jnp.asarray(s_w, jnp.float32), jnp.float32(s_t), design,
                n_objects=n, n_perms=n_perms,
                method=f"pipeline[fused-kernel:{stats.impl}+strata]",
                plan=(f"{stats.impl} rows={stats.row_block} "
                      f"chunks={stats.n_chunks} strata"))
    else:  # pragma: no cover - planner validates
        raise ValueError(pl.materialize)

    if ordination is not None and ordn is None:
        with _obs.span("pipeline.pcoa"):
            ordn = _ordination.pcoa_features(xprep, rows_fn, ordination,
                                             row_block=pl.row_block)
    return dataclasses.replace(
        res,
        plan=f"{pl.describe_stage1()} | {pl.reason} :: {res.plan} "
             f"({design.describe()})",
        ordination=ordn)


# ---------------------------------------------------------------------------
# Batched multi-study pipeline (serving scenario).
# ---------------------------------------------------------------------------

def pipeline_many(xs: Array, groupings: Array, *, n_groups: int,
                  metric: str = "braycurtis", n_perms: int = 999,
                  key: Optional[jax.Array] = None,
                  dist_impl: str = "auto", sw_impl: str = "auto",
                  materialize: str = "auto",
                  row_block: Optional[int] = None,
                  chunk: Optional[int] = None,
                  memory_budget_bytes: Optional[float] = None,
                  matrix_budget_bytes: Optional[float] = None,
                  backend: Optional[str] = None,
                  mesh=None,
                  covariates=None, strata=None, weights=None,
                  ordination: Optional[int] = None
                  ) -> engine.PermanovaManyResult:
    """Stacked studies features→p-values through ONE joint plan.

    xs:         (S, n, d) abundance tables.
    groupings:  (S, n) int labels in [0, n_groups) (shared design width,
                like engine.permanova_many).
    materialize: 'auto' | 'dense' | 'fused-kernel'. The dense path builds
                the (S, n, n) stack study-by-study (lax.map bounds peak
                distance transients to one study's) and runs the engine's
                vmapped program; the fused-kernel path vmaps the single-
                pass sweep — nothing (n, n)-shaped ever exists, per-study
                peak residency (row_block, n). 'auto' picks fused-kernel
                exactly when the stack would blow the matrix budget.
    mesh:       optional Mesh with a 'data' axis — shards the STUDY axis
                over 'data' (fused-kernel only). Permutation draws fold
                the key by GLOBAL study index before sharding, so every
                study's null is independent and sharded == single-host ==
                S separate pipeline() calls, regardless of which shard
                runs it.
    ordination: optional k — per-study top-k PCoA axes into
                `result.ordination` (engine.PermanovaManyResult is the
                shared multi-study contract: F, p, R^2, coordinates +
                explained variance). The dense path eigendecomposes from
                the distance stack; the fused-kernel path re-streams
                squared-distance slabs from the features per study, so
                nothing (n, n)-shaped is added to its footprint.

    covariates / strata / weights: stacked per-study design columns —
    (S, n, c) / (S, n) arrays (see engine.permanova_many). Any of them
    routes the batch through the dense-design program: the dense bridge
    builds the distance stack and delegates, the fused-kernel bridge
    vmaps the per-column basis contraction over the study axis (still
    nothing (n, n)-shaped), shardable over 'data'.

    Study s draws its null from fold_in(key, s) — identical to S
    independent pipeline() calls — on EVERY path; a single fold must never
    be reused across the batch axis.
    """
    if key is None:
        key = jax.random.key(0)
    xs = jnp.asarray(xs)
    if xs.ndim != 3:
        raise ValueError(f"stacked features must be (S, n, d); "
                         f"got shape {xs.shape}")
    groupings = jnp.asarray(groupings, dtype=jnp.int32)
    s_count, n, d = xs.shape
    n_total = n_perms + 1
    stack_bytes = 4 * s_count * n * n
    budget = (_planner.DEFAULT_MATRIX_BUDGET_BYTES
              if matrix_budget_bytes is None else matrix_budget_bytes)

    if mesh is not None and materialize not in ("auto", "fused-kernel"):
        raise ValueError("mesh execution of pipeline_many is fused-kernel "
                         "only; use materialize='auto'/'fused-kernel'")
    if materialize == "auto":
        materialize = ("fused-kernel"
                       if mesh is not None or stack_bytes > budget
                       else "dense")
    if materialize not in ("dense", "fused-kernel"):
        raise ValueError(
            f"pipeline_many supports materialize='dense'/'fused-kernel' "
            f"(got {materialize!r}); stream/fused are single-study bridges")

    designed = (covariates is not None or strata is not None
                or weights is not None)
    if designed and materialize == "dense":
        pl = _planner.plan_pipeline(
            n, d, n_total, n_groups, metric=metric, backend=backend,
            dist_impl=dist_impl, row_block=row_block, materialize="dense",
            matrix_budget_bytes=matrix_budget_bytes,
            memory_budget_bytes=memory_budget_bytes, chunk=chunk)
        dspec = _registry.get(pl.dist_impl)
        _, _, dense_fn = dspec.bound(**pl.dist_tuning)
        dms = jax.lax.map(dense_fn, xs)
        res = engine.permanova_many(
            dms, groupings, n_groups=n_groups, n_perms=n_perms, key=key,
            chunk=chunk, memory_budget_bytes=memory_budget_bytes,
            backend=backend, mesh=mesh, covariates=covariates,
            strata=strata, weights=weights, ordination=ordination)
        res.plan = f"{pl.dist_impl} -> dense(batched lax.map) -> {res.plan}"
        return res
    if designed:
        return _pipeline_many_fused_design(
            xs, groupings, covariates=covariates, strata=strata,
            weights=weights, n_groups=n_groups, metric=metric,
            n_perms=n_perms, key=key, row_block=row_block, chunk=chunk,
            memory_budget_bytes=memory_budget_bytes, backend=backend,
            mesh=mesh, ordination=ordination)

    if materialize == "fused-kernel":
        return _pipeline_many_fused(
            xs, groupings, n_groups=n_groups, metric=metric,
            n_perms=n_perms, key=key, row_block=row_block, chunk=chunk,
            memory_budget_bytes=memory_budget_bytes, backend=backend,
            mesh=mesh, ordination=ordination)

    pl = _planner.plan_pipeline(
        n, d, n_total, n_groups, metric=metric, backend=backend,
        dist_impl=dist_impl, row_block=row_block, materialize="dense",
        matrix_budget_bytes=matrix_budget_bytes,
        memory_budget_bytes=memory_budget_bytes,
        sw_impl=sw_impl, chunk=chunk)
    if stack_bytes > budget:
        warnings.warn(
            f"pipeline_many materializes the full (S, n, n) stack "
            f"({stack_bytes/2**20:.0f}MiB), exceeding the matrix budget "
            f"({budget/2**20:.0f}MiB); use materialize='fused-kernel' "
            "(never builds the stack) or split the studies", stacklevel=2)
    dspec = _registry.get(pl.dist_impl)
    _, _, dense_fn = dspec.bound(**pl.dist_tuning)

    dms = jax.lax.map(dense_fn, xs)        # one study's transients at a time
    res = engine.permanova_many(
        dms, groupings, n_groups=n_groups, n_perms=n_perms, key=key,
        impl=sw_impl, chunk=chunk,
        memory_budget_bytes=memory_budget_bytes, backend=backend,
        ordination=ordination)
    res.plan = (f"{pl.dist_impl} -> dense(batched lax.map) -> "
                f"{res.plan}")
    return res


@functools.lru_cache(maxsize=64)
def _fused_many_program(metric: str, block: int, ch: int, n_chunks: int,
                        n: int, pad: int, n_groups: int):
    """The jitted vmapped fused sweep, cached per static config — serving
    callers must not pay a fresh trace/compile of the scan-of-scans per
    request (mirrors engine.api._many_program)."""
    from repro.core import distance as _dist
    mdef = _dist.ROW_METRICS[metric]

    def one(xp_pad, xp, grouping, igs, study_key):
        return _streaming._sweep_rows_perms(
            xp_pad, xp, grouping, igs, study_key, jnp.int32(0),
            jnp.int32(0), rows_fn=mdef.rows, block=block, chunk=ch,
            n_chunks=n_chunks, n=n, n_rows_pad=n + pad, n_groups=n_groups)

    return jax.jit(jax.vmap(one))


def _pipeline_many_fused(xs: Array, groupings: Array, *, n_groups: int,
                         metric: str, n_perms: int, key: jax.Array,
                         row_block: Optional[int], chunk: Optional[int],
                         memory_budget_bytes: Optional[float],
                         backend: Optional[str],
                         mesh,
                         ordination: Optional[int] = None
                         ) -> engine.PermanovaManyResult:
    """Batched single-pass sweep: vmap of the fused-kernel dataflow over
    the study axis, optionally sharded over the mesh's 'data' axis.

    Per-study keys are folded by GLOBAL study index BEFORE any sharding —
    the stacked studies each draw an independent null exactly as S
    separate pipeline() calls would (a single fold reused across the
    batch axis would correlate every study's permutations).
    """
    from repro.core import distance as _dist
    s_count, n, d = (int(v) for v in xs.shape)
    n_total = n_perms + 1

    # joint plan for ONE study; the vmap holds every study's chunk state
    # live at once, so the label budget splits S ways (engine convention)
    total_budget = (engine.planner.DEFAULT_STREAM_BUDGET_BYTES
                    if memory_budget_bytes is None else memory_budget_bytes)
    # the batched sweep always executes the XLA form (vmapped scan-of-
    # scans) — pin the plan to it so the recorded impl matches execution
    pl = _planner.plan_pipeline(
        n, d, n_total, n_groups, metric=metric, backend=backend,
        materialize="fused-kernel", fused_impl="xla", row_block=row_block,
        memory_budget_bytes=total_budget / s_count, chunk=chunk)
    mdef = _dist.ROW_METRICS[metric]
    xs_prep = mdef.prepare(xs)             # every prepare is last-axis-local
    block = int(min(pl.row_block, n))
    ch = int(max(1, min(pl.sw.chunk, n_total)))
    n_chunks = -(-n_total // ch)
    pad = (-n) % block
    xs_pad = jnp.pad(xs_prep, ((0, 0), (0, pad), (0, 0)))
    inv_gs = jax.vmap(
        lambda g: permutations.inv_group_sizes(g, n_groups))(groupings)
    run = _fused_many_program(metric, block, ch, n_chunks, n, pad,
                              n_groups)

    study_idx = jnp.arange(s_count)
    args = (xs_pad, xs_prep, groupings, inv_gs)
    where = "vmap"
    # study counts that do not divide 'data' wrap-pad and slice, the same
    # contract as engine.permanova_many (shared helper)
    data_ways, s_pad, wrap_idx = engine.api.study_axis_padding(mesh,
                                                              s_count)
    if wrap_idx is not None:
        args = tuple(jnp.take(a, wrap_idx, axis=0) for a in args)
        study_idx = wrap_idx
    # GLOBAL study index -> per-study key, folded before any sharding;
    # a padded slot replays its source study's key, so the pad is inert
    study_keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(study_idx)
    args = args + (study_keys,)
    if data_ways > 1:
        args = engine.api.put_study_sharded(mesh, args)
        where = (f"vmap@data[{data_ways}]"
                 + (f"+pad{s_pad}" if s_pad else ""))
    with _obs.span("bridge.fused-kernel",
                   _fused_attrs(pl, n, d, n_groups, n_total,
                                fspec=_registry.get_fused(pl.fused_impl),
                                studies=s_count)):
        s_w_all, rs = _obs.maybe_block(run(*args))  # (S', n_chunks*ch)
    _obs.metrics.inc("engine.studies", s_count)
    s_w_all = s_w_all[:s_count, :n_total]
    s_t = jnp.sum(rs[:s_count, :n], axis=1) / 2.0 / n
    f_perms = jax.vmap(f_from_sw, in_axes=(0, 0, None, None))(
        s_w_all, s_t.astype(jnp.float32), n, n_groups)
    p_vals = jax.vmap(p_value_from_null)(f_perms)

    ord_res = None
    if ordination is not None:
        # per-study streamed PCoA (unsharded, deterministic — identical
        # embeddings whether or not the sweep above ran on a mesh);
        # lax.map bounds transients to ONE study's subspace iterate, and
        # the Gower marginals reuse the sweep's row sums (`rs`) instead
        # of paying another full distance rebuild per study
        from repro.pipeline import ordination as _ord

        def one_pcoa(xp_rs):
            xp, rs_s = xp_rs
            stats = _streaming.GowerStats(row_sums=rs_s,
                                          total=jnp.sum(rs_s), n=n)
            r = _ord.pcoa_features(xp, mdef.rows, int(ordination),
                                   row_block=block, stats=stats)
            return r.coords, r.eigvals, r.explained

        coords, eigvals, explained = jax.lax.map(
            one_pcoa, (xs_prep, rs[:s_count, :n]))
        ord_res = _ord.PCoAResult(coords=coords, eigvals=eigvals,
                                  explained=explained,
                                  method="subspace-stream")

    return engine.PermanovaManyResult(
        f_stat=f_perms[:, 0], p_value=p_vals, s_t=s_t.astype(jnp.float32),
        s_w=s_w_all[:, 0], f_perms=f_perms, n_objects=n, n_groups=n_groups,
        n_perms=n_perms, ordination=ord_res,
        plan=(f"{pl.fused_impl}({where}) rows={block} "
              f"chunk={ch} studies={s_count} chunks={n_chunks} | "
              f"{pl.reason}"))


@functools.lru_cache(maxsize=64)
def _fused_many_program_design(metric: str, block: int, ch: int,
                               n_chunks: int, n: int, pad: int, k: int):
    """The jitted vmapped fused DESIGN sweep, cached per static config
    (mirrors _fused_many_program): per study, the chunk scan draws
    strata-restricted index permutations, gathers basis rows, and runs
    the per-column contraction against D² row slabs built in-scan."""
    from repro.core import distance as _dist
    mdef = _dist.ROW_METRICS[metric]

    def one(xp_pad, xp, basis, strata, study_key):
        return _streaming._sweep_rows_perms_design(
            xp_pad, xp, basis, strata, study_key, jnp.int32(0),
            jnp.int32(0), rows_fn=mdef.rows, block=block, chunk=ch,
            n_chunks=n_chunks, n=n, n_rows_pad=n + pad, k_cols=k)

    return jax.jit(jax.vmap(one))


def _pipeline_many_fused_design(xs: Array, groupings: Array, *,
                                covariates, strata, weights,
                                n_groups: int, metric: str, n_perms: int,
                                key: jax.Array, row_block, chunk,
                                memory_budget_bytes, backend, mesh,
                                ordination) -> engine.PermanovaManyResult:
    """Batched single-pass DESIGN sweep: vmap of the fused dense-basis
    dataflow over the study axis, optionally sharded over 'data'.

    Per-study keys fold by GLOBAL study index before any sharding, so
    sharded == single-host == S separate pipeline() calls bit-identically
    (including strata-restricted draws)."""
    from repro.core import distance as _dist
    s_count, n, d = (int(v) for v in xs.shape)
    n_total = n_perms + 1

    designs = engine.api._build_study_designs(
        groupings, covariates, strata, weights, n_groups=n_groups, n=n,
        s_count=s_count)
    d0 = designs[0]
    k = d0.k_cols
    basis_stack = jnp.stack([dd.basis for dd in designs])
    strata_stack = jnp.stack([
        dd.strata if dd.strata is not None else jnp.zeros((n,), jnp.int32)
        for dd in designs])

    total_budget = (engine.planner.DEFAULT_STREAM_BUDGET_BYTES
                    if memory_budget_bytes is None else memory_budget_bytes)
    pl = _planner.plan_pipeline(
        n, d, n_total, n_groups, metric=metric, backend=backend,
        materialize="fused-kernel", fused_impl="xla", row_block=row_block,
        memory_budget_bytes=total_budget / s_count, chunk=chunk,
        design_cols=k)
    mdef = _dist.ROW_METRICS[metric]
    xs_prep = mdef.prepare(xs)
    block = int(min(pl.row_block, n))
    ch = int(max(1, min(pl.sw.chunk, n_total)))
    n_chunks = -(-n_total // ch)
    pad = (-n) % block
    xs_pad = jnp.pad(xs_prep, ((0, 0), (0, pad), (0, 0)))
    run = _fused_many_program_design(metric, block, ch, n_chunks, n, pad,
                                     k)

    study_idx = jnp.arange(s_count)
    args = (xs_pad, xs_prep, basis_stack, strata_stack)
    where = "vmap"
    data_ways, s_pad, wrap_idx = engine.api.study_axis_padding(mesh,
                                                              s_count)
    if wrap_idx is not None:
        args = tuple(jnp.take(a, wrap_idx, axis=0) for a in args)
        study_idx = wrap_idx
    study_keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(study_idx)
    args = args + (study_keys,)
    if data_ways > 1:
        args = engine.api.put_study_sharded(mesh, args)
        where = (f"vmap@data[{data_ways}]"
                 + (f"+pad{s_pad}" if s_pad else ""))
    with _obs.span("bridge.fused-kernel",
                   _fused_attrs(pl, n, d, n_groups, n_total,
                                fspec=_registry.get_fused(pl.fused_impl),
                                studies=s_count)):
        s_cols_all, rs = _obs.maybe_block(run(*args))  # (S', nc*ch, K)
    _obs.metrics.inc("engine.studies", s_count)
    s_cols = s_cols_all[:s_count, :n_total]

    ord_res = None
    if ordination is not None:
        from repro.pipeline import ordination as _ord

        def one_pcoa(xp_rs):
            xp, rs_s = xp_rs
            stats = _streaming.GowerStats(row_sums=rs_s,
                                          total=jnp.sum(rs_s), n=n)
            r = _ord.pcoa_features(xp, mdef.rows, int(ordination),
                                   row_block=block, stats=stats)
            return r.coords, r.eigvals, r.explained

        coords, eigvals, explained = jax.lax.map(
            one_pcoa, (xs_prep, rs[:s_count, :n]))
        ord_res = _ord.PCoAResult(coords=coords, eigvals=eigvals,
                                  explained=explained,
                                  method="subspace-stream")

    dof_resid = jnp.full((s_count,), n - d0.rank, jnp.float32)
    return engine.api.design_many_result(
        s_cols, d0, dof_resid=dof_resid, n_objects=n, n_groups=n_groups,
        n_perms=n_perms, ordination=ord_res,
        plan=(f"{pl.fused_impl}({where}) rows={block} chunk={ch} "
              f"studies={s_count} cols={k} chunks={n_chunks} | "
              f"{pl.reason} ({d0.describe()})"))
