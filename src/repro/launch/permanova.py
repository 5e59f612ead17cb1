"""PERMANOVA launcher — the paper's workload as a CLI, routed through the
hardware-aware execution engine.

  PYTHONPATH=src python -m repro.launch.permanova \
      --samples 512 --features 128 --groups 8 --perms 999 \
      --impl auto --metric braycurtis

  # 100k permutations in fixed-memory chunks (no (n_perms, n) label tensor):
  PYTHONPATH=src python -m repro.launch.permanova \
      --samples 512 --perms 100000 --impl auto --budget-mb 64

  # full pipeline under one joint plan (distance stage + s_W planned
  # together; --materialize fused never holds the (n, n) matrix):
  PYTHONPATH=src python -m repro.launch.permanova \
      --samples 2048 --from-features --materialize auto

  # single-pass megakernel sweep (distance tiles contracted in-kernel),
  # row slabs sharded 2-way over the 'model' mesh axis:
  PYTHONPATH=src python -m repro.launch.permanova \
      --samples 4096 --materialize fused-kernel --shard-rows 2

Scales from laptop smoke runs to the paper's EMP shape
(--samples 25145 --perms 3999) on a real mesh.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, engine, obs, pipeline
from repro.core.distance import distance_matrix, validate_distance_matrix
from repro.data.microbiome import synthetic_study

IMPL_CHOICES = ["auto", "brute", "tiled", "matmul",
                "pallas_brute", "pallas_permblock", "pallas_matmul"]


def _emit_obs(args):
    """Export the trace and/or print the telemetry report, if requested."""
    if args.trace:
        obs.trace.export(args.trace)
        print(f"[permanova] trace written to {args.trace} "
              f"({len(obs.events())} events)")
    if args.metrics:
        obs.report()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=512)
    ap.add_argument("--features", type=int, default=128)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--perms", type=int, default=999)
    ap.add_argument("--effect", type=float, default=1.0)
    ap.add_argument("--metric", default="braycurtis")
    ap.add_argument("--impl", default="auto", choices=IMPL_CHOICES,
                    help="'auto' = hardware-aware planner (CPU-tiled vs "
                         "GPU-brute per the paper); or pin a registry impl")
    ap.add_argument("--autotune", action="store_true",
                    help="empirically measure candidates on the real "
                         "operands instead of trusting the heuristics")
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="label-tensor memory budget; sweeps beyond it "
                         "stream in fixed-size chunks")
    ap.add_argument("--chunk", type=int, default=None,
                    help="pin the streaming chunk (perms per dispatch)")
    ap.add_argument("--from-features", action="store_true",
                    help="route through the pipeline subsystem: distance "
                         "construction + s_W planned JOINTLY (stage-1 impl, "
                         "materialization, chunking in one plan)")
    ap.add_argument("--materialize", default="auto",
                    choices=["auto", "dense", "stream", "fused",
                             "fused-kernel"],
                    help="pipeline bridge: materialize D, stream D^2 row "
                         "blocks into one buffer, fuse blocks straight "
                         "into the permutation sweep, or run the single-"
                         "pass fused-kernel (distance tiles contracted "
                         "in-kernel; D^2 never resident) — implies "
                         "--from-features")
    ap.add_argument("--fused-impl", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="fused-kernel implementation: the Pallas "
                         "megakernel (TPU; interpret mode elsewhere) or "
                         "the one-jit XLA sweep")
    ap.add_argument("--feat-precision", default="f32",
                    choices=list(pipeline.registry.PRECISIONS),
                    help="feature-slab storage for the fused-kernel "
                         "sweep: f32, bf16, fp8 (e4m3 + per-metric scale, "
                         "f32 accumulation), or packed (jaccard only: "
                         "presence bits in uint32 words, popcount tiles — "
                         "bit-identical F at 32x fewer feature bytes); "
                         "implies --materialize fused-kernel when not f32")
    ap.add_argument("--shard-rows", type=int, default=None, metavar="N",
                    help="run the fused-kernel sweep over an N-way 'model' "
                         "mesh axis (row slabs sharded, partials psum-"
                         "reduced; remaining devices shard permutations); "
                         "implies --materialize fused-kernel")
    ap.add_argument("--dist-impl", default="auto",
                    help="pin the stage-1 distance impl (e.g. "
                         "'braycurtis.blocked', 'euclidean.pallas'); "
                         "'auto' = pipeline planner")
    ap.add_argument("--features-cache", default=None, metavar="DIR",
                    help="run out of core from a disk slab cache at DIR "
                         "(built from the synthetic study on first use): "
                         "the feature table never lives in memory — slabs "
                         "stream through the async prefetcher into the "
                         "fused sweep; implies the pipeline path")
    ap.add_argument("--cache-format", default="dense",
                    choices=["dense", "csr"],
                    help="slab-cache storage when building --features-"
                         "cache: raw f32 rows, or csr presence structure "
                         "(jaccard only — reads nonzeros, not zeros)")
    ap.add_argument("--slab-rows", type=int, default=None, metavar="R",
                    help="slab height when building --features-cache "
                         "(default: planner's plan_slab_rows for the "
                         "device budget)")
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    help="device-memory budget grading the feature "
                         "residency tier (hbm/host/disk) for "
                         "--features-cache runs; small values force the "
                         "out-of-core sweep")
    ap.add_argument("--pcoa", type=int, default=None, metavar="K",
                    help="also compute the top-K PCoA ordination axes "
                         "(coordinates + explained variance) from the "
                         "same pipeline dataflow — the stream/fused "
                         "bridges never materialize the Gower matrix; "
                         "implies the pipeline path")
    ap.add_argument("--covariates", default=None, metavar="NAMES",
                    help="comma-separated covariate names (synthetic "
                         "standard-normal columns, e.g. 'age,depth') — "
                         "runs the partial/covariate PERMANOVA design "
                         "path: sequential adonis2-style terms, the "
                         "grouping factor last (covariate-adjusted); "
                         "prints a per-term F/R²/p table; implies the "
                         "pipeline path")
    ap.add_argument("--strata", default=None, metavar="NAME[:K]",
                    help="restrict permutations within K synthetic "
                         "blocks (default K=4), e.g. 'site' or 'site:6' "
                         "— vegan's strata=; implies the pipeline path")
    ap.add_argument("--weights", action="store_true",
                    help="weighted PERMANOVA: synthetic positive sample "
                         "weights folded into the design projection; "
                         "implies the pipeline path")
    ap.add_argument("--kernel", action="store_true",
                    help="legacy alias: maps brute/matmul to the Pallas "
                         "kernel variant (interpreted on the CPU)")
    ap.add_argument("--distributed", action="store_true",
                    help="row-shard the distance matrix over all local "
                         "devices ('model' axis), built in place")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record trace spans across every execution layer "
                         "and write Chrome/Perfetto trace_event JSON to "
                         "PATH (open in chrome://tracing or ui.perfetto."
                         "dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the telemetry report after the run: "
                         "per-stage predicted-vs-measured bandwidth table "
                         "plus compile/traffic counters")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    if args.trace or args.metrics:
        obs.enable(trace=bool(args.trace) or args.metrics, metrics=True)

    impl = args.impl
    if args.kernel and not impl.startswith("pallas_"):
        # legacy flag: force the Pallas kernel family ('tiled' maps to
        # permblock, the kernel carrying the paper's CPU-tiling insight)
        impl = {"auto": "pallas_matmul", "brute": "pallas_brute",
                "tiled": "pallas_permblock", "matmul": "pallas_matmul"}[impl]

    x, grouping = synthetic_study(args.samples, args.features, args.groups,
                                  effect_size=args.effect, seed=args.seed)
    budget = None if args.budget_mb is None else args.budget_mb * 2**20

    covariates = strata = weights = None
    design_path = (args.covariates is not None or args.strata is not None
                   or args.weights)
    if design_path:
        from repro.data.microbiome import synthetic_design
        cov_names = (tuple(s for s in args.covariates.split(",") if s)
                     if args.covariates else ())
        n_strata = 0
        if args.strata is not None:
            name, _, kk = args.strata.partition(":")
            n_strata = int(kk) if kk else 4
        covariates, strata, weights = synthetic_design(
            args.samples, covariate_names=cov_names, n_strata=n_strata,
            weighted=args.weights, seed=args.seed)

    fused_tuning = None
    if args.feat_precision != "f32":
        # the precision knobs live on the fused-kernel sweep; route there
        if args.materialize not in ("auto", "fused-kernel"):
            ap.error("--feat-precision applies to the fused-kernel sweep; "
                     "drop --materialize or set it to fused-kernel")
        args.materialize = "fused-kernel"
        fused_tuning = pipeline.registry.precision_tuning(
            args.feat_precision)

    features = jnp.asarray(x)
    if args.features_cache is not None:
        import os
        from repro.data import slabcache
        from repro.pipeline import planner as _pplanner
        dev_budget = (None if args.device_budget_mb is None
                      else args.device_budget_mb * 2**20)
        if os.path.exists(os.path.join(args.features_cache,
                                       slabcache.META_NAME)):
            features = slabcache.SlabCache.open(args.features_cache)
        else:
            rows = args.slab_rows or _pplanner.plan_slab_rows(
                args.samples, args.features,
                device_budget_bytes=dev_budget)
            features = slabcache.build_slab_cache(
                args.features_cache, x, slab_rows=rows,
                fmt=args.cache_format)
            print(f"[permanova] built slab cache {args.features_cache}: "
                  f"{features.n_slabs} slabs x {features.slab_rows} rows, "
                  f"{features.disk_bytes/2**20:.1f} MiB on disk "
                  f"({args.cache_format})")

    if args.from_features or args.materialize != "auto" \
            or args.dist_impl != "auto" or args.shard_rows is not None \
            or args.pcoa is not None or design_path \
            or args.features_cache is not None:
        if args.distributed:
            ap.error("--distributed is not supported with the pipeline "
                     "path (--from-features/--materialize/--dist-impl); "
                     "use --shard-rows for the fused-kernel mesh, or "
                     "precompute the matrix and drop --distributed")
        mesh = None
        if args.shard_rows is not None:
            from repro.launch.mesh import make_host_mesh
            if args.materialize not in ("auto", "fused-kernel"):
                ap.error("--shard-rows runs the fused-kernel sweep; drop "
                         "--materialize or set it to fused-kernel")
            mesh = make_host_mesh(model_ways=args.shard_rows)
        t0 = time.time()
        res = pipeline.pipeline(
            features, jnp.asarray(grouping), metric=args.metric,
            n_perms=args.perms, key=jax.random.key(args.seed),
            dist_impl=args.dist_impl, sw_impl=impl,
            materialize=args.materialize, chunk=args.chunk,
            fused_impl=args.fused_impl, fused_tuning=fused_tuning,
            mesh=mesh, ordination=args.pcoa,
            covariates=covariates, strata=strata, weights=weights,
            memory_budget_bytes=budget, autotune=args.autotune,
            device_budget_bytes=(None if args.device_budget_mb is None
                                 else args.device_budget_mb * 2**20))
        jax.block_until_ready(res.f_perms)
        t_pa = time.time() - t0
        print(f"[permanova] n={args.samples} groups={args.groups} "
              f"perms={res.n_perms} metric={args.metric} pipeline")
        print(f"[permanova] plan: {res.plan}")
        print(f"[permanova] features->p-value {t_pa:.2f}s "
              f"({res.n_perms / t_pa:.1f} perms/s)")
        print(f"[permanova] F={float(res.f_stat):.6g} "
              f"p={float(res.p_value):.6g} R2={float(res.r2):.4g}")
        if res.terms is not None:
            print(f"[permanova] {'term':<12} {'df':>3} {'SS':>10} "
                  f"{'F':>9} {'R2':>8} {'p':>8}")
            for t in res.terms:
                print(f"[permanova] {t.name:<12} {t.df:>3} "
                      f"{float(t.ss):>10.4g} {float(t.f_stat):>9.4g} "
                      f"{float(t.r2):>8.4g} {float(t.p_value):>8.4g}")
        if res.ordination is not None:
            o = res.ordination
            expl = ", ".join(f"{float(v):.3f}" for v in o.explained)
            print(f"[permanova] pcoa[{o.method}] k={o.k} "
                  f"explained=[{expl}] coords={tuple(o.coords.shape)}")
        _emit_obs(args)
        return 0

    t0 = time.time()
    if args.distributed:
        # rows of D over every local device: the matrix is built already
        # sharded, so a D larger than one chip never sits on device 0
        from repro.core import distributed
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model_ways=len(jax.devices()))
        dm = distributed.distance_matrix_sharded(mesh, jnp.asarray(x),
                                                 args.metric)
    else:
        dm = distance_matrix(jnp.asarray(x), args.metric)
        checks = validate_distance_matrix(dm)
        assert checks["ok"], checks
    jax.block_until_ready(dm)
    t_dm = time.time() - t0

    t0 = time.time()
    if args.distributed:
        res = distributed.permanova_distributed(
            mesh, dm, jnp.asarray(grouping), n_perms=args.perms, impl=impl,
            key=jax.random.key(args.seed))
    else:
        res = engine.run(dm, jnp.asarray(grouping), n_perms=args.perms,
                         impl=impl, key=jax.random.key(args.seed),
                         memory_budget_bytes=budget, chunk=args.chunk,
                         autotune=args.autotune)
    jax.block_until_ready(res.f_perms)
    t_pa = time.time() - t0

    print(f"[permanova] n={args.samples} groups={args.groups} "
          f"perms={res.n_perms} metric={args.metric} impl={impl}"
          f"{' +distributed' if args.distributed else ''}")
    if res.plan:
        print(f"[permanova] plan: {res.plan}")
    print(f"[permanova] distance-matrix {t_dm:.2f}s  "
          f"permutation-test {t_pa:.2f}s "
          f"({res.n_perms / t_pa:.1f} perms/s)")
    print(f"[permanova] F={float(res.f_stat):.6g} "
          f"p={float(res.p_value):.6g}")
    _emit_obs(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
