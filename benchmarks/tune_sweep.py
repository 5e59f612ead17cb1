"""Real autotune sweep for the fused-kernel sweep's joint tuning space.

  PYTHONPATH=src python -m benchmarks.tune_sweep             # full sweep
  PYTHONPATH=src python -m benchmarks.tune_sweep --smoke     # CI config

`autotune_fused` times each fused IMPL at its registry-default tiles; this
harness searches the actual knob space per (backend, metric, impl):
tile_r x tile_c x feat_block x perm_block crossed with the feature-slab
precision (f32 / bf16 / fp8 / packed-bit jaccard). The winning tuning per
(impl, precision) is persisted into the SAME per-host autotune cache the
planners read (engine.planner.record_entry, key
'fusedk|<backend>|<metric>|<impl>[|<precision>]'), so a subsequent
plan_pipeline() with those precision knobs picks the measured tiles up as
its defaults — the sweep then REPLANS and verifies that round trip,
exiting nonzero if any recorded winner fails to feed the planner.

--smoke shrinks the problem and the grid to a seconds-scale CI step and
points the cache at a temp file unless --cache is given, asserting the
same round-trip contract on every entry it wrote.
"""

from __future__ import annotations

import argparse
import itertools
import os
import sys
import tempfile
import time


def _tile_grid(smoke: bool):
    """(tile_r, tile_c, feat_block, perm_block) candidates."""
    if smoke:
        return [(16, 16, 8, 4), (32, 32, 8, 4)]
    # full sweep: tiles Mosaic accepts (lane edges of 128 multiples,
    # perm blocks of 8 multiples)
    return [(tr, tc, fb, pb)
            for tr, tc in ((128, 128), (256, 256), (128, 256))
            for fb in (128, 256)
            for pb in (8, 16)]


def _precisions(metric: str, kernel_metric: str, smoke: bool):
    tags = ["f32", "fp8"] if smoke else ["f32", "bf16", "fp8"]
    if kernel_metric == "jaccard":
        tags.append("packed")
    return tags


def sweep(metric: str, backend: str, *, n: int, d: int, g: int,
          sample_perms: int, smoke: bool, emit=print):
    """Sweep one (backend, metric); returns the recorded cache keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import distance as _dist
    from repro.core import permutations as _perms
    from repro.engine import planner as _eplanner
    from repro.pipeline import planner as _pplanner
    from repro.pipeline import registry as _dreg
    from repro.pipeline import streaming as _streaming

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.gamma(1.0, 1.0, size=(n, d)).astype(np.float32))
    grouping = jnp.asarray(
        np.concatenate([np.arange(g), rng.integers(0, g, n - g)]),
        jnp.int32)
    inv_gs = _perms.inv_group_sizes(grouping, g)
    mdef = _dist.ROW_METRICS[metric]
    xprep = mdef.prepare(x)
    key = jax.random.key(0)
    row_block = min(256, n)

    recorded = []
    for name in _dreg.fused_names(metric=metric):
        spec = _dreg.get_fused(name)
        if backend not in spec.backends and \
                not (smoke and spec.kind == "pallas"):
            # smoke keeps the megakernel in (interpreted on the CPU) so CI
            # exercises the tile grid + precision kernel bodies end to end
            continue
        # xla has no tile knobs: one config per precision
        tiles = (_tile_grid(smoke) if spec.kind == "pallas"
                 else [None])
        for tag in _precisions(metric, spec.kernel_metric, smoke):
            best_t, best_tuning = float("inf"), None
            for tile in tiles:
                tuning = dict(spec.tuning)
                tuning.update(_dreg.precision_tuning(tag))
                tuning = {k: v for k, v in tuning.items()
                          if k in spec.tuning}
                if tile is not None:
                    tuning.update(zip(("tile_r", "tile_c", "feat_block",
                                       "perm_block"), tile))

                def run(_tuning=tuning):
                    return _streaming.fused_kernel_sw(
                        xprep, mdef.rows, grouping, inv_gs, key,
                        sample_perms, impl=spec.kind,
                        kernel_metric=spec.kernel_metric,
                        row_block=row_block, chunk=sample_perms,
                        tuning=_tuning)

                try:
                    run()                      # compile + warm
                    t0 = time.perf_counter()
                    run()
                    t = time.perf_counter() - t0
                except Exception as exc:  # noqa: BLE001 — counted, logged
                    _eplanner.record_candidate_failure(
                        "tune_sweep", f"{name}[{tag}]", exc)
                    emit(f"# skip {name}[{tag}] tile={tile}: {exc}")
                    continue
                emit(f"tune/{backend}/{name}/{tag}/"
                     f"{'x'.join(map(str, tile)) if tile else 'default'},"
                     f"{t*1e6:.1f}")
                if t < best_t:
                    best_t, best_tuning = t, tuning
            if best_tuning is None:
                continue
            ckey = _pplanner._fused_key(backend, metric, name, best_tuning)
            _eplanner.record_entry(ckey, {
                "impl": name, "us": round(best_t * 1e6, 1), "n": n, "d": d,
                "bucket": _eplanner._bucket(n), "tuning": best_tuning})
            recorded.append((ckey, name, best_tuning))
            emit(f"tune/winner {ckey} -> "
                 f"{sorted(best_tuning.items())} ({best_t*1e6:.0f}us)")
    return recorded


def verify_roundtrip(recorded, metric: str, backend: str, *, n: int,
                     d: int, g: int, sample_perms: int, emit=print) -> int:
    """Replan with each recorded entry's precision knobs and check the
    persisted winner's tiles came back as the plan's defaults."""
    from repro.engine import planner as _eplanner
    from repro.pipeline import planner as _pplanner

    _eplanner.load_autotune_cache(reload=True)   # from disk, like a fresh
    failures = 0                                 # process would
    for ckey, name, tuning in recorded:
        entry = _eplanner.measured_entry(ckey)
        if not entry or entry.get("schema") != _eplanner.CACHE_SCHEMA \
                or entry.get("tuning") != tuning:
            emit(f"# FAIL {ckey}: entry did not round-trip the cache "
                 f"(got {entry})")
            failures += 1
            continue
        prec = {k: v for k, v in tuning.items()
                if k.startswith("feat_") and k != "feat_block"}
        pl = _pplanner.plan_pipeline(
            n, d, sample_perms, g, metric=metric, backend=backend,
            materialize="fused-kernel", fused_impl=name, fused_tuning=prec)
        if pl.fused_tuning != tuning:
            emit(f"# FAIL {ckey}: planner defaults {pl.fused_tuning} != "
                 f"recorded winner {tuning}")
            failures += 1
        else:
            emit(f"tune/verified {ckey} feeds planner defaults")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--metric", default=None,
                    help="comma-separated metrics (default: all fused)")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--d", type=int, default=None)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--perms", type=int, default=None,
                    help="permutation sample per timing")
    ap.add_argument("--cache", default=None,
                    help="autotune cache file (default: the per-host "
                         "cache; --smoke defaults to a temp file)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI config: tiny problem, 2-point "
                         "tile grid, temp cache unless --cache")
    args = ap.parse_args()

    if args.cache or args.smoke:
        cache = args.cache or os.path.join(
            tempfile.mkdtemp(prefix="repro-tune-"), "autotune.json")
        os.environ["REPRO_AUTOTUNE_CACHE"] = cache
        print(f"# cache: {cache}")

    # env must be set before the planner first loads the cache
    from repro.engine import planner as _eplanner
    from repro.pipeline import registry as _dreg
    _eplanner.load_autotune_cache(reload=True)

    backend = args.backend or _eplanner.default_backend()
    n = args.n or (64 if args.smoke else 1024)
    d = args.d or (32 if args.smoke else 256)
    perms = args.perms or (4 if args.smoke else 16)
    metrics = (args.metric.split(",") if args.metric
               else sorted({_dreg.get_fused(f).metric
                            for f in _dreg.fused_names()}))

    failures = 0
    for metric in metrics:
        recorded = sweep(metric, backend, n=n, d=d, g=args.groups,
                         sample_perms=perms, smoke=args.smoke)
        if not recorded:
            print(f"# FAIL {metric}: sweep recorded no cache entries")
            failures += 1
            continue
        failures += verify_roundtrip(recorded, metric, backend, n=n, d=d,
                                     g=args.groups, sample_perms=perms)
    print(f"# tune_sweep: {'FAILED' if failures else 'ok'} "
          f"({failures} failures)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
