#!/usr/bin/env python3
"""On-chip smoke test: PERMANOVA's main paths at the paper's size on a TPU.

  python chip_smoke.py                # one chip: paper matrix cell,
                                      # features -> p-value, serving
  python chip_smoke.py --four-chips   # only the row-sharded matrix path,
                                      # D larger than one chip, 4 chips

Everything runs in this one process, which holds the chip; no child
process touches JAX. Each phase goes through the entry points a user
calls (engine.run, pipeline.pipeline, PermanovaServer,
core.distributed), checks its answers against an independent reference
and raises on any mismatch, printing each error beside its limit. Wall
times printed here are progress notes, not measurements of record.

On a machine where JAX finds no TPU it exits non-zero and runs nothing.
On success the last line of stdout is exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
WORKDIR = os.path.join(ROOT, ".smoke")      # slab cache of the features phase

# The paper's matrix-input cell (Sfiligoi 2025, Fig. 1): EMP UniFrac shape.
PAPER = dict(n=25145, n_perms=3999, n_groups=8, n_features=128)
FEATURES = dict(n=25145, d=1024, n_perms=999,
                metrics=("braycurtis", "jaccard"))
SERVING = dict(sizes=(100, 250, 600, 1200, 2000), n_perms=999)
FOUR_CHIPS = dict(n=65536, n_perms=999, n_groups=8, n_features=64)

# Limits. s_W and s_T are f32 sums of up to n^2 terms, added in blocks;
# a running f32 sum of N blocks drifts by ~eps * sqrt(N) / 6 relative,
# about 1e-6 at these sizes, and 1e-5 leaves ten times that. F is
# compared through the s_W error it carries: dF / (F + (n-a)/(a-1)) =
# ds_W / s_W (F = c (s_T/s_W - 1), c = (n-a)/(a-1)), because s_T - s_W
# cancels at large n and F itself inherits that cancellation.
SW_LIMIT = 1e-5
ST_LIMIT = 1e-5


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(what: str, err: float, limit: float) -> None:
    log(f"  {what}: max error {err:.3e} (limit {limit:.1e})")
    if not np.isfinite(err) or err > limit:
        raise AssertionError(f"{what}: error {err!r} exceeds {limit!r}")


def f_error(f_a, f_b, n: int, a: int) -> np.ndarray:
    """Per-permutation |dF| / (F + c): the relative s_W error F carries."""
    f_a = np.asarray(f_a, np.float64)
    f_b = np.asarray(f_b, np.float64)
    c = (n - a) / (a - 1)
    return np.abs(f_a - f_b) / (np.abs(f_b) + c)


def check_p(what: str, f_a, f_b, n: int, a: int, limit: float) -> None:
    """p-values from the same permutations must agree up to the
    permutations whose F lies within rounding of the observed F."""
    f_a = np.asarray(f_a, np.float64)
    f_b = np.asarray(f_b, np.float64)
    c = (n - a) / (a - 1)
    band = 2.0 * limit * (np.abs(f_b[1:]) + c)
    ambiguous = int(np.sum(np.abs(f_b[1:] - f_b[0]) <= band))
    ge_a = int(np.sum(f_a[1:] >= f_a[0]))
    ge_b = int(np.sum(f_b[1:] >= f_b[0]))
    m = f_a.shape[0]
    log(f"  {what}: p {(ge_a + 1) / m:.6g} vs {(ge_b + 1) / m:.6g} "
        f"({abs(ge_a - ge_b)} perms apart; {ambiguous} within rounding "
        "of the observed F)")
    if abs(ge_a - ge_b) > ambiguous:
        raise AssertionError(f"{what}: p-values differ beyond rounding")


def host_reference(dm, labels, n_groups: int, n: int, rows: int = 2048):
    """fp64 NumPy s_W (for each row of `labels`) and s_T over the device's
    own D, streamed to the host in row blocks of each shard. The one-hot
    form, vectorised: s_W[p] = 1/2 sum_g w_g sum_ij D2_ij e_pg(i) e_pg(j)
    (zero diagonal), w_g = 1 / n_g."""
    labels = np.asarray(labels)
    p = labels.shape[0]
    counts = np.bincount(labels[0], minlength=n_groups).astype(np.float64)
    w = 1.0 / counts
    onehot = np.zeros((n, p * n_groups), np.float64)
    for q in range(p):
        onehot[np.arange(n), q * n_groups + labels[q]] = 1.0
    tot = np.zeros(p * n_groups, np.float64)
    s_t = 0.0
    seen = set()
    for shard in dm.addressable_shards:
        lo0 = shard.index[0].start or 0
        if lo0 in seen:            # replicas along the other mesh axes
            continue
        seen.add(lo0)
        data = shard.data
        for lo in range(0, data.shape[0], rows):
            g_lo = lo0 + lo
            if g_lo >= n:
                break
            hi = min(lo + rows, data.shape[0], n - lo0)
            d2 = np.asarray(data[lo:hi], np.float64) ** 2
            s_t += d2.sum()
            tot += np.einsum("ik,ik->k", d2 @ onehot,
                             onehot[g_lo:g_lo + (hi - lo)])
    sw = 0.5 * (tot.reshape(p, n_groups) * w).sum(axis=1)
    return sw, s_t / 2.0 / n


def sw_from_f(f, s_t: float, n: int, a: int) -> np.ndarray:
    """Invert F = c (s_T/s_W - 1) in f64 (well conditioned)."""
    return s_t / (1.0 + np.asarray(f, np.float64) / ((n - a) / (a - 1)))


def peak_gb() -> float:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def compiles() -> int:
    from repro.obs import metrics
    return int(metrics.value("jax.backend_compiles"))


# ---------------------------------------------------------------------------
# Phases. Sizes are arguments, so the tests can run them tiny on the CPU.
# ---------------------------------------------------------------------------

def phase_paper_cell(*, n, n_perms, n_groups, n_features, seed=0,
                     impl="auto", expect_impl="pallas_matmul",
                     ref_perms=4, autotune=True):
    """Matrix input at the paper's shape through engine.run; checked
    against an fp64 host reference and the XLA matmul impl."""
    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.core import permutations
    from repro.core.distance import distance_matrix
    from repro.data.microbiome import synthetic_study

    x, g = synthetic_study(n, n_features, n_groups, effect_size=1.0,
                           seed=seed)
    t0 = time.perf_counter()
    dm = jax.block_until_ready(distance_matrix(jnp.asarray(x),
                                               "braycurtis"))
    log(f"paper cell: braycurtis D n={n} ({dm.nbytes / 1e9:.2f} GB f32) "
        f"built in {time.perf_counter() - t0:.1f}s")
    key = jax.random.key(seed)
    grouping = jnp.asarray(g)

    t0 = time.perf_counter()
    res = engine.run(dm, grouping, n_perms=n_perms, impl=impl, key=key)
    f_k = np.asarray(res.f_perms)
    log(f"  plan: {res.plan}")
    log(f"  {n_perms} perms in {time.perf_counter() - t0:.1f}s (first "
        f"call, compile included); F={f_k[0]:.6g} p={float(res.p_value):.6g}")
    if expect_impl and not res.plan.startswith(expect_impl):
        raise AssertionError(f"plan runs {res.plan.split('[')[0]!r}, "
                             f"expected {expect_impl!r}")
    if jax.default_backend() == "tpu" and "interpreted" in res.plan:
        raise AssertionError("a Pallas kernel ran interpreted on TPU")

    t0 = time.perf_counter()
    res_x = engine.run(dm, grouping, n_perms=n_perms, impl="matmul", key=key)
    f_x = np.asarray(res_x.f_perms)
    log(f"  XLA matmul impl: {time.perf_counter() - t0:.1f}s; "
        f"plan {res_x.plan}")

    t0 = time.perf_counter()
    labels = np.asarray(permutations.permutation_batch(
        key, grouping, 0, ref_perms + 1))
    sw_ref, st_ref = host_reference(dm, labels, n_groups, n)
    log(f"  fp64 host reference over {ref_perms + 1} permutations in "
        f"{time.perf_counter() - t0:.1f}s")
    s_t = float(res.s_t)
    check("s_T vs fp64", abs(s_t - st_ref) / st_ref, ST_LIMIT)
    check("observed s_W vs fp64",
          abs(float(res.s_w) - sw_ref[0]) / sw_ref[0], SW_LIMIT)
    sw_k = sw_from_f(f_k[:ref_perms + 1], s_t, n, n_groups)
    check(f"s_W of perms 0..{ref_perms} vs fp64",
          float(np.max(np.abs(sw_k - sw_ref) / sw_ref)), SW_LIMIT)
    f_ref = st_ref / sw_ref - 1.0
    f_ref *= (n - n_groups) / (n_groups - 1)
    check(f"F of perms 0..{ref_perms} vs fp64",
          float(np.max(f_error(f_k[:ref_perms + 1], f_ref, n, n_groups))),
          SW_LIMIT)
    check(f"all {n_perms + 1} F vs XLA matmul",
          float(np.max(f_error(f_k, f_x, n, n_groups))), SW_LIMIT)
    check_p("p vs XLA matmul", f_k, f_x, n, n_groups, SW_LIMIT)

    if autotune:
        from repro.engine import planner, registry
        mat2 = dm * dm
        cands = list(registry.PALLAS_IMPLS) + ["matmul"]
        inv_gs = permutations.inv_group_sizes(grouping, n_groups)
        win = planner.autotune(mat2, grouping, inv_gs, candidates=cands,
                               key=key, use_cache=False)
        log(f"  autotune shoot-out over {cands}: {win} fastest on 16 "
            "perms (progress note, not a measurement of record)")
        del mat2
    log(f"  peak device bytes so far: {peak_gb():.2f} GB")
    return {"f": f_k, "plan": res.plan}


def phase_features(*, n, d, n_perms, metrics, seed=0, n_groups=8,
                   density=0.1, workdir=WORKDIR):
    """Features -> p-value through the Pallas megakernel, against the
    dense two-stage distance_matrix -> engine.run path."""
    import jax
    import jax.numpy as jnp

    from repro import engine, pipeline
    from repro.core.distance import distance_matrix
    from repro.data.microbiome import synthetic_sparse_counts

    shutil.rmtree(workdir, ignore_errors=True)
    try:
        cache, g = synthetic_sparse_counts(n, d, density=density, seed=seed,
                                           cache_dir=workdir,
                                           n_groups=n_groups)
        x = jnp.asarray(cache.to_array())
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    grouping = jnp.asarray(g)
    out = {}
    for metric in metrics:
        key = jax.random.key(seed)
        t0 = time.perf_counter()
        res = pipeline.pipeline(x, grouping, metric=metric, n_perms=n_perms,
                                key=key, materialize="fused-kernel",
                                fused_impl="pallas")
        f_k = np.asarray(res.f_perms)
        log(f"features {metric}: n={n} d={d} plan: {res.plan}")
        log(f"  fused-kernel {time.perf_counter() - t0:.1f}s (compile "
            f"included); F={f_k[0]:.6g} p={float(res.p_value):.6g}")
        if "fusedk.pallas" not in res.plan:
            raise AssertionError("features phase did not run the Pallas "
                                 "megakernel")
        t0 = time.perf_counter()
        dm = distance_matrix(x, metric)
        ref = engine.run(dm, grouping, n_perms=n_perms, key=key)
        f_r = np.asarray(ref.f_perms)
        log(f"  dense two-stage {time.perf_counter() - t0:.1f}s; plan "
            f"{ref.plan}")
        del dm
        check(f"{metric} s_T vs dense",
              abs(float(res.s_t) - float(ref.s_t)) / float(ref.s_t),
              ST_LIMIT)
        check(f"{metric} all {n_perms + 1} F vs dense",
              float(np.max(f_error(f_k, f_r, n, n_groups))), SW_LIMIT)
        check_p(f"{metric} p vs dense", f_k, f_r, n, n_groups, SW_LIMIT)
        out[metric] = f_k
    log(f"  peak device bytes so far: {peak_gb():.2f} GB")
    return out


def phase_serving(*, sizes, n_perms, seed=0, n_groups=4, n_features=64):
    """A handful of mixed-size studies through PermanovaServer, each
    answer checked against engine.run on the same request."""
    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.core.distance import distance_matrix
    from repro.data.microbiome import synthetic_study
    from repro.serve.permanova import PermanovaServer, StudyRequest

    reqs = []
    for i, n in enumerate(sizes):
        x, g = synthetic_study(n, n_features, n_groups, effect_size=0.3,
                               seed=seed + i)
        dm = np.asarray(distance_matrix(jnp.asarray(x), "braycurtis"))
        reqs.append(StudyRequest(grouping=g, dm=dm, n_perms=n_perms,
                                 seed=seed + i, request_id=f"n{n}"))
    srv = PermanovaServer(workers=2, block=128)
    t0 = time.perf_counter()
    results = srv.serve(reqs)
    log(f"serving: {len(reqs)} studies n={list(sizes)} in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    for req, r in zip(reqs, results):
        if r.status != "ok":
            raise AssertionError(f"{req.request_id}: status {r.status} "
                                 f"{r.error}")
        n = req.dm.shape[0]
        ref = engine.run(req.dm, req.grouping, n_perms=n_perms,
                         key=jax.random.key(req.seed))
        f_s, f_r = float(r.result.f_stat), float(ref.f_stat)
        p_s, p_r = float(r.result.p_value), float(ref.p_value)
        log(f"  {req.request_id} bucket {r.bucket}: F {f_s:.6g} vs "
            f"{f_r:.6g}, p {p_s:.4g} vs {p_r:.4g}")
        check(f"{req.request_id} observed F vs engine.run",
              float(f_error([f_s], [f_r], n, n_groups)[0]), SW_LIMIT)
        # the server pads to its bucket, so its null draws may differ
        # from the unpadded run's: the p-values agree in distribution
        sd = np.sqrt(max(p_r * (1 - p_r), 1.0 / n_perms) / n_perms)
        check(f"{req.request_id} p vs engine.run (Monte-Carlo)",
              abs(p_s - p_r), 5.0 * sd + 2.0 / (n_perms + 1))
    return results


def phase_four_chips(*, n, n_perms, n_groups, n_features, seed=0,
                     model_ways=4, ref_perms=4, impl="auto"):
    """The row-sharded matrix path (core.distributed) with D born sharded
    over 'model', against a blocked fp64 host reference."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed, permutations
    from repro.data.microbiome import synthetic_study
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model_ways=model_ways)
    log(f"four chips: mesh {dict(mesh.shape)} over "
        f"{[d.id for d in mesh.devices.flat]}")
    x, g = synthetic_study(n, n_features, n_groups, effect_size=1.0,
                           seed=seed)
    t0 = time.perf_counter()
    dm = jax.block_until_ready(distributed.distance_matrix_sharded(
        mesh, jnp.asarray(x), "braycurtis"))
    per_dev = sorted({s.data.nbytes for s in dm.addressable_shards})
    log(f"  D {dm.shape} ({dm.nbytes / 1e9:.1f} GB f32) built sharded in "
        f"{time.perf_counter() - t0:.1f}s; {per_dev[-1] / 1e9:.2f} GB per "
        "device")
    key = jax.random.key(seed)
    grouping = jnp.asarray(g)
    t0 = time.perf_counter()
    res = distributed.permanova_distributed(mesh, dm, grouping,
                                            n_perms=n_perms, key=key,
                                            impl=impl)
    f_d = np.asarray(res.f_perms)
    log(f"  {n_perms} perms in {time.perf_counter() - t0:.1f}s (compile "
        f"included); F={f_d[0]:.6g} p={float(res.p_value):.6g}")
    t0 = time.perf_counter()
    labels = np.asarray(permutations.permutation_batch(
        key, grouping, 0, ref_perms + 1))
    sw_ref, st_ref = host_reference(dm, labels, n_groups, n)
    log(f"  fp64 host reference in {time.perf_counter() - t0:.1f}s")
    s_t = float(res.s_t)
    check("s_T vs fp64", abs(s_t - st_ref) / st_ref, ST_LIMIT)
    check("observed s_W vs fp64",
          abs(float(res.s_w) - sw_ref[0]) / sw_ref[0], SW_LIMIT)
    sw_d = sw_from_f(f_d[:ref_perms + 1], s_t, n, n_groups)
    check(f"s_W of perms 0..{ref_perms} vs fp64",
          float(np.max(np.abs(sw_d - sw_ref) / sw_ref)), SW_LIMIT)
    stats = [d.memory_stats() or {} for d in mesh.devices.flat]
    log("  peak device bytes: " + ", ".join(
        f"{s.get('peak_bytes_in_use', 0) / 1e9:.2f} GB" for s in stats))
    return {"f": f_d}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded matrix path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s). Nothing "
              "was run.", file=sys.stderr)
        return 2

    sys.path.insert(0, SRC)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", "off")
    from repro import compile_cache, obs
    from repro.obs import metrics
    cache_dir = compile_cache.enable()
    obs.enable(trace=False, metrics=True)
    d0 = devices[0]
    log(f"{len(devices)} x {d0.device_kind} ({d0.platform}); jax "
        f"{jax.__version__}; compile cache {cache_dir}")

    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(
            seed=args.seed, **FOUR_CHIPS))]
    else:
        phases = [
            ("paper_cell", lambda: phase_paper_cell(seed=args.seed,
                                                    **PAPER)),
            ("features", lambda: phase_features(seed=args.seed,
                                                **FEATURES)),
            ("serving", lambda: phase_serving(seed=args.seed, **SERVING)),
        ]
    for name, run in phases:
        t0, c0 = time.perf_counter(), compiles()
        run()
        log(f"phase {name} passed in {time.perf_counter() - t0:.1f}s, "
            f"{compiles() - c0} backend compiles")
    failed = metrics.value("autotune.candidate_failed")
    if failed:
        raise AssertionError(f"{failed:g} autotune candidates failed to "
                             "lower or run (see the log lines above)")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
