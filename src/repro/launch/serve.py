"""Serving launcher.

  # always-on PERMANOVA service (chaos smoke: inject a worker death and
  # assert the served result is bit-identical to the failure-free run)
  PYTHONPATH=src python -m repro.launch.serve permanova \
      --studies 6 --workers 3 --inject-death --trace serve_trace.json
"""

from __future__ import annotations

import argparse

import numpy as np

from repro import compile_cache, obs


def _pa_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--studies", type=int, default=6,
                    help="number of synthetic studies to admit")
    ap.add_argument("--n-min", type=int, default=18)
    ap.add_argument("--n-max", type=int, default=40)
    ap.add_argument("--groups", type=int, default=3)
    ap.add_argument("--n-perms", type=int, default=199)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--block", type=int, default=32)
    ap.add_argument("--queue-limit", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject-death", action="store_true",
                    help="replay the stream with a worker killed mid-"
                         "request and assert bit-identical results")
    ap.add_argument("--batch", type=int, default=0,
                    help="replay the stream with same-bucket requests "
                         "coalesced into batched dispatches of up to this "
                         "many studies; asserts bit-identity against the "
                         "serial run and zero warm retraces")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome trace of the serve session")


def _synth_stream(args: argparse.Namespace) -> list:
    from repro.core.distance import distance_matrix
    from repro.serve.permanova import StudyRequest

    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.studies):
        n = int(rng.integers(args.n_min, args.n_max + 1))
        x = rng.normal(size=(n, 5)).astype(np.float32)
        g = rng.integers(0, args.groups, size=n).astype(np.int32)
        reqs.append(StudyRequest(
            grouping=g, dm=np.asarray(distance_matrix(x, "euclidean")),
            n_perms=args.n_perms, seed=i, request_id=f"study{i}"))
    return reqs


def _run_stream(args: argparse.Namespace, reqs, injector=None) -> list:
    from repro.serve.permanova import PermanovaServer

    srv = PermanovaServer(workers=args.workers, block=args.block,
                          queue_limit=args.queue_limit, injector=injector)
    return srv.serve(reqs)


def cmd_permanova(args: argparse.Namespace) -> int:
    from repro.runtime.faultinject import FaultInjector
    from repro.serve.permanova import serve_stats_from_events

    reqs = _synth_stream(args)
    with obs.session(args.trace):
        clean = _run_stream(args, reqs)
        stats = serve_stats_from_events(obs.events())
    bad = [r for r in clean if not r.ok]
    for r in clean:
        print(f"[serve.pa] {r.request_id}: status={r.status} "
              f"F={float(r.result.f_stat):.5f} "
              f"p={float(r.result.p_value):.4f} "
              f"bucket={r.bucket} wall={r.wall_s:.2f}s")
    print(f"[serve.pa] requests={stats['requests']} "
          f"rps={stats['requests_per_s']:.2f} "
          f"p50={stats['p50_s'] * 1e3:.1f}ms "
          f"p99={stats['p99_s'] * 1e3:.1f}ms")
    if bad:
        print(f"[serve.pa] FAILED requests: {[r.request_id for r in bad]}")
        return 1
    if args.trace:
        print(f"[serve.pa] trace written to {args.trace}")

    if args.batch:
        # batched smoke: same stream coalesced by shape bucket; the
        # per-request fold_in(key, global_index) draws make the batched
        # dispatch bit-identical to serial serving, and a second warm
        # replay must reuse every traced jaxpr (fixed batch composition)
        from repro.obs import jaxhooks
        from repro.serve.permanova import PermanovaServer

        with obs.session():
            srv = PermanovaServer(workers=args.workers, block=args.block,
                                  queue_limit=args.queue_limit,
                                  max_batch=args.batch)
            batched = srv.serve(reqs, batched=True, max_batch=args.batch)
            for c, b in zip(clean, batched):
                assert b.ok, f"{b.request_id} failed batched: {b.error}"
                assert float(c.result.f_stat) == float(b.result.f_stat), \
                    f"{c.request_id}: F diverged under batching"
                assert float(c.result.p_value) == float(b.result.p_value), \
                    f"{c.request_id}: p diverged under batching"
                assert np.array_equal(np.asarray(c.result.f_perms),
                                      np.asarray(b.result.f_perms)), \
                    f"{c.request_id}: permutation set diverged under batching"
            before = obs.metrics.value(jaxhooks.RETRACES, 0.0)
            warm = srv.serve(reqs, batched=True, max_batch=args.batch)
            after = obs.metrics.value(jaxhooks.RETRACES, 0.0)
            assert all(r.ok for r in warm)
            assert after == before, \
                f"warm batched replay retraced {after - before:.0f} jaxprs"
            n_b = obs.metrics.value("serve.batches", 0.0)
            n_br = obs.metrics.value("serve.batched_requests", 0.0)
        print(f"[serve.pa] batched: max_batch={args.batch} "
              f"batches={n_b:.0f} batched_requests={n_br:.0f} -> "
              f"bit-identical to serial, 0 warm retraces")

    if args.inject_death:
        # chaos smoke: kill worker 0 two blocks into the stream; the
        # idempotent-block contract (global-index key folding) must
        # reconverge to bit-identical statistics
        inj = FaultInjector(seed=args.seed)
        inj.kill_worker_after_blocks(0, 2)
        faulty = _run_stream(args, reqs, injector=inj)
        for c, f in zip(clean, faulty):
            assert f.ok, f"{f.request_id} failed under fault: {f.error}"
            assert float(c.result.f_stat) == float(f.result.f_stat), \
                f"{c.request_id}: F diverged under worker death"
            assert float(c.result.p_value) == float(f.result.p_value), \
                f"{c.request_id}: p diverged under worker death"
            assert np.array_equal(np.asarray(c.result.f_perms),
                                  np.asarray(f.result.f_perms)), \
                f"{c.request_id}: permutation set diverged"
        print(f"[serve.pa] chaos: worker death injected -> "
              f"{len(faulty)} requests bit-identical to the clean run "
              f"(F, p, permutation sets)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    sub = ap.add_subparsers(dest="cmd", required=True)
    _pa_args(sub.add_parser(
        "permanova", help="always-on PERMANOVA service smoke"))
    args = ap.parse_args(argv)
    compile_cache.enable()
    return cmd_permanova(args)


if __name__ == "__main__":
    raise SystemExit(main())
