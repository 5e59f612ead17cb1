# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness (deliverable d).

  PYTHONPATH=src python -m benchmarks.run [--only fig1,stream,...] [--json]

Suites:
  fig1         paper Figure 1 analogue — s_W variants by algorithm
  stream       paper Appendix A2 — STREAM copy/scale/add/triad
  sweep        paper section 2 workload envelope (n, n_perms scaling)
  pa_roofline  PERMANOVA arithmetic-intensity roofline on TPU v5e
  serve        always-on PERMANOVA serving: studies/sec vs latency SLO,
               p99 from serve.step spans, worker-death recovery overhead

--json writes one BENCH_<suite>.json per suite (rows + host metadata) into
--json-dir (default: cwd) — the machine-readable perf trajectory consumed
by CI across PRs.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback

import jax

from benchmarks import (fig1_sw_variants, permanova_roofline,
                        pipeline_scale, serve_bench, stream_triad,
                        sweep_scale)
from repro import compile_cache, obs

SUITES = {
    "fig1": fig1_sw_variants.run,
    "stream": stream_triad.run,
    "sweep": sweep_scale.run,
    "pipeline": pipeline_scale.run,
    "pa_roofline": permanova_roofline.run,
    "serve": serve_bench.run,
}


def _host_meta() -> dict:
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
        "platform": platform.platform(),
        "python": platform.python_version(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<suite>.json per suite")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<suite>.json files")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)
    compile_cache.enable()

    # counters only (no spans): retraces/compiles and traffic counters per
    # suite get stamped into BENCH_*.json without perturbing the timings
    obs.enable(trace=False, metrics=True)

    print("name,us_per_call,derived")
    failed = []
    for name in names:
        rows = []

        def emit(row_name, us, derived, extra=None, _rows=rows):
            print(f"{row_name},{us:.1f},{derived}")
            row = {"name": row_name, "us_per_call": round(us, 1),
                   "derived": derived}
            if extra:
                row.update(extra)   # machine-readable columns (precision,
                                    # feat_bytes_mib, ...) for CI trending
            _rows.append(row)

        t0 = time.time()
        before = obs.metrics.snapshot()
        ok = True
        try:
            SUITES[name](emit)
        except Exception:  # noqa: BLE001
            ok = False
            failed.append(name)
            traceback.print_exc()
        obs.record_device_memory()
        if args.json:
            os.makedirs(args.json_dir, exist_ok=True)
            payload = {
                "suite": name,
                "ok": ok,
                "wall_s": round(time.time() - t0, 2),
                "host": _host_meta(),
                "obs": obs.metrics.counter_delta(before),
                "rows": rows,
            }
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"# wrote {path}", file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
