#!/usr/bin/env python3
"""Time to an exact PERMANOVA result on the chip, one cell per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic, entry and metric readers are found by
name (bench/manifest.py). A run makes its inputs on the device from the
seed, warms the cell's shapes with one test, then starts tests back to
back until `--seconds` have passed since the first one started, and
finishes the test in flight. Every test draws a fresh permutation key.
Then it reads the peak device memory, checks the window's answers against
the float64 reference (bench/reference.py), prints each number compared
beside its limit on stderr, and prints one JSON line last on stdout.

With --trace 0 the line carries the end-to-end metrics; with --trace 1
the window runs under the profiler and the line carries the per-layer
metrics, the device's busy and window seconds, and a breakdown. It exits
non-zero and prints no result where JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    config: dict
    traffic: dict
    peaks: Optional[dict]     # bench/peaks.json entry of this device kind
    chips: int
    trace: object             # devtrace.Trace of the window, or None
    compiles: int             # backend compiles inside the window
    peak_bytes: int           # fullest device's peak_bytes_in_use


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peaks_for(kind: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peak entry for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def compiles() -> int:
    from repro.obs import metrics
    return int(metrics.value("jax.backend_compiles"))


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         t_start: float = T_START) -> int:
    args = parse(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from bench import manifest

    cell = manifest.load_cell(args.workload, root)
    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            log(f"{cell.name} needs {cell.chips} TPU chip(s); JAX sees "
                f"{len(devices)} {devices[0].platform} device(s). Nothing "
                "was run.")
            return 3
        peaks = peaks_for(devices[0].device_kind, root)
    else:
        peaks = None

    from repro import obs
    prev = (obs.trace_enabled(), obs.metrics_enabled())
    obs.enable(trace=bool(args.trace), metrics=True)
    log(f"{cell.name}: {len(devices)} x {devices[0].device_kind}; jax "
        f"{jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    try:
        return _run(args, cell, devices, peaks, t_start)
    finally:
        obs.enable(trace=prev[0], metrics=prev[1])


def _run(args, cell, devices, peaks, t_start) -> int:
    import jax
    from bench import devtrace, reference
    used = devices[:cell.chips]

    entry = cell.entry
    state = entry.setup(cell.config, cell.traffic, args.seed)
    _, plan = entry.run_test(state, 0)             # warms every shape
    log(f"plan: {plan}")

    trace_dir = os.path.join(OUT_DIR, f"{cell.name}.{args.seed}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler = jax.profiler.trace(
            trace_dir, profiler_options=devtrace.profile_options())
    else:
        profiler = contextlib.nullcontext()
    answers, times = [], []
    c0 = compiles()
    with profiler:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            t_end = t0
            while t_end - t0 < args.seconds or not answers:
                t = len(answers) + 1
                with jax.profiler.TraceAnnotation(devtrace.TEST):
                    ans, _ = entry.run_test(state, t)
                t_prev, t_end = t_end, time.perf_counter()
                answers.append(ans)
                times.append(t_end - t_prev)
    n_compiles = compiles() - c0
    mem = peak_bytes(used)
    test_s = (t_end - t0) / len(answers)
    log(f"{len(answers)} tests in {t_end - t0:.3f}s; per test "
        f"{[round(x, 4) for x in times]}; {n_compiles} compiles in the "
        f"window; set-up {setup_s:.3f}s; peak {mem / 1e9:.3f} GB")

    t_ref = time.perf_counter()
    checks, failed = reference.check(state, answers,
                                     entry.reference_matrix(state))
    log(f"reference and comparison {time.perf_counter() - t_ref:.3f}s")

    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result = {"correct": all(c.ok for c in checks) and failed == 0,
              "attempted": len(answers), "failed": failed}
    if args.trace:
        tr = devtrace.load(trace_dir)
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(trace_dir + ".summary.json", "w") as f:
            json.dump(devtrace.summary(tr), f, indent=1)
        ctx = Context(config=cell.config, traffic=cell.traffic, peaks=peaks,
                      chips=cell.chips, trace=tr, compiles=n_compiles,
                      peak_bytes=mem)
        for m, reader in cell.per_layer:
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = devtrace.busy_s(tr, list(range(cell.chips)))
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                               "idle_gaps": devtrace.idle_gaps(tr)}
    else:
        values = {"test_s": test_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r}")
    print(json.dumps(result), flush=True)
    return 0


def cli() -> int:
    # before JAX is imported: no persisted autotune winner may change the
    # plan between two checkouts, and compiled programs stay in the checkout
    os.environ["REPRO_AUTOTUNE_CACHE"] = "off"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    return main()


if __name__ == "__main__":
    sys.exit(cli())
