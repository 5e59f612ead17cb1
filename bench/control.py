#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 [--tests 2]

For each seed, in one process: the cell's inputs, `--tests` tests of the
program through the cell's own entry, and the same tests computed by the
reference in bfloat16 in the program's place (the control), plus the
single-pass form (bfloat16 operands, float32 sums). Each is compared with
the float64 reference as a run compares its window, and one JSON line per
seed and side gives the numbers. The limits in the cell's configuration
lie above the program's largest reading and below the control's least.
The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, tests: int, sides=("program", "bf16", "bf16_f32")):
    import jax.numpy as jnp
    import numpy as np

    from bench import data, reference
    entry = cell.entry
    state = entry.setup(cell.config, cell.traffic, seed)
    out = []
    if "program" in sides:
        entry.run_test(state, 0)
        t0 = time.perf_counter()
        answers = [entry.run_test(state, t)[0] for t in range(1, tests + 1)]
        wall = time.perf_counter() - t0
    dm = entry.reference_matrix(state)
    try:
        for side in sides:
            if side == "program":
                got = answers
            else:
                acc = jnp.bfloat16 if side == "bf16" else jnp.float32
                got = [reference.control_answer(
                    dm, state.grouping, data.test_key(state.perm_key, t), t,
                    state.n_perms, cell.config["n_groups"], acc=acc)
                    for t in range(1, tests + 1)]
            state.rng = np.random.default_rng(seed)   # same sample per side
            checks, failed = reference.compare(
                got, dm, np.asarray(state.grouping), state.perm_key,
                state.n_perms, cell.config["n_groups"], cell.config["limits"],
                cell.config["check_perms"], state.rng)
            row = {"cell": cell.name, "seed": seed, "side": side,
                   "failed": failed,
                   **{c.name: c.value for c in checks}}
            if side == "program":
                row["wall_s"] = wall
            out.append(row)
    finally:
        if dm is not getattr(state, "dm", None):
            dm.delete()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tests", type=int, default=2)
    args = ap.parse_args(argv)
    os.environ["REPRO_AUTOTUNE_CACHE"] = "off"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import manifest
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    cell = manifest.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in readings(cell, seed, args.tests):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
