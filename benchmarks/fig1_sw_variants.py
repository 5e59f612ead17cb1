"""Paper Figure 1 analogue: PERMANOVA s_W execution time by algorithm.

The paper benchmarks brute-force vs tiled on MI300A CPU/GPU at
n=25145, perms=3999. This container is a 1-core CPU host, so we run a
scaled-down shape and report:
  * wall time per variant (host-CPU numbers, labeled as such),
  * effective matrix-stream bandwidth (bytes of mat2 consumed / s),
  * the projected time at the paper's full shape (linear in n^2 * perms).

Variants come from the engine registry (the unified s_W impl table): the
jnp brute / tiled / permblock-matmul forms, plus the Pallas kernels
(interpreted on the CPU: correctness-path timing only). The suite
also reports what the hardware-aware planner picks for this backend/shape.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import engine, hw
from repro.core import permutations
from repro.utils.timing import time_fn

N = 1024
N_PERMS = 64
N_GROUPS = 8

# fig1/jnp_* CSV names are stable across PRs; tuning mirrors the pre-engine
# hand-picked values.
JNP_TUNING = {
    "brute": {"block": 16},
    "tiled": {"tile": 256, "block": 4},
    "matmul": {"perm_block": 32},
}


def _instance(n=N, p=N_PERMS, g=N_GROUPS, seed=0):
    rng = np.random.default_rng(seed)
    d = rng.random((n, n)).astype(np.float32)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    grouping = rng.integers(0, g, size=n).astype(np.int32)
    grouping[:g] = np.arange(g)
    inv_gs = permutations.inv_group_sizes(jnp.asarray(grouping), g)
    gperms = permutations.permutation_batch(jax.random.key(0),
                                            jnp.asarray(grouping), 0, p)
    return jnp.asarray(d * d), gperms, inv_gs


def run(emit):
    mat2, gperms, inv_gs = _instance()
    n, p = mat2.shape[0], gperms.shape[0]
    stream_bytes = 4.0 * n * n * p          # brute-force mat2 traffic

    results = {}
    for name in engine.names(kind="jnp"):
        fn = jax.jit(engine.get(name).bound(**JNP_TUNING.get(name, {})))
        t = time_fn(fn, mat2, gperms, inv_gs, iters=3, warmup=1).median
        results[name] = t
        gbps = stream_bytes / t / 1e9
        scale = (hw.PAPER_N_DIMS / n) ** 2 * (hw.PAPER_N_PERMS / p)
        emit(f"fig1/jnp_{name}", t * 1e6, f"host_gbps={gbps:.2f} "
             f"projected_paper_shape_s={t * scale:.1f}")

    speedup = results["brute"] / results["matmul"]
    emit("fig1/matmul_speedup_over_brute", 0.0, f"x{speedup:.2f} "
         f"(paper: GPU brute 6x over CPU brute; here the MXU-form "
         f"reformulation is the analogous winner)")

    # What would the planner run here? (the paper's finding as dispatch)
    pl = engine.plan(n, p, N_GROUPS)
    emit("fig1/planner_pick", 0.0, f"impl={pl.impl} ({pl.reason})")
    for backend in ("cpu", "gpu", "tpu"):
        pl_b = engine.plan(hw.PAPER_N_DIMS, hw.PAPER_N_PERMS, N_GROUPS,
                           backend=backend)
        emit(f"fig1/planner_paper_shape_{backend}", 0.0, f"impl={pl_b.impl}")

    # Pallas kernels, smaller shape (interpreter overhead on the CPU)
    how = "compiled" if jax.default_backend() == "tpu" else "interpret"
    m2s, gps, igs = _instance(n=256, p=8)
    for name in engine.names(kind="pallas"):
        fn = engine.get(name).bound(tile_r=128, tile_c=128, perm_block=8)
        t = time_fn(fn, m2s, gps, igs, iters=2, warmup=1).median
        emit(f"fig1/{name}_{how}", t * 1e6,
             f"{how} on {jax.devices()[0].device_kind}")
