"""The row-sharded matrix path (core.distributed.permanova_distributed)
as one compiled program, on 4 virtual CPU devices: a warm call compiles
nothing, the answers are engine.run's on the same D and the eager
shard_map's at the same key, and the program names its phases (obs
spans), its shapes (gauges) and its label and psum layers (scopes in the
compiled HLO's op metadata)."""

import json

import pytest

from conftest import run_subprocess

CODE = r"""
import json, re
import jax, jax.numpy as jnp
import numpy as np
from repro import engine, obs
from repro.core import distributed, permutations
from repro.core.permanova import f_from_sw
from repro.data.microbiome import synthetic_study
from repro.core import distance
from repro.engine.scheduler import LABELS
from repro.launch.mesh import make_mesh
from repro.obs import metrics

N, G, PERMS = 203, 5, 99
x, g = synthetic_study(N, 16, G, effect_size=0.5, seed=3)
x, g = jnp.asarray(x, jnp.float32), jnp.asarray(g, jnp.int32)
out = {}
obs.enable(trace=True, metrics=True)

def compiles():
    return metrics.value("jax.backend_compiles")

def sw_of(f, s_t):
    f = np.asarray(f, np.float64)
    return float(s_t) / (1.0 + f / ((N - G) / (G - 1)))

for shape in ((1, 4), (2, 2)):
    mesh = make_mesh(shape, ("data", "model"))
    dm = distributed.distance_matrix_sharded(mesh, x, "braycurtis")
    ref_dm = jnp.asarray(np.asarray(dm)[:N])
    for impl in ("auto", "pallas_matmul"):
        tag = f"{shape[0]}x{shape[1]}.{impl}"
        key = jax.random.key(11)
        obs.clear()
        c0 = compiles()
        r = distributed.permanova_distributed(mesh, dm, g, n_perms=PERMS,
                                              key=key, impl=impl)
        c1 = compiles()
        r2 = distributed.permanova_distributed(mesh, dm, g, n_perms=PERMS,
                                               key=jax.random.key(12),
                                               impl=impl)
        np.asarray(r2.f_perms)
        c2 = compiles()
        spans = [e["name"] for e in obs.events()]
        ref = engine.run(ref_dm, g, n_perms=r.n_perms, key=key)
        # the same shard_map run eagerly (sw_distributed), at the same key
        rimpl = distributed.resolve_impl(impl, N, PERMS + 1, G)
        sw_eager = np.asarray(distributed.sw_distributed(
            mesh, dm * dm, g, permutations.inv_group_sizes(g, G), key,
            PERMS + 1, impl=rimpl))
        f_eager = np.asarray(f_from_sw(jnp.asarray(sw_eager), r.s_t, N, G))
        # the program permanova_distributed ran (cached): its s_W output
        sw_jit = np.asarray(distributed._program(
            dm, g, key, mesh=mesh, impl=rimpl, n_groups=G,
            n_total=PERMS + 1, perm_block=64)[0])
        sw_d, sw_r = sw_of(r.f_perms, r.s_t), sw_of(ref.f_perms, ref.s_t)
        out[tag] = {
            "impl": rimpl, "first": c1 - c0, "second": c2 - c1,
            "n_f": int(np.shape(r.f_perms)[0]),
            "n_ref": int(np.shape(ref.f_perms)[0]),
            "sw_rel": float(np.max(np.abs(sw_d - sw_r) / sw_r)),
            "st_rel": abs(float(r.s_t) - float(ref.s_t)) / float(ref.s_t),
            "count": round(float(r.p_value) * (r.n_perms + 1)),
            "count_ref": round(float(ref.p_value) * (ref.n_perms + 1)),
            "eager_bits": bool(np.array_equal(sw_jit, sw_eager)),
            "eager_f_rel": float(np.max(np.abs(np.asarray(r.f_perms) - f_eager)
                                        / np.abs(f_eager))),
            "spans": spans,
            "gauges": {k: metrics.gauge_value(k) for k in (
                "dist.model_ways", "dist.rows_per_chip",
                "dist.perms_per_chip")},
            "plan": r.plan,
        }
        metrics.reset()

# the scopes, in the compiled program's op metadata
mesh = make_mesh((1, 4), ("data", "model"))
dm = distributed.distance_matrix_sharded(mesh, x, "braycurtis")
txt = distributed._program.lower(
    dm, g, jax.random.key(0), mesh=mesh, impl="pallas_matmul", n_groups=G,
    n_total=PERMS + 1, perm_block=64).compile().as_text()
ops = []
for line in txt.splitlines():
    m = re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = .*? ([a-z][\w\-]*)\(", line)
    name = re.search(r'op_name="([^"]*)"', line)
    if m and name:
        ops.append((m.group(1), name.group(1)))
out["scopes"] = {
    "sort_labels": [LABELS in n for op, n in ops if op == "sort"],
    "all_reduce": sorted({n.split("/")[-2] if "/" in n else n
                          for op, n in ops if op.startswith("all-reduce")}),
    "psum": any("dist.psum" in n for op, n in ops
                if op.startswith("all-reduce")),
}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    text = run_subprocess(CODE, devices=4, timeout=600)
    return json.loads(text.strip().splitlines()[-1])


CASES = ["1x4.auto", "1x4.pallas_matmul", "2x2.auto", "2x2.pallas_matmul"]


@pytest.mark.parametrize("case", CASES)
def test_warm_call_compiles_nothing(four_devices, case):
    r = four_devices[case]
    assert r["first"] >= 1 and r["second"] == 0
    assert "one compiled program" in r["plan"]


@pytest.mark.parametrize("case", CASES)
def test_jitted_program_matches_engine_run(four_devices, case):
    """Same key, same D: every permutation's s_W to f32 rounding (the row
    partials and their psum sum in another order than engine.run's one
    contraction) and the same p count. A 'data' axis of 2 draws each
    half of the permutations from its own folded keys: a fold collapsed
    onto one shard's indices would put half the null far off."""
    r = four_devices[case]
    assert r["n_f"] == r["n_ref"] == 100
    assert r["sw_rel"] < 2e-6 and r["st_rel"] < 2e-6
    assert r["count"] == r["count_ref"]


@pytest.mark.parametrize("case", CASES)
def test_jitted_program_is_the_eager_shard_map_bit_for_bit(four_devices,
                                                           case):
    """s_W of every permutation bit for bit at the same key, so the
    permutations folded inside the jitted shard_map are the eager ones; F
    to f32 rounding only, as XLA turns F's divisions by the constant
    degrees of freedom into products inside the program."""
    r = four_devices[case]
    assert r["eager_bits"]
    assert r["eager_f_rel"] < 1e-6


@pytest.mark.parametrize("case", CASES)
def test_spans_and_gauges(four_devices, case):
    r = four_devices[case]
    assert r["spans"].count("engine.dist.sw") == 2
    assert r["spans"].count("engine.dist.finalize") == 2
    data, model = (int(v) for v in case.split(".")[0].split("x"))
    rows = -(-203 // model)
    # the second call traces nothing, so only the first sets the gauges
    assert r["gauges"] == {"dist.model_ways": model,
                           "dist.rows_per_chip": rows,
                           "dist.perms_per_chip": 100 // data}


def test_label_and_psum_scopes_in_the_compiled_program(four_devices):
    s = four_devices["scopes"]
    assert s["sort_labels"] and all(s["sort_labels"])
    assert s["psum"]


DISTRIBUTED_PERMANOVA_CODE = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core import distance, permanova
from repro.core.distributed import permanova_distributed
from repro.data.microbiome import synthetic_study
from repro.launch.mesh import make_mesh

x, grouping = synthetic_study(48, 32, 3, effect_size=0.0, seed=7)
dm = distance.braycurtis(jnp.asarray(x))
ref = permanova(dm, jnp.asarray(grouping), n_perms=99, sw_impl="brute")
for shape, names in [((4, 2), ("data", "model")),
                     ((2, 2, 2), ("pod", "data", "model"))]:
    mesh = make_mesh(shape, names)
    for impl in ("brute", "matmul"):
        r = permanova_distributed(mesh, dm, jnp.asarray(grouping),
                                  n_perms=99, impl=impl)
        assert abs(float(r.f_stat) - float(ref.f_stat)) < 1e-4
        assert abs(float(r.p_value) - float(ref.p_value)) < 1e-6
print("DIST-PERMANOVA-OK")
"""


@pytest.mark.multidevice
def test_distributed_permanova_multi_device():
    from conftest import run_subprocess
    out = run_subprocess(DISTRIBUTED_PERMANOVA_CODE, devices=8, timeout=600)
    assert "DIST-PERMANOVA-OK" in out
