"""Sharding rules + multi-device behaviour (subprocess with fake devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import (RULES_MULTI_POD, RULES_SINGLE_POD,
                                  logical_to_spec)


def _mesh_1():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


class TestLogicalToSpec:
    def test_basic_mapping(self):
        mesh = _mesh_1()
        spec = logical_to_spec(("embed", "mlp"), (64, 128), mesh,
                               RULES_SINGLE_POD)
        assert spec == P("data", "model")

    def test_indivisible_dim_dropped(self):
        mesh = _mesh_1()
        # sizes are 1 so everything divides; simulate with a fake mesh of 2
        # via the rules path in a subprocess instead — here check None axes
        spec = logical_to_spec((None, "mlp"), (7, 128), mesh,
                               RULES_SINGLE_POD)
        assert spec == P(None, "model")

    def test_trailing_nones_trimmed(self):
        mesh = _mesh_1()
        spec = logical_to_spec(("batch", None, None), (8, 4, 4), mesh,
                               RULES_SINGLE_POD)
        assert spec == P("data")

    def test_multi_pod_batch_axes(self):
        assert RULES_MULTI_POD.rules["batch"] == ("pod", "data")


MULTI_DEVICE_CODE = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import SMOKES
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.sharding.rules import set_active, rules_for_mesh
from repro.sharding.state import axes_to_shardings, batch_axes, train_state_axes
from repro.train.step import make_train_state_init, make_train_step
from repro.optim import adamw

assert len(jax.devices()) == 8, jax.devices()
mesh = make_mesh((4, 2), ("data", "model"))
cfg = SMOKES["internlm2-1.8b"].replace(attn_q_chunk=8)
model = build_model(cfg)
opt = adamw()
step = make_train_step(model, opt)
init = make_train_state_init(model, opt)
state = init(jax.random.key(0))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, size=(8, 32)).astype(np.int32)),
         "targets": jnp.asarray(rng.integers(0, cfg.vocab, size=(8, 32)).astype(np.int32))}

# single-device reference
ref_state, ref_metrics = jax.jit(step)(state, batch)

state_abs = jax.eval_shape(init, jax.random.key(0))
rules = rules_for_mesh(mesh)
state_sh = axes_to_shardings(train_state_axes(model, opt, state_abs), state_abs, mesh, rules)
batch_sh = axes_to_shardings(batch_axes(batch), batch, mesh, rules)
with set_active(mesh):
    sharded_step = jax.jit(step, in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, NamedSharding(mesh, P())))
    state_in = jax.device_put(state, state_sh)
    batch_in = jax.device_put(batch, batch_sh)
    out_state, metrics = sharded_step(state_in, batch_in)

err = abs(float(metrics["loss"]) - float(ref_metrics["loss"]))
assert err < 5e-3, f"sharded loss mismatch: {err}"
for a, b in zip(jax.tree.leaves(ref_state.params), jax.tree.leaves(out_state.params)):
    d = np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)))
    assert d < 5e-2, f"param mismatch {d}"
print("SHARDED-TRAIN-OK", float(metrics["loss"]))
"""


@pytest.mark.multidevice
def test_sharded_train_step_matches_single_device(run=None):
    from conftest import run_subprocess
    out = run_subprocess(MULTI_DEVICE_CODE, devices=8, timeout=600)
    assert "SHARDED-TRAIN-OK" in out


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
