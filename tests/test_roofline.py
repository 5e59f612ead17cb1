"""Roofline machinery: loop-aware HLO costing and collective parsing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_cost import loop_aware_cost
from repro.roofline.analysis import parse_collective_bytes


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestLoopAwareCost:
    def test_scan_matches_unroll(self):
        def scanned(x, w):
            def body(c, _):
                return c @ w, None
            out, _ = jax.lax.scan(body, x, None, length=10)
            return out

        def unrolled(x, w):
            def body(c, _):
                return c @ w, None
            out, _ = jax.lax.scan(body, x, None, length=10, unroll=True)
            return out

        xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        c_s = loop_aware_cost(_compile(scanned, xs, xs).as_text())
        c_u = loop_aware_cost(_compile(unrolled, xs, xs).as_text())
        expect = 10 * 2 * 128 ** 3
        assert c_s.flops == pytest.approx(expect, rel=0.01)
        assert c_u.flops == pytest.approx(expect, rel=0.01)

    def test_nested_loops_multiply(self):
        def nested(x, w):
            def outer(c, _):
                def inner(c2, _):
                    return c2 @ w, None
                c2, _ = jax.lax.scan(inner, c, None, length=5)
                return c2, None
            out, _ = jax.lax.scan(outer, x, None, length=4)
            return out

        xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c = loop_aware_cost(_compile(nested, xs, xs).as_text())
        assert c.flops == pytest.approx(20 * 2 * 64 ** 3, rel=0.01)

    def test_dot_flops_with_batch_dims(self):
        def f(a, b):
            return jnp.einsum("bij,bjk->bik", a, b)

        a = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
        b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
        c = loop_aware_cost(_compile(f, a, b).as_text())
        assert c.flops == pytest.approx(2 * 4 * 32 * 16 * 8, rel=0.2)

    def test_model_flops_close_to_6nd(self):
        from repro.configs.registry import SMOKES
        from repro.models.model import build_model
        cfg = SMOKES["internlm2-1.8b"]
        model = build_model(cfg)
        params = model.abstract_params()
        batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
                 "targets": jax.ShapeDtypeStruct((4, 64), jnp.int32)}

        def grad_fn(p, b):
            return jax.grad(lambda pp: model.loss(pp, b)[0])(p)

        c = loop_aware_cost(_compile(grad_fn, params, batch).as_text())
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        six_nd = 6 * n * 4 * 64
        assert 0.8 * six_nd < c.flops < 2.0 * six_nd


class TestCollectiveParsing:
    @pytest.mark.multidevice
    def test_psum_produces_all_reduce_bytes(self):
        from conftest import run_subprocess
        code = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.roofline.hlo_cost import loop_aware_cost
mesh = make_mesh((8,), ("data",))
def f(x):
    return jax.shard_map(lambda y: jax.lax.psum(y, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P())(x)
xs = jax.ShapeDtypeStruct((1024,), jnp.float32)
c = jax.jit(f).lower(xs).compile()
cost = loop_aware_cost(c.as_text())
assert cost.coll_bytes > 0, cost
assert cost.coll_by_kind["all-reduce"] > 0, cost.coll_by_kind
print("COLLECTIVE-OK", cost.coll_bytes)
"""
        out = run_subprocess(code, devices=8)
        assert "COLLECTIVE-OK" in out

    def test_text_parser_units(self):
        text = (" %ar = f32[256]{0} all-reduce(f32[256]{0} %x), "
                "replica_groups={}\n")
        out = parse_collective_bytes(text)
        assert out["all-reduce"] == 1024  # operand bytes
