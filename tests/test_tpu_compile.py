"""Mosaic compiles of the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed here; the chip is described, not attached,
so these tests need no accelerator. Each lowers a kernel at the widths a
TPU plan uses — the paper's n=25145 padded to the 256 tile, the pipeline
registry's megakernel tiles — and compiles it in this test's own
process. A refused block shape, an unsupported layout or a kernel that
outgrows VMEM fails here instead of on the chip. Interpret mode is
switched off explicitly: the backend of this process is the CPU.
"""

import contextlib
import functools
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

PAPER_N_PAD = 25344          # 25145 rounded up to the 256 tile
P_TOTAL = 4000               # 3999 permutations + the observed labels
N_GROUPS = 8
CELL_GROUPS = 17             # the paper cell's EMPO level 3

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def _kernels(metric):
    """The HLO-name pattern a benchmark roofline reader finds its kernels
    by (bench/metrics/<metric>.py)."""
    path = os.path.join(REPO, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return re.compile(mod.KERNELS)


def _instructions(txt):
    """(line without its metadata, name, shape, opcode, op_name) of every
    instruction of the compiled module."""
    out = []
    for line in txt.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((re.sub(r",? metadata=\{[^}]*\}", "", line),
                        m.group(1), m.group(2), m.group(3),
                        op.group(1) if op else ""))
    return out


def _custom_calls(txt):
    """Instruction names of the Mosaic kernels, as a device trace names
    their ops."""
    return [name for line, name, _, _, _ in _instructions(txt)
            if 'custom_call_target="tpu_custom_call"' in line]


def _elements(shape):
    dims = re.match(r"^[a-z0-9]+\[([\d,]*)\]", shape)
    return math.prod(int(d) for d in dims.group(1).split(",") if d)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # a described-device compile cannot be read back from a persistent
    # cache without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """('data', 'model') = (1, 4) over the described host's chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


@contextlib.contextmanager
def _mxu_passes():
    """Yields a callable reading the `sw.mxu_passes` gauge that the
    one-hot kernel sets while the body is traced (0 if nothing set it)."""
    from repro import obs
    obs.enable(trace=False, metrics=True)
    try:
        obs.metrics.gauge_set("sw.mxu_passes", 0)
        yield lambda: obs.metrics.gauge_value("sw.mxu_passes")
    finally:
        obs.disable()


@pytest.mark.parametrize("n_groups", [N_GROUPS, CELL_GROUPS])
@pytest.mark.parametrize("variant", ["matmul", "permblock", "brute"])
def test_permanova_sw_kernel_paper_width(one_chip, variant, n_groups):
    from repro.engine import registry
    from repro.kernels.permanova_sw import ops
    tuning = dict(registry.get(f"pallas_{variant}").tuning)

    def fn(m2, g, w):
        return ops.permanova_sw(m2, g, w, variant=variant, interpret=False,
                                **tuning)

    with _mxu_passes() as passes:
        txt = _compile_text(
            fn, _spec(one_chip, (PAPER_N_PAD, PAPER_N_PAD), jnp.float32),
            _spec(one_chip, (P_TOTAL, PAPER_N_PAD), jnp.int32),
            _spec(one_chip, (n_groups,), jnp.float32))
        assert passes() == (3 if variant == "matmul" else 0)
    calls = _custom_calls(txt)
    assert len(calls) == 1 and _kernels("sw_roofline").match(calls[0])


def test_pallas_matmul_row_slab_four_chip_width(one_chip):
    """One device's row slab of the four-chip matrix path: n = 65536 rows
    over 'model' = 4, 1000 permutations (core.distributed)."""
    from repro.engine import registry
    from repro.kernels.permanova_sw import ops
    n, rows = 65536, 16384
    tuning = dict(registry.get("pallas_matmul").tuning, perm_block=64)

    def fn(m2_rows, g, w):
        return ops.sw_matmul_rows_partial(m2_rows, rows, g, w,
                                          interpret=False, **tuning)

    with _mxu_passes() as passes:
        txt = _compile_text(fn, _spec(one_chip, (rows, n), jnp.float32),
                            _spec(one_chip, (P_TOTAL, n), jnp.int32),
                            _spec(one_chip, (N_GROUPS,), jnp.float32))
        assert passes() == 3
    calls = _custom_calls(txt)
    assert len(calls) == 1 and _kernels("sw_roofline").match(calls[0])
    assert calls[0].startswith("%sw_matmul_rows_partial")


def test_distributed_program_four_chip_width(four_chips, monkeypatch):
    """The four-chip matrix cell's whole test as one program
    (core.distributed._program): D of n = 65536 rows over 'model' = 4,
    17 groups, 1000 permutations on pallas_matmul's row slab. One kernel
    per chip under the name sw_rows_roofline reads, one all-reduce under
    the `dist.psum` scope, the label sorts under `engine.labels` and no
    gather there, and no chip holds more than 10 GB: its rows of D, D^2
    of them and the labels."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed
    from repro.engine import scheduler
    from repro.kernels import common
    # this process's backend is the CPU: compile the kernel for the chip
    monkeypatch.setattr(common, "interpret_mode", lambda interpret=None: False)
    n = 65536
    rows = NamedSharding(four_chips, P("model", None))
    rep = NamedSharding(four_chips, P())
    with _mxu_passes() as passes:
        compiled = distributed._program.lower(
            _spec(rows, (n, n), jnp.float32), _spec(rep, (n,), jnp.int32),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep),
            mesh=four_chips, impl="pallas_matmul", n_groups=CELL_GROUPS,
            n_total=1000, perm_block=64).compile()
        assert passes() == 3
    ins = _instructions(compiled.as_text())
    calls = _custom_calls(compiled.as_text())
    assert len(calls) == 1 and _kernels("sw_rows_roofline").match(calls[0])
    reduces = [i for i in ins if i[3].startswith("all-reduce")]
    assert reduces and any(distributed.PSUM in i[4] for i in reduces)
    sorts = [i for i in ins if i[3] == "sort"]
    assert sorts and all(scheduler.LABELS in i[4] for i in sorts)
    assert not [i for i in ins if scheduler.LABELS in i[4]
                and i[4].endswith("/gather")]
    mem = compiled.memory_analysis()
    shard = 4 * n * n // 4                 # a chip's rows of D, 4.29 GB
    assert shard <= mem.argument_size_in_bytes < shard + 1e6
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10e9


@pytest.mark.parametrize("metric", ["braycurtis", "jaccard"])
def test_fused_megakernel_planned_tiles(one_chip, metric):
    from repro.kernels.fused_sw import ops
    from repro.pipeline import registry
    tuning = dict(registry.get_fused(f"{metric}.fusedk.pallas").tuning)
    n, d, p = 25145, 1024, 1000

    def fn(x, g, w):
        return ops.fused_sw_rows(x, x, g, g, w, 0, metric=metric,
                                 interpret=False, **tuning)

    txt = _compile_text(fn, _spec(one_chip, (n, d), jnp.float32),
                        _spec(one_chip, (p, n), jnp.int32),
                        _spec(one_chip, (N_GROUPS,), jnp.float32))
    calls = _custom_calls(txt)
    assert calls and all(_kernels("fusedk_roofline").match(c) and
                         c.startswith("%fused_sw_rows.") for c in calls)


def test_fused_design_megakernel_planned_tiles(one_chip):
    from repro.kernels.fused_sw import ops
    from repro.pipeline import registry
    tuning = dict(registry.get_fused("braycurtis.fusedk.pallas").tuning)
    n, d, p, k = 4096, 256, 64, 5

    def fn(x, v):
        return ops.fused_sw_rows_cols(x, x, v, v, 0, metric="braycurtis",
                                      interpret=False, **tuning)

    txt = _compile_text(fn, _spec(one_chip, (n, d), jnp.float32),
                        _spec(one_chip, (p, n, k), jnp.float32))
    calls = _custom_calls(txt)
    assert calls and all(_kernels("fusedk_roofline").match(c) and
                         c.startswith("%fused_sw_rows_cols") for c in calls)


@pytest.mark.parametrize("metric,packed", [("braycurtis", 0),
                                           ("jaccard", 1)])
def test_distance_kernel_planned_tiles(one_chip, metric, packed):
    from repro.kernels.distance import ops
    from repro.pipeline import registry
    tuning = {k: v for k, v in
              registry.get(f"{metric}.pallas").tuning.items()
              if k != "packed"}

    def fn(x):
        return ops.pairwise_distance_rows(x[:512], x, metric=metric,
                                          packed=packed, interpret=False,
                                          **tuning)

    txt = _compile_text(fn, _spec(one_chip, (4096, 1024), jnp.float32))
    calls = _custom_calls(txt)
    assert calls and all(c.startswith("%pairwise_distance") for c in calls)
    assert not any(_kernels(m).match(c) for c in calls
                   for m in ("sw_roofline", "fusedk_roofline"))


CELL_N, CELL_CHUNK = 25145, 2668


def test_step_program_at_the_paper_cell(one_chip):
    """The streaming s_W step of the paper cell's plan (pallas_matmul,
    17 groups, 2668-permutation chunks over n = 25145): one kernel under
    the name the roofline reader matches, the label layer's two sorts
    under the `engine.labels` scope and no gather of the chunk's labels
    (they ride the sorts as payload), and the same ops as the step built
    without the scope."""
    from repro.core import permutations
    from repro.engine import registry, scheduler
    from repro.kernels.permanova_sw import ops
    fn = ops.make_sw_fn("matmul", interpret=False,
                        **registry.get("pallas_matmul").tuning)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    args = (_spec(one_chip, (CELL_N, CELL_N), jnp.float32),
            _spec(one_chip, (CELL_N,), jnp.int32),
            _spec(one_chip, (CELL_GROUPS,), jnp.float32), key,
            _spec(one_chip, (), jnp.int32))
    static = dict(fn=fn, chunk=CELL_CHUNK, identity_first=True)

    @functools.partial(jax.jit,
                       static_argnames=("fn", "chunk", "identity_first"))
    def _step(mat2, grouping, inv_gs, key, lo, *, fn, chunk,
              identity_first):
        gperms = permutations.permutation_batch_dyn(
            key, grouping, lo, chunk, identity_first=identity_first)
        return fn(mat2, gperms, inv_gs)

    scoped = _instructions(
        scheduler._step.lower(*args, **static).compile().as_text())
    plain = _instructions(_step.lower(*args, **static).compile().as_text())

    calls = [name for line, name, _, _, _ in scoped
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and _kernels("sw_roofline").match(calls[0])

    labels = scheduler.LABELS
    sorts = [i for i in scoped if i[3] == "sort"]
    gathers = [i for i in scoped if i[4].endswith("/gather")
               and _elements(i[2]) == CELL_CHUNK * CELL_N
               and i[2].startswith("s32")]
    assert len(sorts) == 2 and not gathers
    assert all(labels in i[4] for i in sorts)
    assert not any(labels in i[4] for i in scoped if i[1] in calls)
    assert [i[0] for i in scoped] == [i[0] for i in plain]
