"""Distributed PERMANOVA over a (pod, data, model) device mesh.

Mapping (DESIGN.md section 4):
  * 'data' (and 'pod' when present) axes shard the PERMUTATION dimension —
    the paper's "most obvious parallelization target". Work is generated
    shard-locally by folding the PRNG key with GLOBAL permutation indices,
    so no (n_perms, n) label tensor ever crosses the network and recovery /
    re-dispatch is idempotent.
  * 'model' shards the distance-matrix ROWS (a 100k^2 fp32 matrix is 40 GB
    and must be split to fit HBM). Each shard computes a partial s_W over
    its row block; one psum over 'model' reconstructs the statistic.

The only inter-pod traffic is the final (n_perms,) gather — DCN-friendly.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs as _obs
from repro.core import distance as _distance
from repro.core import fstat, permutations, permanova as _permanova
from repro.obs import metrics as _metrics

Array = jax.Array

PSUM = "dist.psum"          # device scope of the s_W psum over 'model'


def pad_to_multiple(x: Array, multiple: int, axis: int = 0):
    """Zero-pad axis to a multiple (matrix rows for even model sharding)."""
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _perm_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def _my_perm_range(mesh: Mesh, n_perms_padded: int):
    """(lo, hi) of this shard's global permutation indices (traced)."""
    axes = _perm_axes(mesh)
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    idx = jnp.zeros((), jnp.int32)
    for a in axes:  # row-major linearization over permutation axes
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    per = n_perms_padded // total
    return idx * per, per


def resolve_impl(impl: str, n: int, n_perms: int, n_groups: int) -> str:
    """Map an impl request ('auto' or a registry name) to a concrete
    registry impl via the engine planner."""
    from repro import engine  # deferred: engine imports core modules
    pinned = None if impl == "auto" else impl
    return engine.plan(n, n_perms, n_groups, impl=pinned).impl


def make_sw_shard_fn(mesh: Mesh, *, impl: str = "matmul",
                     n_groups: int, identity_first: bool = True,
                     perm_block: int = 64):
    """Build the shard-local body: generate my permutations, compute my
    row-partial s_W, psum over 'model'. Returns f(mat2_rows, grouping, inv_gs,
    key, n_perms_padded) -> (local_perms,) s_W.

    The row-sharded partial is looked up in the engine registry: the exact
    impl's companion when it has one, else the nearest family member
    (tiled, pallas_brute -> brute rows; pallas_permblock -> matmul rows).
    The label generation runs under the engine's `engine.labels` scope and
    the psum under `dist.psum`, so a device trace can tell them apart."""
    from repro import engine  # deferred: engine imports core modules
    from repro.engine.scheduler import LABELS
    partial_fn = engine.get_sharded(impl)
    tuning_key = ("block" if partial_fn is fstat.sw_rows_partial
                  else "perm_block")

    def shard_body(mat2_rows, grouping, inv_gs, key, n_perms_padded):
        n_local = mat2_rows.shape[0]
        row_offset = jax.lax.axis_index("model") * n_local
        with jax.named_scope(LABELS):
            lo, per = _my_perm_range(mesh, n_perms_padded)
            gperms = permutations.permutation_batch_dyn(
                key, grouping, lo, per, identity_first=identity_first)
        part = partial_fn(mat2_rows, row_offset, gperms, inv_gs,
                          **{tuning_key: perm_block})
        with jax.named_scope(PSUM):
            return jax.lax.psum(part, axis_name="model")

    return shard_body


def _perm_ways(mesh: Mesh) -> int:
    ways = 1
    for a in _perm_axes(mesh):
        ways *= mesh.shape[a]
    return ways


def _sw_shard_map(mesh: Mesh, impl: str, n_groups: int, n_perms_padded: int,
                  perm_block: int):
    """The s_W shard_map over row-padded mat2 (not jitted by itself)."""
    body = make_sw_shard_fn(mesh, impl=impl, n_groups=n_groups,
                            perm_block=perm_block)
    return jax.shard_map(
        functools.partial(body, n_perms_padded=n_perms_padded),
        mesh=mesh,
        in_specs=(P("model", None), P(), P(), P()),
        out_specs=P(_perm_axes(mesh)),
        # a Pallas partial's kernel body mixes its (varying) blocks with
        # unvarying iotas, which the varying-axes check rejects
        check_vma=False,
    )


def _padded_perms(mesh: Mesh, n_perms: int) -> int:
    return n_perms + ((-n_perms) % _perm_ways(mesh))


def sw_distributed(mesh: Mesh, mat2: Array, grouping: Array, inv_gs: Array,
                   key: jax.Array, n_perms: int, *, impl: str = "matmul",
                   perm_block: int = 64) -> Array:
    """Full-batch distributed s_W, run eagerly. Returns (n_perms_padded,)
    with the global permutation order; entry 0 is the observed statistic."""
    n_groups = int(inv_gs.shape[0])
    impl = resolve_impl(impl, mat2.shape[1], n_perms, n_groups)
    mat2p, _ = pad_to_multiple(mat2, mesh.shape["model"], axis=0)
    sw = _sw_shard_map(mesh, impl, n_groups, _padded_perms(mesh, n_perms),
                       perm_block)
    return sw(mat2p, grouping, inv_gs, key)


@functools.partial(jax.jit, static_argnames=("mesh", "impl", "n_groups",
                                             "n_total", "perm_block"))
def _program(dm, grouping, key, *, mesh: Mesh, impl: str, n_groups: int,
             n_total: int, perm_block: int):
    """One whole test as one jitted program: mat2 = D∘D, the s_W
    shard_map and its psum, s_T, F of every permutation and p. jit keys
    the static arguments, shapes and dtypes, so a warm call compiles
    nothing. Takes D already row-sharded (n_pad, n) over 'model' with zero
    pad rows."""
    n_perms_padded = _padded_perms(mesh, n_total)
    model_ways = mesh.shape["model"]
    n = dm.shape[1]
    # gauges of the traced shapes (set at trace time, as sw.tile_share)
    _metrics.gauge_set("dist.model_ways", model_ways)
    _metrics.gauge_set("dist.rows_per_chip", dm.shape[0] // model_ways)
    _metrics.gauge_set("dist.perms_per_chip",
                       n_perms_padded // _perm_ways(mesh))
    mat2 = dm * dm
    inv_gs = permutations.inv_group_sizes(grouping, n_groups)
    sw = _sw_shard_map(mesh, impl, n_groups, n_perms_padded, perm_block)
    s_w_all = sw(mat2, grouping, inv_gs, key)
    s_t = jnp.sum(mat2) / 2.0 / n          # pad rows are zero
    f_all = _permanova.f_from_sw(s_w_all, s_t, n, n_groups)
    return s_w_all, s_t, f_all, _permanova.p_value_from_null(f_all)


def _rows_on_mesh(mesh: Mesh, dm) -> Array:
    """D as the program takes it: rows padded with zeros to a multiple of
    the 'model' width and sharded P('model', None). A D that is already so
    (distance_matrix_sharded's) is returned as is."""
    sharding = NamedSharding(mesh, P("model", None))
    model_ways = mesh.shape["model"]
    if (isinstance(dm, jax.Array) and dm.shape[0] % model_ways == 0
            and dm.sharding.is_equivalent_to(sharding, dm.ndim)):
        return dm
    dm, _ = pad_to_multiple(jnp.asarray(dm), model_ways, axis=0)
    return jax.device_put(dm, sharding)


def distance_matrix_sharded(mesh: Mesh, x: Array, metric: str = "braycurtis",
                            *, block: int = 256) -> Array:
    """Distance matrix born row-sharded over 'model'.

    Shard k builds rows [k * n_local, (k + 1) * n_local) against all n
    samples in row blocks, so no device ever holds more than its own rows
    (a D larger than one chip's memory never exists in one place). Rows
    are padded to a multiple of the 'model' width: the result is
    (n_pad, n), sharded P('model', None), with pad rows and the diagonal
    exactly zero — the operand permanova_distributed takes as is."""
    mdef = _distance.ROW_METRICS[metric]
    xp = mdef.prepare(jnp.asarray(x, jnp.float32))
    n, d = xp.shape
    model_ways = mesh.shape["model"]
    n_local = -(-n // model_ways)
    block = min(block, n_local)
    n_local = -(-n_local // block) * block
    x_rows = jnp.pad(xp, ((0, n_local * model_ways - n), (0, 0)))

    def body(rows, x_full):
        def step(_, xb):
            return None, mdef.rows(xb, x_full)
        _, dr = jax.lax.scan(step, None, rows.reshape(-1, block, d))
        gi = (jax.lax.axis_index("model") * n_local
              + jnp.arange(n_local))[:, None]
        gj = jnp.arange(n)[None, :]
        return jnp.where((gi < n) & (gi != gj), dr.reshape(n_local, n), 0.0)

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(P("model", None), P()),
                               out_specs=P("model", None)))
    return fn(x_rows, xp)


def permanova_distributed(mesh: Mesh, dm: Array, grouping: Array, *,
                          n_perms: int = 999, key: Optional[jax.Array] = None,
                          n_groups: Optional[int] = None,
                          impl: str = "auto", perm_block: int = 64):
    """Distributed full PERMANOVA. Semantics match core.permanova.permanova
    (up to permutation count padding, which only adds extra null draws).

    dm is (n, n), or (n_pad, n) with zero pad rows as built by
    distance_matrix_sharded (already row-sharded over 'model'). A warm
    call at the same shapes runs one compiled program (`_program`).

    Label normalization routes through the design shim like every other
    entry point; only plain single-factor designs run here (strata /
    covariate / weighted designs shard over the STUDY axis via
    engine.permanova_many(mesh=...) instead of matrix rows).

    Host phases, each an obs span: `engine.dist.sw` (design, plan and the
    program's dispatch) and `engine.dist.finalize` (the host waits for
    s_T, F and p)."""
    from repro.core import design as _design  # deferred: light cycle guard
    if key is None:
        key = jax.random.key(0)
    with _obs.span("engine.dist.sw"):
        design = _design.Design.from_labels(grouping, n_groups=n_groups)
        if not design.is_plain_labels:
            raise ValueError(
                "permanova_distributed shards matrix rows for plain "
                "single-factor designs; use engine.permanova_many(mesh=...) "
                "for strata/covariate/weighted designs")
        dm = _rows_on_mesh(mesh, dm)
        n = dm.shape[1]
        n_groups = design.n_groups
        n_total = n_perms + 1
        impl = resolve_impl(impl, n, n_total, n_groups)
        out = _program(dm, design.grouping, key, mesh=mesh, impl=impl,
                       n_groups=n_groups, n_total=n_total,
                       perm_block=perm_block)
    with _obs.span("engine.dist.finalize"):
        s_w_all, s_t, f_all, p_value = jax.block_until_ready(out)
    return _permanova.PermanovaResult(
        f_stat=f_all[0],
        p_value=p_value,
        s_t=s_t,
        s_w=s_w_all[0],
        f_perms=f_all,
        n_objects=n,
        n_groups=n_groups,
        n_perms=int(f_all.shape[0]) - 1,
        method=f"permanova_distributed[{impl}]",
        plan=(f"{impl}[perm_block={perm_block}] rows over "
              f"model={mesh.shape['model']}, permutations over "
              f"{_perm_ways(mesh)} way(s); one compiled program"),
    )
