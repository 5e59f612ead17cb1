"""Streaming distance construction + fused distance→s_W execution.

Four materialization strategies for getting from an (n, d) table to the
squared-distance operand `mat2 = D∘D` the s_W engine consumes:

  dense    build D, hand it to the engine (which squares it) — D and mat2
           are both resident transiently. Cheapest to trace; fine while
           8n² bytes fit.
  stream   produce D row blocks, square + diagonal-mask them ON DEVICE as
           they are emitted, and accumulate into ONE host mat2 buffer —
           the raw distance matrix D is never materialized, and only one
           (n, n) array is SUSTAINED (the device handoff copy is a
           transient 2x; on unified-memory APUs it is the same physical
           pages). Gower marginals (row sums / grand sum) are accumulated
           in the same pass, so s_T and the centered form come free.
  fused    never materialize (n, n) at all: each mat2 row block feeds the
           streaming permutation scheduler's chunks directly (row-partial
           s_W in the one-hot matmul form), with labels regenerated on
           device per chunk by the same global-index key folding the
           engine scheduler uses. Peak residency is one (row_block, n)
           slab + one (chunk, n) label block, independent of n.

  fused-kernel
           the single-pass form of `fused`: distance construction and the
           s_W contraction execute inside ONE program, so the D² slab is
           not round-tripped through HBM between two dispatches and the
           sweep pays no per-cell host sync. Two implementations behind
           the same driver (`fused_kernel_sw`): the Pallas megakernel
           (kernels.fused_sw — D² tiles live only in VMEM) and a one-jit
           XLA scan-of-scans (`fused_sw_onepass`) for backends without a
           kernel path. `fused_sw_sharded` runs the same dataflow over a
           device mesh: row slabs shard the 'model' axis, permutations
           shard the remaining axes, partials psum-reduced — mirroring
           core.distributed, but without ever building the matrix.

The fused partial is the Gower-centered trace statistic in disguise:
s_W over row blocks is exactly the blockwise trace form of Anderson's
centered inner-product matrix, so consuming mat2 blocks as produced IS
streaming into the centering — no second pass over the matrix.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs as _obs
from repro.core import distance as _dist
from repro.core import fstat, permutations

Array = jax.Array


class GowerStats(NamedTuple):
    """Marginals of mat2 accumulated during the streaming pass."""
    row_sums: np.ndarray   # (n,) float64 — sum_j mat2[i, j]
    total: float           # sum_ij mat2[i, j]
    n: int

    @property
    def s_t(self) -> float:
        """s_T = sum_{i<j} d²/n = total / 2 / n (zero diagonal)."""
        return self.total / 2.0 / self.n


def gower_center(mat2: Array, stats: Optional[GowerStats] = None) -> Array:
    """Gower-centered matrix G = -1/2 (mat2 - rowmean - colmean + grandmean).

    PERMANOVA's s_T/s_W are trace forms over G; the engine consumes mat2
    directly, but ordination-style consumers (PCoA) want G itself."""
    n = mat2.shape[0]
    if stats is None:
        rs = jnp.sum(mat2, axis=1)
        total = jnp.sum(rs)
    else:
        rs = jnp.asarray(stats.row_sums, mat2.dtype)
        total = stats.total
    rm = rs[:, None] / n
    cm = rs[None, :] / n
    return -0.5 * (mat2 - rm - cm + total / (n * n))


# ---------------------------------------------------------------------------
# Row-block producer: one jitted step serves every block of the sweep.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("rows_fn", "block", "n"))
def _mat2_rows_step(xprep_pad, xprep, lo, *, rows_fn, block, n):
    """mat2 rows for GLOBAL rows [lo, lo+block): distance slab, squared,
    with pad rows and the exact diagonal zeroed. `lo` is traced, so one
    compiled program serves every block."""
    d = xprep_pad.shape[1]
    xb = jax.lax.dynamic_slice(xprep_pad, (lo, 0), (block, d))
    drows = rows_fn(xb, xprep)                       # (block, n)
    row_ids = lo + jnp.arange(block)
    valid = (row_ids < n)[:, None] & (row_ids[:, None]
                                      != jnp.arange(n)[None, :])
    return jnp.where(valid, drows * drows, 0.0)


def _pad_rows(xprep: Array, block: int):
    n = xprep.shape[0]
    pad = (-n) % block
    if pad:
        return jnp.pad(xprep, ((0, pad), (0, 0))), n + pad
    return xprep, n


def mat2_row_blocks(xprep: Array, rows_fn: Callable, *, block: int):
    """Yield (lo, mat2_rows) device slabs covering rows [0, n) in order.

    The last slab is block-sized with zeroed pad rows; consumers slice
    [:n - lo] or rely on the zero contract."""
    n = int(xprep.shape[0])
    block = int(min(block, n))
    xpad, n_pad = _pad_rows(xprep, block)
    for lo in range(0, n_pad, block):
        yield lo, _mat2_rows_step(xpad, xprep, jnp.int32(lo),
                                  rows_fn=rows_fn, block=block, n=n)


def build_mat2_streaming(xprep: Array, rows_fn: Callable, *, block: int):
    """mat2 via the streaming producer: ONE (n, n) buffer, filled blockwise.

    D itself is never materialized — each row slab is squared and masked on
    device, then written into the single host-side mat2 buffer. Returns
    (mat2 float32 ndarray, GowerStats accumulated in the same pass). The
    caller should release this buffer once it is handed to the device
    (pipeline's stream bridge does) so only one (n, n) array is sustained.
    """
    n = int(xprep.shape[0])
    mat2 = np.empty((n, n), np.float32)
    row_sums = np.zeros((n,), np.float64)
    for lo, slab in mat2_row_blocks(xprep, rows_fn, block=block):
        with _obs.span("stream.mat2_block", {"lo": lo}):
            hi = min(lo + slab.shape[0], n)
            # np.asarray is the device sync for this slab — inside the span
            rows = np.asarray(slab[: hi - lo])
            mat2[lo:hi] = rows
            row_sums[lo:hi] = rows.sum(axis=1, dtype=np.float64)
    _obs.metrics.inc("pipeline.mat2_bytes_built", 4.0 * n * n)
    return mat2, GowerStats(row_sums=row_sums, total=float(row_sums.sum()),
                            n=n)


# ---------------------------------------------------------------------------
# Fused distance → s_W: mat2 row blocks feed permutation chunks directly.
# ---------------------------------------------------------------------------

class FusedStats(NamedTuple):
    """Execution evidence: how the fused sweep actually ran."""
    n_total: int
    chunk: int
    n_chunks: int
    row_block: int
    n_row_blocks: int
    peak_slab_bytes: int     # (row_block, n) mat2 slab — the live matrix
    peak_label_bytes: int    # (chunk, n) labels


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block", "n", "n_groups"))
def _fused_sw_step(m2rows, grouping, strata, inv_gs, key, lo_r, lo_p, *,
                   chunk, block, n, n_groups):
    """Row-partial s_W (fstat's matmul-form contraction) for permutation
    indices [lo_p, lo_p+chunk), over mat2 rows [lo_r, lo_r+block).

    Labels are regenerated on device by global-index key folding (identical
    to the engine scheduler), so every (row block × perm chunk) cell of the
    sweep is independent and the results sum exactly to the full statistic.
    `strata=None` is the free generator — byte-identical to the pre-design
    sweep (None traces a distinct program); an array restricts draws within
    blocks. Pad rows carry zeroed mat2 rows, so their (arbitrary) labels
    contribute nothing; the row-label slice comes from a zero-padded label
    block so the slice window never clamps out of alignment."""
    if strata is None:
        g = permutations.permutation_batch_dyn(key, grouping, lo_p, chunk)
    else:
        g = permutations.strata_label_batch_dyn(key, grouping, strata,
                                                lo_p, chunk)
    e = fstat.onehot_perm_factors(g, n_groups, m2rows.dtype)  # (P, n, G)
    e_pad = jnp.pad(e, ((0, 0), (0, (-n) % block), (0, 0)))
    e_rows = jax.lax.dynamic_slice(e_pad, (0, lo_r, 0),
                                   (chunk, block, n_groups))
    return fstat.sw_matmul_contract(m2rows, e, e_rows, inv_gs)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block", "n", "k_cols",
                                    "groups"))
def _fused_sw_step_cols(m2rows, basis, strata, key, lo_r, lo_p, *,
                        chunk, block, n, k_cols, groups=()):
    """Dense-design cousin of _fused_sw_step: strata-restricted index
    permutations gather basis rows; the per-column contraction returns a
    (chunk, K) partial over this row slab. `groups` (static, from
    fstat.sparse_col_groups) switches to the block-sparse gather form —
    the dropped terms are structural zeros (equal to f32 rounding)."""
    perms = permutations.strata_permutation_batch_dyn(key, strata, lo_p,
                                                      chunk)
    v = fstat.basis_perm_factors(basis, perms)               # (P, n, K)
    v_pad = jnp.pad(v, ((0, 0), (0, (-n) % block), (0, 0)))
    v_rows = jax.lax.dynamic_slice(v_pad, (0, lo_r, 0),
                                   (chunk, block, k_cols))
    if groups:
        return fstat.sw_cols_contract_sparse(m2rows, v, v_rows, groups)
    return fstat.sw_cols_contract(m2rows, v, v_rows)


def fused_sw(xprep: Array, rows_fn: Callable, grouping: Array,
             inv_gs: Array, key: jax.Array, n_total: int, *,
             row_block: int, chunk: int,
             strata: Optional[Array] = None,
             progress: Optional[Callable[[int, int], None]] = None):
    """s_W for permutation indices [0, n_total) without ever holding the
    (n, n) matrix: outer loop over mat2 row slabs (each built once), inner
    loop over permutation chunks consuming the live slab.

    Returns (s_w float64 ndarray (n_total,), s_t float, FusedStats).
    """
    n = int(xprep.shape[0])
    n_groups = int(inv_gs.shape[0])
    row_block = int(min(row_block, n))
    chunk = int(max(1, min(chunk, n_total)))
    grouping = jnp.asarray(grouping, jnp.int32)
    out = np.zeros((n_total,), np.float64)
    s_t_sum = 0.0
    n_row_blocks = 0
    for lo_r, slab in mat2_row_blocks(xprep, rows_fn, block=row_block):
        with _obs.span("fused.row_slab", {"lo": lo_r}):
            n_row_blocks += 1
            s_t_sum += float(jnp.sum(slab))  # s_T marginal, once per slab
            for lo_p in range(0, n_total, chunk):
                sw = _fused_sw_step(
                    slab, grouping, strata, inv_gs, key, jnp.int32(lo_r),
                    jnp.int32(lo_p), chunk=chunk, block=slab.shape[0], n=n,
                    n_groups=n_groups)
                hi = min(lo_p + chunk, n_total)
                out[lo_p:hi] += np.asarray(sw[: hi - lo_p], np.float64)
        if progress is not None:
            progress(min(lo_r + row_block, n), n)
    stats = FusedStats(
        n_total=n_total, chunk=chunk, n_chunks=-(-n_total // chunk),
        row_block=row_block, n_row_blocks=n_row_blocks,
        peak_slab_bytes=4 * row_block * n,
        peak_label_bytes=4 * chunk * n)
    _obs.metrics.inc("fused.row_slabs", n_row_blocks)
    _obs.metrics.inc("fused.chunk_steps", n_row_blocks * stats.n_chunks)
    return out, s_t_sum / 2.0 / n, stats


def fused_sw_design(xprep: Array, rows_fn: Callable, design, key: jax.Array,
                    n_total: int, *, row_block: int, chunk: int,
                    block_sparse: bool = True,
                    progress: Optional[Callable[[int, int], None]] = None):
    """The fused bridge for DENSE designs: per-column quadratic forms
    accumulated over mat2 row slabs, nothing (n, n)-shaped ever resident.
    Strata-blocked bases (the common multi-study / repeated-measures
    designs) contract block-sparsely: each column group only touches its
    strata's sample columns — exact, since the skipped terms are zeros.

    Returns (s_cols float64 ndarray (n_total, K), s_t float, FusedStats).
    """
    n = int(xprep.shape[0])
    k = design.k_cols
    basis = design.basis
    strata = (design.strata if design.strata is not None
              else jnp.zeros((n,), jnp.int32))
    groups = ()
    if block_sparse and design.strata is not None:
        groups = fstat.sparse_col_groups(basis, design.strata)
        if len(groups) <= 1:   # dense support: gather buys nothing
            groups = ()
    row_block = int(min(row_block, n))
    chunk = int(max(1, min(chunk, n_total)))
    out = np.zeros((n_total, k), np.float64)
    s_t_sum = 0.0
    n_row_blocks = 0
    for lo_r, slab in mat2_row_blocks(xprep, rows_fn, block=row_block):
        with _obs.span("fused.row_slab", {"lo": lo_r, "cols": k}):
            n_row_blocks += 1
            s_t_sum += float(jnp.sum(slab))
            for lo_p in range(0, n_total, chunk):
                sc = _fused_sw_step_cols(
                    slab, basis, strata, key, jnp.int32(lo_r),
                    jnp.int32(lo_p), chunk=chunk, block=slab.shape[0], n=n,
                    k_cols=k, groups=groups)
                hi = min(lo_p + chunk, n_total)
                out[lo_p:hi] += np.asarray(sc[: hi - lo_p], np.float64)
        if progress is not None:
            progress(min(lo_r + row_block, n), n)
    stats = FusedStats(
        n_total=n_total, chunk=chunk, n_chunks=-(-n_total // chunk),
        row_block=row_block, n_row_blocks=n_row_blocks,
        peak_slab_bytes=4 * row_block * n,
        peak_label_bytes=4 * chunk * n * (k + 1))
    _obs.metrics.inc("fused.row_slabs", n_row_blocks)
    _obs.metrics.inc("fused.chunk_steps", n_row_blocks * stats.n_chunks)
    return out, s_t_sum / 2.0 / n, stats


# ---------------------------------------------------------------------------
# Fused-kernel: single-pass distance → s_W (tentpole of the megakernel PR).
# ---------------------------------------------------------------------------

class FusedKernelStats(NamedTuple):
    """Execution evidence: how the single-pass sweep actually ran."""
    impl: str                # 'pallas' | 'xla'
    n_total: int
    chunk: int
    n_chunks: int
    row_block: int
    peak_slab_bytes: int     # (row_block, n) D² residency (0 for pallas:
                             # tiles never leave VMEM)
    peak_label_bytes: int    # (chunk, n) labels + (chunk, n, G) one-hot


def _sweep_rows_perms(x_rows_pad, x_full, grouping, inv_gs, key,
                      row_offset, perm_lo, *, rows_fn, block, chunk,
                      n_chunks, n, n_rows_pad, n_groups, strata=None):
    """Fully-traced fused sweep over LOCAL rows × a permutation range.

    x_rows_pad: (n_local, d) prepared features, n_local a multiple of
                `block`; the slab's global rows start at `row_offset`
                (traced — one program serves every shard/offset).
    perm_lo:    first global permutation index (traced); the sweep covers
                [perm_lo, perm_lo + n_chunks*chunk).
    strata:     None = free label permutations (the pre-design program);
                an (n,) array restricts draws within blocks.
    Returns (s_w (n_chunks*chunk,) f32 partial over these rows,
             row_sums (n_local,) f32). Scan over row blocks outside, scan
    over permutation chunks inside — each D² block is built once and
    consumed immediately; nothing (n, n)-shaped ever exists.
    """
    n_local = x_rows_pad.shape[0]
    d_feat = x_rows_pad.shape[1]
    chunk_los = perm_lo + jnp.arange(n_chunks) * chunk

    def slab_body(carry, lo_r):
        sw_acc, rs = carry
        xb = jax.lax.dynamic_slice(x_rows_pad, (lo_r, 0), (block, d_feat))
        drows = rows_fn(xb, x_full)                      # (block, n)
        gids = row_offset + lo_r + jnp.arange(block)
        valid = (gids < n)[:, None] & (gids[:, None]
                                       != jnp.arange(n)[None, :])
        m2 = jnp.where(valid, drows * drows, 0.0)

        def chunk_body(_, lo_p):
            if strata is None:
                g = permutations.permutation_batch_dyn(key, grouping, lo_p,
                                                       chunk)
            else:
                g = permutations.strata_label_batch_dyn(
                    key, grouping, strata, lo_p, chunk)
            e = fstat.onehot_perm_factors(g, n_groups, m2.dtype)
            e_pad = jnp.pad(e, ((0, 0), (0, n_rows_pad - n), (0, 0)))
            e_rows = jax.lax.dynamic_slice(
                e_pad, (0, row_offset + lo_r, 0), (chunk, block, n_groups))
            return None, fstat.sw_matmul_contract(m2, e, e_rows, inv_gs)

        _, sws = jax.lax.scan(chunk_body, None, chunk_los)
        rs = jax.lax.dynamic_update_slice(rs, jnp.sum(m2, axis=1), (lo_r,))
        return (sw_acc + sws.reshape(-1), rs), None

    init = (jnp.zeros((n_chunks * chunk,), jnp.float32),
            jnp.zeros((n_local,), jnp.float32))
    (s_w, rs), _ = jax.lax.scan(slab_body, init,
                                jnp.arange(n_local // block) * block)
    return s_w, rs


def _sweep_rows_perms_design(x_rows_pad, x_full, basis, strata, key,
                             row_offset, perm_lo, *, rows_fn, block, chunk,
                             n_chunks, n, n_rows_pad, k_cols):
    """_sweep_rows_perms for DENSE designs: the chunk scan draws
    strata-restricted index permutations, gathers basis rows, and runs the
    per-column contraction. Returns (s_cols (n_chunks*chunk, K) f32,
    row_sums (n_local,) f32)."""
    n_local = x_rows_pad.shape[0]
    d_feat = x_rows_pad.shape[1]
    chunk_los = perm_lo + jnp.arange(n_chunks) * chunk

    def slab_body(carry, lo_r):
        sc_acc, rs = carry
        xb = jax.lax.dynamic_slice(x_rows_pad, (lo_r, 0), (block, d_feat))
        drows = rows_fn(xb, x_full)
        gids = row_offset + lo_r + jnp.arange(block)
        valid = (gids < n)[:, None] & (gids[:, None]
                                       != jnp.arange(n)[None, :])
        m2 = jnp.where(valid, drows * drows, 0.0)

        def chunk_body(_, lo_p):
            perms = permutations.strata_permutation_batch_dyn(
                key, strata, lo_p, chunk)
            v = fstat.basis_perm_factors(basis, perms)   # (chunk, n, K)
            v_pad = jnp.pad(v, ((0, 0), (0, n_rows_pad - n), (0, 0)))
            v_rows = jax.lax.dynamic_slice(
                v_pad, (0, row_offset + lo_r, 0), (chunk, block, k_cols))
            return None, fstat.sw_cols_contract(m2, v, v_rows)

        _, scs = jax.lax.scan(chunk_body, None, chunk_los)
        rs = jax.lax.dynamic_update_slice(rs, jnp.sum(m2, axis=1), (lo_r,))
        return (sc_acc + scs.reshape(-1, k_cols), rs), None

    init = (jnp.zeros((n_chunks * chunk, k_cols), jnp.float32),
            jnp.zeros((n_local,), jnp.float32))
    (s_cols, rs), _ = jax.lax.scan(slab_body, init,
                                   jnp.arange(n_local // block) * block)
    return s_cols, rs


@functools.partial(jax.jit, static_argnames=(
    "rows_fn", "block", "chunk", "n_chunks", "n", "n_rows_pad", "n_groups"))
def _onepass_step(x_rows_pad, x_full, grouping, strata, inv_gs, key, *,
                  rows_fn, block, chunk, n_chunks, n, n_rows_pad, n_groups):
    return _sweep_rows_perms(
        x_rows_pad, x_full, grouping, inv_gs, key, jnp.int32(0),
        jnp.int32(0), rows_fn=rows_fn, block=block, chunk=chunk,
        n_chunks=n_chunks, n=n, n_rows_pad=n_rows_pad, n_groups=n_groups,
        strata=strata)


@functools.partial(jax.jit, static_argnames=(
    "rows_fn", "block", "chunk", "n_chunks", "n", "n_rows_pad", "k_cols"))
def _onepass_step_design(x_rows_pad, x_full, basis, strata, key, *,
                         rows_fn, block, chunk, n_chunks, n, n_rows_pad,
                         k_cols):
    return _sweep_rows_perms_design(
        x_rows_pad, x_full, basis, strata, key, jnp.int32(0),
        jnp.int32(0), rows_fn=rows_fn, block=block, chunk=chunk,
        n_chunks=n_chunks, n=n, n_rows_pad=n_rows_pad, k_cols=k_cols)


def fused_sw_onepass(xprep: Array, rows_fn: Callable, grouping: Array,
                     inv_gs: Array, key: jax.Array, n_total: int, *,
                     row_block: int, chunk: int,
                     strata: Optional[Array] = None):
    """The fused sweep as ONE jitted program (the off-TPU megakernel form).

    Same math as `fused_sw`, but the (row block × perm chunk) double loop
    runs as a scan-of-scans inside a single dispatch: no per-cell host
    round trips, no host-side accumulation buffers, and XLA keeps each D²
    block live exactly as long as its contractions need it.
    """
    n = int(xprep.shape[0])
    n_groups = int(inv_gs.shape[0])
    block = int(min(row_block, n))
    chunk = int(max(1, min(chunk, n_total)))
    n_chunks = -(-n_total // chunk)
    xpad, n_pad = _pad_rows(xprep, block)
    s_w, rs = _onepass_step(
        xpad, xprep, jnp.asarray(grouping, jnp.int32), strata, inv_gs, key,
        rows_fn=rows_fn, block=block, chunk=chunk, n_chunks=n_chunks, n=n,
        n_rows_pad=n_pad, n_groups=n_groups)
    s_t = float(jnp.sum(rs)) / 2.0 / n
    _obs.metrics.inc("engine.perm_chunks", n_chunks)
    stats = FusedKernelStats(
        impl="xla", n_total=n_total, chunk=chunk, n_chunks=n_chunks,
        row_block=block, peak_slab_bytes=4 * block * n,
        peak_label_bytes=4 * chunk * n * (n_groups + 1))
    return np.asarray(s_w[:n_total], np.float64), s_t, stats


def fused_sw_onepass_design(xprep: Array, rows_fn: Callable, design,
                            key: jax.Array, n_total: int, *,
                            row_block: int, chunk: int):
    """fused_sw_onepass for DENSE designs: one jitted scan-of-scans, the
    per-column contraction inside. Returns (s_cols (n_total, K) f64,
    s_t, FusedKernelStats)."""
    n = int(xprep.shape[0])
    k = design.k_cols
    strata = (design.strata if design.strata is not None
              else jnp.zeros((n,), jnp.int32))
    block = int(min(row_block, n))
    chunk = int(max(1, min(chunk, n_total)))
    n_chunks = -(-n_total // chunk)
    xpad, n_pad = _pad_rows(xprep, block)
    s_cols, rs = _onepass_step_design(
        xpad, xprep, design.basis, strata, key, rows_fn=rows_fn,
        block=block, chunk=chunk, n_chunks=n_chunks, n=n, n_rows_pad=n_pad,
        k_cols=k)
    s_t = float(jnp.sum(rs)) / 2.0 / n
    _obs.metrics.inc("engine.perm_chunks", n_chunks)
    stats = FusedKernelStats(
        impl="xla", n_total=n_total, chunk=chunk, n_chunks=n_chunks,
        row_block=block, peak_slab_bytes=4 * block * n,
        peak_label_bytes=4 * chunk * n * (k + 1))
    return np.asarray(s_cols[:n_total], np.float64), s_t, stats


def _precision_roundtrip(xprep: Array, metric: str,
                         tuning: Optional[dict]) -> Array:
    """Value parity for the XLA one-pass path: quantize the feature table
    ONCE up front per the precision knobs, round-tripped back to f32 (XLA
    streams f32 regardless — the knobs buy traffic only on the kernel
    path), so both fused impls contract identical quantized features."""
    t = dict(tuning or {})
    if int(t.get("feat_packed", 0)):
        if metric != "jaccard":
            raise ValueError("feat_packed=1 requires the jaccard kernel "
                             f"body (got metric={metric!r})")
        return (jnp.asarray(xprep) > 0).astype(jnp.float32)
    if int(t.get("feat_fp8", 0)):
        return _dist.fp8_roundtrip(
            xprep, _dist.fp8_metric_scale(xprep, metric))
    if int(t.get("feat_bf16", 0)):
        return jnp.asarray(xprep, jnp.float32).astype(
            jnp.bfloat16).astype(jnp.float32)
    return xprep


def _fp8_scale_kwargs(xprep: Array, metric: str, tuning: dict) -> dict:
    """Per-metric fp8 calibration, computed ONCE per study before the chunk
    loop (re-deriving it per chunk would re-reduce the whole table)."""
    if int(tuning.get("feat_fp8", 0)):
        return {"feat_scale": _dist.fp8_metric_scale(xprep, metric)}
    return {}


_labels_step = jax.jit(permutations.permutation_batch_dyn,
                       static_argnames=("chunk", "identity_first"))
_strata_labels_step = jax.jit(permutations.strata_label_batch_dyn,
                              static_argnames=("chunk", "identity_first"))
_strata_perms_step = jax.jit(permutations.strata_permutation_batch_dyn,
                             static_argnames=("chunk", "identity_first"))


def fused_sw_megakernel(xprep: Array, grouping: Array, inv_gs: Array,
                        key: jax.Array, n_total: int, *, kernel_metric: str,
                        chunk: int, tuning: Optional[dict] = None,
                        interpret: Optional[bool] = None,
                        strata: Optional[Array] = None,
                        progress: Optional[Callable[[int, int], None]] = None):
    """The fused sweep through the Pallas megakernel (kernels.fused_sw).

    One kernel launch per permutation chunk covers ALL row/col tiles and
    every perm block of the chunk: D² tiles are built from feature slabs
    and contracted in VMEM, so the only HBM traffic per chunk is the
    feature table and the (chunk, n) labels — the distance matrix never
    exists at any scope wider than one (tile_r, tile_c) scratch buffer.
    Labels are generated outside the kernel, so strata-restricted draws
    slot straight in.
    """
    from repro.kernels.fused_sw import ops as _fops  # deferred: pallas
    n = int(xprep.shape[0])
    chunk = int(max(1, min(chunk, n_total)))
    tuning = dict(tuning or {})
    scale_kwargs = _fp8_scale_kwargs(xprep, kernel_metric, tuning)
    grouping = jnp.asarray(grouping, jnp.int32)
    out = np.zeros((n_total,), np.float64)
    rowsums = None
    n_chunks = 0
    for lo in range(0, n_total, chunk):
        with _obs.span("fusedk.chunk", {"lo": lo}):
            if strata is None:
                g = _labels_step(key, grouping, jnp.int32(lo), chunk=chunk)
            else:
                g = _strata_labels_step(key, grouping, strata, jnp.int32(lo),
                                        chunk=chunk)
            sw, rs = _fops.fused_sw_rows(
                xprep, xprep, g, g, inv_gs, 0, metric=kernel_metric,
                interpret=interpret, **scale_kwargs, **tuning)
            hi = min(lo + chunk, n_total)
            out[lo:hi] = np.asarray(sw[: hi - lo], np.float64)
        if rowsums is None:
            rowsums = np.asarray(rs, np.float64)
        n_chunks += 1
        if progress is not None:
            progress(hi, n_total)
    s_t = float(rowsums.sum()) / 2.0 / n
    tr = int(tuning.get("tile_r", 128))
    tc = int(tuning.get("tile_c", 128))
    stats = FusedKernelStats(
        impl="pallas", n_total=n_total, chunk=chunk, n_chunks=n_chunks,
        row_block=tr, peak_slab_bytes=16 * tr * tc,  # 4 VMEM scratch tiles
        peak_label_bytes=4 * chunk * n)
    _obs.metrics.inc("engine.perm_chunks", n_chunks)
    return out, s_t, stats


def fused_sw_megakernel_design(xprep: Array, design, key: jax.Array,
                               n_total: int, *, kernel_metric: str,
                               chunk: int, tuning: Optional[dict] = None,
                               interpret: Optional[bool] = None,
                               progress: Optional[Callable[[int, int],
                                                           None]] = None):
    """The megakernel sweep for DENSE designs: permuted basis blocks
    replace the in-kernel one-hot build (the MXU contraction consumes
    hat-matrix factor columns directly); per-column partials come back
    per chunk. D² residency is unchanged — VMEM tiles only."""
    from repro.kernels.fused_sw import ops as _fops  # deferred: pallas
    n = int(xprep.shape[0])
    k = design.k_cols
    basis = design.basis
    strata = (design.strata if design.strata is not None
              else jnp.zeros((n,), jnp.int32))
    chunk = int(max(1, min(chunk, n_total)))
    tuning = dict(tuning or {})
    scale_kwargs = _fp8_scale_kwargs(xprep, kernel_metric, tuning)
    out = np.zeros((n_total, k), np.float64)
    rowsums = None
    n_chunks = 0
    for lo in range(0, n_total, chunk):
        with _obs.span("fusedk.chunk", {"lo": lo, "cols": k}):
            perms = _strata_perms_step(key, strata, jnp.int32(lo),
                                       chunk=chunk)
            v = fstat.basis_perm_factors(basis, perms)
            sc, rs = _fops.fused_sw_rows_cols(
                xprep, xprep, v, v, 0, metric=kernel_metric,
                interpret=interpret, **scale_kwargs, **tuning)
            hi = min(lo + chunk, n_total)
            out[lo:hi] = np.asarray(sc[: hi - lo], np.float64)
        if rowsums is None:
            rowsums = np.asarray(rs, np.float64)
        n_chunks += 1
        if progress is not None:
            progress(hi, n_total)
    s_t = float(rowsums.sum()) / 2.0 / n
    tr = int(tuning.get("tile_r", 128))
    tc = int(tuning.get("tile_c", 128))
    stats = FusedKernelStats(
        impl="pallas", n_total=n_total, chunk=chunk, n_chunks=n_chunks,
        row_block=tr, peak_slab_bytes=16 * tr * tc,
        peak_label_bytes=4 * chunk * n * (k + 1))
    _obs.metrics.inc("engine.perm_chunks", n_chunks)
    return out, s_t, stats


def fused_kernel_sw(xprep: Array, rows_fn: Callable, grouping: Array,
                    inv_gs: Array, key: jax.Array, n_total: int, *,
                    impl: str, kernel_metric: str, row_block: int,
                    chunk: int, tuning: Optional[dict] = None,
                    interpret: Optional[bool] = None,
                    strata: Optional[Array] = None,
                    progress: Optional[Callable[[int, int], None]] = None):
    """Dispatch the single-pass fused sweep to the planned implementation.

    impl: 'pallas' (the megakernel; interpreted on the CPU) or 'xla' (the
    one-jit scan-of-scans). Both return (s_w (n_total,) float64, s_t,
    FusedKernelStats) with identical statistics for a fixed key.
    """
    if impl == "pallas":
        return fused_sw_megakernel(
            xprep, grouping, inv_gs, key, n_total,
            kernel_metric=kernel_metric, chunk=chunk, tuning=tuning,
            interpret=interpret, strata=strata, progress=progress)
    if impl == "xla":
        return fused_sw_onepass(
            _precision_roundtrip(xprep, kernel_metric, tuning), rows_fn,
            grouping, inv_gs, key, n_total,
            row_block=row_block, chunk=chunk, strata=strata)
    raise ValueError(f"unknown fused-kernel impl {impl!r}; "
                     "expected 'pallas' or 'xla'")


def fused_kernel_sw_design(xprep: Array, rows_fn: Callable, design,
                           key: jax.Array, n_total: int, *,
                           impl: str, kernel_metric: str, row_block: int,
                           chunk: int, tuning: Optional[dict] = None,
                           interpret: Optional[bool] = None):
    """fused_kernel_sw for DENSE designs: both impls return
    (s_cols (n_total, K) float64, s_t, FusedKernelStats)."""
    if impl == "pallas":
        return fused_sw_megakernel_design(
            xprep, design, key, n_total, kernel_metric=kernel_metric,
            chunk=chunk, tuning=tuning, interpret=interpret)
    if impl == "xla":
        return fused_sw_onepass_design(
            _precision_roundtrip(xprep, kernel_metric, tuning), rows_fn,
            design, key, n_total, row_block=row_block, chunk=chunk)
    raise ValueError(f"unknown fused-kernel impl {impl!r}; "
                     "expected 'pallas' or 'xla'")


# ---------------------------------------------------------------------------
# Out-of-core fused sweeps: the feature table never exists in memory. Disk
# slabs arrive through the async prefetcher (slab k+1 staged while slab k's
# tiles contract), each (slab_rows, n) m2 row slab is assembled from
# (slab, slab) distance tiles, and the UNCHANGED fused steps consume it —
# so the statistic matches the in-memory bridges at the same slab
# boundaries up to the f32 rounding of the tile-wise distance step.
# ---------------------------------------------------------------------------

class OocStats(NamedTuple):
    """Execution evidence: how the out-of-core sweep actually ran."""
    n_total: int
    chunk: int
    n_chunks: int
    slab_rows: int
    n_slabs: int
    disk_bytes_read: int     # actual bytes through the prefetcher
    stall_s: float           # consumer time blocked on slab I/O
    sweep_s: float           # whole-sweep wall clock


@functools.partial(jax.jit,
                   static_argnames=("rows_fn", "prep_fn", "block", "n"))
def _ooc_m2_tile(x_rows, x_cols, lo_r, lo_c, *, rows_fn, prep_fn, block, n):
    """One (block, block) m2 tile from two RAW feature slabs: metric prep
    (row-local for every registered metric) then distance rows, squared,
    with pad rows/cols and the exact diagonal zeroed by GLOBAL ids.
    lo_r/lo_c are traced, so one compiled program serves the whole sweep
    — zero warm retraces regardless of slab count."""
    drows = rows_fn(prep_fn(x_rows), prep_fn(x_cols))
    gi = lo_r + jnp.arange(block)
    gj = lo_c + jnp.arange(block)
    valid = (gi < n)[:, None] & (gj < n)[None, :] \
        & (gi[:, None] != gj[None, :])
    return jnp.where(valid, drows * drows, 0.0)


@functools.partial(jax.jit, static_argnames=("chunk", "n_chunks", "block",
                                             "n", "n_groups"))
def _ooc_rowslab_onepass(m2rows, grouping, strata, inv_gs, key, lo_r, *,
                         chunk, n_chunks, block, n, n_groups):
    """ONE dispatch covering every permutation chunk of one assembled m2
    row slab (the fused-kernel form out of core: scan inside, so the slab
    is read from HBM once per chunk without per-chunk host syncs)."""
    chunk_los = jnp.arange(n_chunks) * chunk

    def chunk_body(_, lo_p):
        if strata is None:
            g = permutations.permutation_batch_dyn(key, grouping, lo_p,
                                                   chunk)
        else:
            g = permutations.strata_label_batch_dyn(key, grouping, strata,
                                                    lo_p, chunk)
        e = fstat.onehot_perm_factors(g, n_groups, m2rows.dtype)
        e_pad = jnp.pad(e, ((0, 0), (0, (-n) % block), (0, 0)))
        e_rows = jax.lax.dynamic_slice(e_pad, (0, lo_r, 0),
                                       (chunk, block, n_groups))
        return None, fstat.sw_matmul_contract(m2rows, e, e_rows, inv_gs)

    _, sws = jax.lax.scan(chunk_body, None, chunk_los)
    return sws.reshape(-1)


@functools.partial(jax.jit, static_argnames=("chunk", "n_chunks", "block",
                                             "n", "k_cols", "groups"))
def _ooc_rowslab_onepass_cols(m2rows, basis, strata, key, lo_r, *,
                              chunk, n_chunks, block, n, k_cols, groups=()):
    """_ooc_rowslab_onepass for DENSE designs (per-column contraction)."""
    chunk_los = jnp.arange(n_chunks) * chunk

    def chunk_body(_, lo_p):
        perms = permutations.strata_permutation_batch_dyn(key, strata, lo_p,
                                                          chunk)
        v = fstat.basis_perm_factors(basis, perms)
        v_pad = jnp.pad(v, ((0, 0), (0, (-n) % block), (0, 0)))
        v_rows = jax.lax.dynamic_slice(v_pad, (0, lo_r, 0),
                                       (chunk, block, k_cols))
        if groups:
            return None, fstat.sw_cols_contract_sparse(m2rows, v, v_rows,
                                                       groups)
        return None, fstat.sw_cols_contract(m2rows, v, v_rows)

    _, scs = jax.lax.scan(chunk_body, None, chunk_los)
    return scs.reshape(-1, k_cols)


def _ooc_sweep(cache, rows_fn, prep_fn, consume, *, prefetch_depth=2):
    """Drive one full OOC pass: for each row slab r, prefetch slab r then
    the whole column stream, assemble the (slab_rows, n) m2 row slab from
    tiles, and hand it to `consume(lo_r, m2rows)`. The prefetcher thread
    is torn down even when consume raises mid-sweep. Returns the drained
    prefetcher (for its I/O counters)."""
    from repro.data import slabcache as _slabcache
    n, block, n_slabs = cache.n, cache.slab_rows, cache.n_slabs
    pf = _slabcache.SlabPrefetcher(cache, _slabcache.ooc_schedule(n_slabs),
                                   depth=prefetch_depth, pad_to=block)
    try:
        it = iter(pf)
        for r in range(n_slabs):
            _, x_rows = next(it)
            lo_r = r * block
            with _obs.span("ooc.row_slab", {"lo": lo_r}):
                tiles = []
                for c in range(n_slabs):
                    _, x_cols = next(it)
                    tiles.append(_ooc_m2_tile(
                        x_rows, x_cols, jnp.int32(lo_r),
                        jnp.int32(c * block), rows_fn=rows_fn,
                        prep_fn=prep_fn, block=block, n=n))
                m2 = jnp.concatenate(tiles, axis=1)[:, :n]
                consume(lo_r, m2)
    finally:
        pf.close()
    return pf


def fused_sw_ooc(cache, rows_fn: Callable, prep_fn: Callable,
                 grouping: Array, inv_gs: Array, key: jax.Array,
                 n_total: int, *, chunk: int,
                 strata: Optional[Array] = None, onepass: bool = False,
                 prefetch_depth: int = 2):
    """s_W with the feature table on DISK: slab-cache streaming into the
    fused contraction. onepass=False reuses `_fused_sw_step` verbatim (the
    'fused' bridge out of core — partial sums in the same accumulation
    order as `fused_sw` at row_block == slab_rows);
    onepass=True runs one dispatch per row slab (the 'fused-kernel' form).

    Returns (s_w float64 (n_total,), s_t float, OocStats).
    """
    n = cache.n
    block = cache.slab_rows
    n_groups = int(inv_gs.shape[0])
    chunk = int(max(1, min(chunk, n_total)))
    n_chunks = -(-n_total // chunk)
    grouping = jnp.asarray(grouping, jnp.int32)
    out = np.zeros((n_total,), np.float64)
    s_t_sum = 0.0

    def consume(lo_r, m2):
        nonlocal s_t_sum
        s_t_sum += float(jnp.sum(m2))
        if onepass:
            sws = _ooc_rowslab_onepass(
                m2, grouping, strata, inv_gs, key, jnp.int32(lo_r),
                chunk=chunk, n_chunks=n_chunks, block=block, n=n,
                n_groups=n_groups)
            out[:] += np.asarray(sws[:n_total], np.float64)
        else:
            for lo_p in range(0, n_total, chunk):
                sw = _fused_sw_step(
                    m2, grouping, strata, inv_gs, key, jnp.int32(lo_r),
                    jnp.int32(lo_p), chunk=chunk, block=block, n=n,
                    n_groups=n_groups)
                hi = min(lo_p + chunk, n_total)
                out[lo_p:hi] += np.asarray(sw[: hi - lo_p], np.float64)

    t0 = time.perf_counter()
    pf = _ooc_sweep(cache, rows_fn, prep_fn, consume,
                    prefetch_depth=prefetch_depth)
    sweep_s = time.perf_counter() - t0
    _obs.metrics.inc("fused.row_slabs", cache.n_slabs)
    _obs.metrics.inc("fused.chunk_steps", cache.n_slabs * n_chunks)
    stats = OocStats(
        n_total=n_total, chunk=chunk, n_chunks=n_chunks, slab_rows=block,
        n_slabs=cache.n_slabs, disk_bytes_read=pf.bytes_read,
        stall_s=pf.stall_s, sweep_s=sweep_s)
    return out, s_t_sum / 2.0 / n, stats


def fused_sw_ooc_design(cache, rows_fn: Callable, prep_fn: Callable,
                        design, key: jax.Array, n_total: int, *,
                        chunk: int, block_sparse: bool = True,
                        onepass: bool = False, prefetch_depth: int = 2):
    """fused_sw_ooc for DENSE designs (covariates / strata / weights):
    the per-column contraction over disk-streamed m2 row slabs. Returns
    (s_cols float64 (n_total, K), s_t float, OocStats)."""
    n = cache.n
    block = cache.slab_rows
    k = design.k_cols
    basis = design.basis
    strata = (design.strata if design.strata is not None
              else jnp.zeros((n,), jnp.int32))
    groups = ()
    if block_sparse and design.strata is not None:
        groups = fstat.sparse_col_groups(basis, design.strata)
        if len(groups) <= 1:
            groups = ()
    chunk = int(max(1, min(chunk, n_total)))
    n_chunks = -(-n_total // chunk)
    out = np.zeros((n_total, k), np.float64)
    s_t_sum = 0.0

    def consume(lo_r, m2):
        nonlocal s_t_sum
        s_t_sum += float(jnp.sum(m2))
        if onepass:
            scs = _ooc_rowslab_onepass_cols(
                m2, basis, strata, key, jnp.int32(lo_r), chunk=chunk,
                n_chunks=n_chunks, block=block, n=n, k_cols=k,
                groups=groups)
            out[:] += np.asarray(scs[:n_total], np.float64)
        else:
            for lo_p in range(0, n_total, chunk):
                sc = _fused_sw_step_cols(
                    m2, basis, strata, key, jnp.int32(lo_r),
                    jnp.int32(lo_p), chunk=chunk, block=block, n=n,
                    k_cols=k, groups=groups)
                hi = min(lo_p + chunk, n_total)
                out[lo_p:hi] += np.asarray(sc[: hi - lo_p], np.float64)

    t0 = time.perf_counter()
    pf = _ooc_sweep(cache, rows_fn, prep_fn, consume,
                    prefetch_depth=prefetch_depth)
    sweep_s = time.perf_counter() - t0
    _obs.metrics.inc("fused.row_slabs", cache.n_slabs)
    _obs.metrics.inc("fused.chunk_steps", cache.n_slabs * n_chunks)
    stats = OocStats(
        n_total=n_total, chunk=chunk, n_chunks=n_chunks, slab_rows=block,
        n_slabs=cache.n_slabs, disk_bytes_read=pf.bytes_read,
        stall_s=pf.stall_s, sweep_s=sweep_s)
    return out, s_t_sum / 2.0 / n, stats


# ---------------------------------------------------------------------------
# Multi-device fused sharding: row slabs over 'model', perms over the rest.
# ---------------------------------------------------------------------------

def fused_sw_sharded(mesh, xprep: Array, rows_fn: Callable, grouping: Array,
                     inv_gs: Array, key: jax.Array, n_total: int, *,
                     row_block: int, chunk: int):
    """The fused sweep over a (…, 'data', 'model') device mesh.

    Mirrors core.distributed's mapping without ever building the matrix:
    'model' shards the feature-table ROWS (each device sweeps only its row
    slab's D² blocks — peak per-device residency (row_block, n)), the
    remaining axes shard the PERMUTATION range (labels regenerated
    shard-locally by global-index key folding). One psum over 'model'
    reconstructs each permutation's statistic exactly.

    The host drives one shard_map dispatch per permutation WINDOW of
    perm_ways * chunk global indices; inside it each shard generates its
    (chunk, n) label block with a single key-folding call. (Folding inside
    a lax.scan over traced chunk offsets has miscompiled under shard_map —
    the folded offsets silently collapsing to the first shard's when the
    labels feed a matmul — so the chunk loop stays at the host, exactly
    like the megakernel driver.)

    Returns (s_w (n_total,) float64, s_t float, FusedKernelStats).
    """
    from repro.core import distributed as _distrib  # deferred: jax mesh
    n, d_feat = (int(s) for s in xprep.shape)
    n_groups = int(inv_gs.shape[0])
    model_ways = mesh.shape["model"]
    perm_axes = _distrib._perm_axes(mesh)
    perm_ways = 1
    for a in perm_axes:
        perm_ways *= mesh.shape[a]

    rows_per_shard = -(-n // model_ways)
    block = int(min(row_block, rows_per_shard))
    rows_per_shard = -(-rows_per_shard // block) * block
    n_rows_pad = rows_per_shard * model_ways
    xpad = jnp.pad(xprep, ((0, n_rows_pad - n), (0, 0)))

    chunk_local = int(max(1, min(chunk, -(-n_total // perm_ways))))
    window = chunk_local * perm_ways
    grouping = jnp.asarray(grouping, jnp.int32)

    def body(x_rows, x_full, grp, igs, k, wlo):
        row_offset = jax.lax.axis_index("model") * rows_per_shard
        pidx = jnp.zeros((), jnp.int32)
        for a in perm_axes:  # row-major linearization over perm axes
            pidx = pidx * mesh.shape[a] + jax.lax.axis_index(a)
        lo = wlo[0] + pidx * chunk_local
        g = permutations.permutation_batch_dyn(k, grp, lo, chunk_local)
        e = fstat.onehot_perm_factors(g, n_groups, jnp.float32)
        e_pad = jnp.pad(e, ((0, 0), (0, n_rows_pad - n), (0, 0)))

        def slab_body(carry, lo_r):
            sw_acc, rs = carry
            xb = jax.lax.dynamic_slice(x_rows, (lo_r, 0), (block, d_feat))
            drows = rows_fn(xb, x_full)
            gids = row_offset + lo_r + jnp.arange(block)
            valid = (gids < n)[:, None] & (gids[:, None]
                                           != jnp.arange(n)[None, :])
            m2 = jnp.where(valid, drows * drows, 0.0)
            e_rows = jax.lax.dynamic_slice(
                e_pad, (0, row_offset + lo_r, 0),
                (chunk_local, block, n_groups))
            rs = jax.lax.dynamic_update_slice(rs, jnp.sum(m2, axis=1),
                                              (lo_r,))
            return (sw_acc + fstat.sw_matmul_contract(m2, e, e_rows, igs),
                    rs), None

        # the carries vary across shards (partials per row slab and perm
        # range); say so, as shard_map's type check requires of a scan
        init = (jax.lax.pcast(jnp.zeros((chunk_local,), jnp.float32),
                              tuple(mesh.axis_names), to="varying"),
                jax.lax.pcast(jnp.zeros((rows_per_shard,), jnp.float32),
                              ("model",), to="varying"))
        (s_w, rs), _ = jax.lax.scan(
            slab_body, init, jnp.arange(rows_per_shard // block) * block)
        return jax.lax.psum(s_w, axis_name="model"), rs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None), P(), P(), P(), P(), P()),
        out_specs=(P(perm_axes), P("model")))
    out = np.zeros((n_total,), np.float64)
    rowsums = None
    n_windows = 0
    for wlo in range(0, n_total, window):
        with _obs.span("fusedk.window", {"lo": wlo}):
            s_w, rs = fn(xpad, xprep, grouping, inv_gs, key,
                         jnp.full((1,), wlo, jnp.int32))
            hi = min(wlo + window, n_total)
            out[wlo:hi] = np.asarray(s_w[: hi - wlo], np.float64)
        if rowsums is None:
            rowsums = np.asarray(rs, np.float64)
        n_windows += 1
    s_t = float(rowsums[:n].sum()) / 2.0 / n
    stats = FusedKernelStats(
        impl="xla", n_total=n_total, chunk=chunk_local,
        n_chunks=n_windows * perm_ways, row_block=block,
        peak_slab_bytes=4 * block * n,
        peak_label_bytes=4 * chunk_local * n * (n_groups + 1))
    _obs.metrics.inc("engine.perm_chunks", stats.n_chunks)
    return out, s_t, stats
