"""Streaming permutation scheduler.

Executes an n_perms-permutation sweep in fixed-memory chunks. Labels are
regenerated ON DEVICE per chunk by folding the PRNG key with GLOBAL
permutation indices — the same trick core.distributed uses across shards —
so a single-host 100k..1M-permutation run never materializes the
(n_perms, n) label tensor. Peak live label memory is (chunk, n) int32,
independent of n_perms; results accumulate into a host-side float32 buffer
(4 bytes/perm).

One jitted step program serves every chunk (the start index is a traced
scalar), so the sweep compiles once.

Every step program generates its labels under `jax.named_scope(LABELS)`:
the key folding, the shuffle's sorts (which carry the labels as their
payload) and the identity-first select carry `engine.labels` in their
op_name metadata, so a device trace can sum the label layer apart from
the s_W contraction. A scope adds metadata only; the compiled ops are
the same.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core import permutations

Array = jax.Array

LABELS = "engine.labels"    # device scope of the permutation-label layer


class StreamStats(NamedTuple):
    """Execution evidence for tests/telemetry: how the sweep actually ran."""
    n_total: int
    chunk: int
    n_chunks: int
    peak_label_bytes: int   # (chunk, n) int32 — the live label footprint


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step(mat2, grouping, inv_gs, key, lo, *, fn, chunk, identity_first):
    with jax.named_scope(LABELS):
        gperms = permutations.permutation_batch_dyn(
            key, grouping, lo, chunk, identity_first=identity_first)
    return fn(mat2, gperms, inv_gs)


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_strata(mat2, grouping, strata, inv_gs, key, lo, *, fn, chunk,
                 identity_first):
    """The strata-restricted cousin of _step: labels composed with
    within-block index permutations; every label-based impl consumes them
    unchanged. A separate jitted program so the free-permutation path
    stays byte-identical to the pre-design repo."""
    with jax.named_scope(LABELS):
        gperms = permutations.strata_label_batch_dyn(
            key, grouping, strata, lo, chunk, identity_first=identity_first)
    return fn(mat2, gperms, inv_gs)


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_cols(mat2, basis, strata, key, lo, *, fn, chunk, identity_first):
    """Dense-design step: index permutations (strata-restricted; a
    constant strata vector is the free case) gather basis rows, and the
    per-column contraction returns (chunk, K)."""
    from repro.core import fstat
    with jax.named_scope(LABELS):
        perms = permutations.strata_permutation_batch_dyn(
            key, strata, lo, chunk, identity_first=identity_first)
    return fn(mat2, fstat.basis_perm_factors(basis, perms))


# ---------------------------------------------------------------------------
# Serving block programs: masked variants of the chunk steps above.
#
# The always-on server (serve/permanova.py) pads every study up to a SHAPE
# BUCKET so one compiled program serves all requests of that bucket; the
# true sample count rides along as a traced `n_valid` scalar and the
# masked/strata permutation generators keep pad rows inert (PR 4's ragged
# contract). Each step computes s_W (or the per-column statistic) for ONE
# BLOCK of global permutation indices [lo, lo+chunk) — the idempotent unit
# of work the elastic executor dispatches, re-dispatches, and speculates:
# key folding by global index makes a block a pure function of (key, lo),
# so recomputation anywhere is bit-identical.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_masked(mat2, grouping, n_valid, inv_gs, key, lo, *, fn, chunk,
                 identity_first):
    with jax.named_scope(LABELS):
        gperms = permutations.masked_permutation_batch_dyn(
            key, grouping, n_valid, lo, chunk, identity_first=identity_first)
    return fn(mat2, gperms, inv_gs)


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_masked_strata(mat2, grouping, strata, n_valid, inv_gs, key, lo, *,
                        fn, chunk, identity_first):
    with jax.named_scope(LABELS):
        st = permutations.masked_strata(strata, n_valid)
        gperms = permutations.strata_label_batch_dyn(
            key, grouping, st, lo, chunk, identity_first=identity_first)
    return fn(mat2, gperms, inv_gs)


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_masked_cols(mat2, basis, strata, n_valid, key, lo, *, fn, chunk,
                      identity_first):
    from repro.core import fstat
    with jax.named_scope(LABELS):
        st = permutations.masked_strata(strata, n_valid)
        perms = permutations.strata_permutation_batch_dyn(
            key, st, lo, chunk, identity_first=identity_first)
    return fn(mat2, fstat.basis_perm_factors(basis, perms))


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_masked_many(mat2, grouping, n_valid, inv_gs, key, lo, *, fn,
                      chunk, identity_first):
    """Batched-bucket label step: the vmapped cousin of `_step_masked`.

    All leading-S operands are stacked same-bucket studies; `n_valid` is a
    traced (S,) vector so one compiled program serves any mix of true
    sample counts within the bucket. Each study draws its labels from ITS
    OWN key folded by the GLOBAL permutation index, so row s of the
    result is bit-identical to an unbatched `_step_masked` call with that
    study's operands (asserted by the serve batched-vs-serial tests)."""
    def one(m2, g, nv, igs, k):
        with jax.named_scope(LABELS):
            gperms = permutations.masked_permutation_batch_dyn(
                k, g, nv, lo, chunk, identity_first=identity_first)
        return fn(m2, gperms, igs)
    return jax.vmap(one)(mat2, grouping, n_valid, inv_gs, key)


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_masked_strata_many(mat2, grouping, strata, n_valid, inv_gs, key,
                             lo, *, fn, chunk, identity_first):
    def one(m2, g, st, nv, igs, k):
        with jax.named_scope(LABELS):
            stm = permutations.masked_strata(st, nv)
            gperms = permutations.strata_label_batch_dyn(
                k, g, stm, lo, chunk, identity_first=identity_first)
        return fn(m2, gperms, igs)
    return jax.vmap(one)(mat2, grouping, strata, n_valid, inv_gs, key)


@functools.partial(jax.jit, static_argnames=("fn", "chunk", "identity_first"))
def _step_masked_cols_many(mat2, basis, strata, n_valid, key, lo, *, fn,
                           chunk, identity_first):
    from repro.core import fstat

    def one(m2, bs, st, nv, k):
        with jax.named_scope(LABELS):
            stm = permutations.masked_strata(st, nv)
            perms = permutations.strata_permutation_batch_dyn(
                k, stm, lo, chunk, identity_first=identity_first)
        return fn(m2, fstat.basis_perm_factors(bs, perms))
    return jax.vmap(one)(mat2, basis, strata, n_valid, key)


def sw_block_many(mat2, grouping, n_valid, inv_gs, keys, lo: int, *, fn,
                  block: int, strata=None):
    """One label-mode serving block for a BATCH of same-bucket studies:
    (S, block) s_W values for global permutation indices [lo, lo+block)
    across all S studies in one dispatch. Operands carry a leading study
    axis (shardable over the 'data' mesh axis when the caller device_puts
    them with a NamedSharding); `keys` is the (S,) stack of per-study PRNG
    keys, so study s's column is bit-identical to `sw_block` on study s
    alone. Plain batches pass strata=None."""
    if strata is None:
        return _step_masked_many(mat2, grouping, n_valid, inv_gs, keys,
                                 jnp.int32(lo), fn=fn, chunk=block,
                                 identity_first=True)
    return _step_masked_strata_many(mat2, grouping, strata, n_valid, inv_gs,
                                    keys, jnp.int32(lo), fn=fn, chunk=block,
                                    identity_first=True)


def sw_cols_block_many(mat2, basis, strata, n_valid, keys, lo: int, *, fn,
                       block: int):
    """One dense-design serving block for a batch of same-bucket studies:
    (S, block, K) per-column statistics in one dispatch."""
    return _step_masked_cols_many(mat2, basis, strata, n_valid, keys,
                                  jnp.int32(lo), fn=fn, chunk=block,
                                  identity_first=True)


def sw_block(mat2, grouping, n_valid, inv_gs, key, lo: int, *, fn,
             block: int, strata=None):
    """One label-mode serving block: s_W for global permutation indices
    [lo, lo+block) on a (possibly padded) study. Returns a device array
    of length `block`; callers slice the final ragged block themselves.
    Plain requests pass strata=None; the strata-restricted program is a
    separate jitted step so the free path's draws never change."""
    if strata is None:
        return _step_masked(mat2, grouping, n_valid, inv_gs, key,
                            jnp.int32(lo), fn=fn, chunk=block,
                            identity_first=True)
    return _step_masked_strata(mat2, grouping, strata, n_valid, inv_gs, key,
                               jnp.int32(lo), fn=fn, chunk=block,
                               identity_first=True)


def sw_cols_block(mat2, basis, strata, n_valid, key, lo: int, *, fn,
                  block: int):
    """One dense-design serving block: (block, K) per-column statistics
    for global permutation indices [lo, lo+block)."""
    return _step_masked_cols(mat2, basis, strata, n_valid, key,
                             jnp.int32(lo), fn=fn, chunk=block,
                             identity_first=True)


def sw_streaming(mat2: Array, grouping: Array, inv_gs: Array, key: jax.Array,
                 n_total: int, fn: Callable, *, chunk: int,
                 identity_first: bool = True,
                 strata: Optional[Array] = None,
                 progress: Optional[Callable[[int, int], None]] = None):
    """s_W for global permutation indices [0, n_total) in fixed-size chunks.

    fn: batch impl fn(mat2, groupings, inv_gs) -> (P,) (a registry impl
        bound via SwImpl.bound(), or any compatible callable; must be
        jit-traceable).
    strata: optional (n,) int32 block labels — permutations restricted
        within blocks (core.permutations.strata_permutation_batch); None
        is the pre-design free-permutation program, unchanged.
    Returns (s_w float32 ndarray of shape (n_total,), StreamStats).
    Chunk results beyond n_total (last ragged chunk) are computed and
    discarded — identical labels to any other sweep of the same key, since
    folding is by global index.
    """
    n = int(mat2.shape[0])
    chunk = int(max(1, min(chunk, n_total)))
    out = np.empty((n_total,), np.float32)
    n_chunks = 0
    for lo in range(0, n_total, chunk):
        hi = min(lo + chunk, n_total)
        # spans take no attrs here: a disabled span site is one bool check
        with _obs.span("engine.sw_chunk"):
            with _obs.span("engine.dispatch"):
                if strata is None:
                    s = _step(mat2, grouping, inv_gs, key, jnp.int32(lo),
                              fn=fn, chunk=chunk,
                              identity_first=identity_first)
                else:
                    s = _step_strata(mat2, grouping, strata, inv_gs, key,
                                     jnp.int32(lo), fn=fn, chunk=chunk,
                                     identity_first=identity_first)
                s = s[: hi - lo]
            # np.asarray waits for the chunk and copies it back
            with _obs.span("engine.fetch"):
                out[lo:hi] = np.asarray(s)
        n_chunks += 1
        if progress is not None:
            progress(hi, n_total)
    stats = StreamStats(n_total=n_total, chunk=chunk, n_chunks=n_chunks,
                        peak_label_bytes=4 * chunk * n)
    _obs.metrics.inc("engine.perm_chunks", n_chunks)
    return out, stats


@functools.partial(jax.jit, static_argnames=("fn", "n_total",
                                             "identity_first"))
def _batch_step(mat2, grouping, inv_gs, key, *, fn, n_total, identity_first):
    with jax.named_scope(LABELS):
        gperms = permutations.permutation_batch(
            key, grouping, 0, n_total, identity_first=identity_first)
    return fn(mat2, gperms, inv_gs)


@functools.partial(jax.jit, static_argnames=("fn", "n_total",
                                             "identity_first"))
def _batch_step_strata(mat2, grouping, strata, inv_gs, key, *, fn, n_total,
                       identity_first):
    with jax.named_scope(LABELS):
        gperms = permutations.strata_label_batch_dyn(
            key, grouping, strata, jnp.int32(0), n_total,
            identity_first=identity_first)
    return fn(mat2, gperms, inv_gs)


def sw_batch(mat2: Array, grouping: Array, inv_gs: Array, key: jax.Array,
             n_total: int, fn: Callable, *, identity_first: bool = True,
             strata: Optional[Array] = None):
    """One-shot path for small sweeps: materialize all labels, single
    dispatch. Same key semantics as the streaming path.

    The step is one jitted program keyed on the (memoized) impl callable,
    like the streaming `_step`. The previous eager form re-traced any
    scan inside the impl on EVERY call, so a warm serving process paid a
    fresh jaxpr trace per request — the obs retrace counter caught it."""
    with _obs.span("engine.sw_chunk"):
        with _obs.span("engine.dispatch"):
            if strata is None:
                s_w = _batch_step(mat2, grouping, inv_gs, key, fn=fn,
                                  n_total=n_total,
                                  identity_first=identity_first)
            else:
                s_w = _batch_step_strata(
                    mat2, grouping, strata, inv_gs, key, fn=fn,
                    n_total=n_total, identity_first=identity_first)
        # the result stays on the device: wait for it only while tracing
        with _obs.span("engine.fetch"):
            s_w = _obs.maybe_block(s_w)
    stats = StreamStats(n_total=n_total, chunk=n_total, n_chunks=1,
                        peak_label_bytes=4 * n_total * int(mat2.shape[0]))
    _obs.metrics.inc("engine.perm_chunks", 1)
    return s_w, stats


# ---------------------------------------------------------------------------
# Dense-design sweeps: per-column contraction of permuted basis factors.
# ---------------------------------------------------------------------------

def sw_cols_streaming(mat2: Array, basis: Array, strata: Array,
                      key: jax.Array, n_total: int, fn: Callable, *,
                      chunk: int, identity_first: bool = True,
                      progress: Optional[Callable[[int, int], None]] = None):
    """Per-column statistic (n_total, K) in fixed-memory chunks.

    The streamed state is (chunk, n) int32 index permutations plus the
    gathered (chunk, n, K) basis factor (the planner sizes the chunk for
    K columns); results accumulate host-side exactly like sw_streaming.
    `strata` is always an array here — pass zeros(n) for free
    permutations (the dense-mode draws come from the strata generator, a
    distinct deterministic stream from the label path's).
    """
    n = int(mat2.shape[0])
    k = int(basis.shape[1])
    chunk = int(max(1, min(chunk, n_total)))
    out = np.empty((n_total, k), np.float32)
    n_chunks = 0
    for lo in range(0, n_total, chunk):
        hi = min(lo + chunk, n_total)
        with _obs.span("engine.sw_chunk"):
            with _obs.span("engine.dispatch"):
                s = _step_cols(mat2, basis, strata, key, jnp.int32(lo),
                               fn=fn, chunk=chunk,
                               identity_first=identity_first)
                s = s[: hi - lo]
            with _obs.span("engine.fetch"):
                out[lo:hi] = np.asarray(s)
        n_chunks += 1
        if progress is not None:
            progress(hi, n_total)
    stats = StreamStats(n_total=n_total, chunk=chunk, n_chunks=n_chunks,
                        peak_label_bytes=4 * chunk * n * (k + 1))
    _obs.metrics.inc("engine.perm_chunks", n_chunks)
    return out, stats
