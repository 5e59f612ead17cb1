"""Permutation engine for the PERMANOVA permutation test.

Generates batches of permuted grouping vectors. Group sizes are invariant
under label permutation, so `inv_group_sizes` is computed once from the
observed grouping. Permutation 0 is ALWAYS the identity (the observed
grouping), matching the scikit-bio convention where the observed statistic
joins the null distribution denominator.

The generator is deliberately splittable/stateless (one fold of the PRNG key
per permutation index) so that:
  * distributed shards generate their own permutation ranges without
    communication (shard p-range [lo, hi) folds keys lo..hi-1), and
  * straggler re-dispatch / elastic re-meshing re-generates identical
    permutations on a different host (idempotent recovery — DESIGN.md section 4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def group_sizes(grouping: Array, n_groups: int) -> Array:
    """(n_groups,) counts of each label value in the observed grouping."""
    return jnp.bincount(grouping, length=n_groups)


def inv_group_sizes(grouping: Array, n_groups: int) -> Array:
    sizes = group_sizes(grouping, n_groups).astype(jnp.float32)
    return jnp.where(sizes > 0, 1.0 / jnp.maximum(sizes, 1.0), 0.0)


def permute_grouping(key: jax.Array, grouping: Array) -> Array:
    """One random relabeling: grouping composed with a random permutation.

    The labels ride the shuffle's stable sorts as their payload, so no
    index permutation is built and gathered through. The draw is
    `grouping[jax.random.permutation(key, n)]` bit for bit: the shuffle's
    random sort keys do not depend on what they carry.
    """
    return jax.random.permutation(key, grouping)


def permutation_batch(key: jax.Array, grouping: Array, lo: int, hi: int,
                      *, identity_first: bool = True) -> Array:
    """Grouping vectors for permutation indices [lo, hi).

    Index 0 is the identity when identity_first. Key folding is by GLOBAL
    permutation index, so any shard holding any index range produces the
    same labels as a single-host run.
    """
    return permutation_batch_dyn(key, grouping, lo, hi - lo,
                                 identity_first=identity_first)


def permutation_batch_dyn(key: jax.Array, grouping: Array, lo: Array,
                          chunk: int, *, identity_first: bool = True) -> Array:
    """permutation_batch with a TRACED start index.

    Same key-folding-by-global-index semantics, but `lo` may be a traced
    scalar, so one jitted program serves every chunk of a streaming sweep
    (the scheduler re-invokes it with lo = 0, chunk, 2*chunk, ... without
    retracing). `chunk` must be static.
    """
    idx = lo + jnp.arange(chunk)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    perms = jax.vmap(lambda k: permute_grouping(k, grouping))(keys)
    if identity_first:
        perms = jnp.where((idx == 0)[:, None], grouping[None, :], perms)
    return perms


def permutation_batch_host(key: jax.Array, grouping, n_perms: int):
    """Convenience full-batch generator (host-side, small studies)."""
    return permutation_batch(key, jnp.asarray(grouping), 0, n_perms)


# ---------------------------------------------------------------------------
# Strata-restricted permutations (design subsystem).
#
# Restricted permutation tests (vegan's `strata=`) shuffle samples only
# WITHIN blocks — sites, batches, repeated-measure subjects — so the null
# respects the blocking structure. The generators below ride the exact
# global-index key-folding contract of the free generators above: any shard
# holding any index range reproduces the same draws as a single host.
# ---------------------------------------------------------------------------

def strata_permutation(key: jax.Array, strata: Array) -> Array:
    """One uniform permutation restricted within strata blocks.

    Returns an INDEX permutation perm (n,) int32 with strata[perm[i]] ==
    strata[i] for every i, uniformly distributed over all such
    permutations. Construction: two stable argsorts group positions by
    stratum — once in a uniformly-random within-block order, once in the
    original order — and matching them up block-by-block yields a uniform
    within-block bijection (no float-keyed lexsort, so no tie hazards).
    A constant strata vector gives an unrestricted uniform permutation
    (a distinct stream from jax.random.permutation's — documented where
    the dense design path draws from it)."""
    n = strata.shape[0]
    u = jax.random.uniform(key, (n,))
    a = jnp.argsort(u)                              # random position order
    a = a[jnp.argsort(strata[a], stable=True)]      # by stratum, random within
    b = jnp.argsort(strata, stable=True)            # by stratum, original order
    return jnp.zeros((n,), jnp.int32).at[b].set(a.astype(jnp.int32))


def strata_permutation_batch_dyn(key: jax.Array, strata: Array, lo: Array,
                                 chunk: int, *,
                                 identity_first: bool = True) -> Array:
    """(chunk, n) strata-restricted INDEX permutations for global
    permutation indices [lo, lo+chunk). Key folding is by GLOBAL index
    (`lo` may be traced), so sharded sweeps are bit-identical to
    single-host ones. Index 0 is the identity when identity_first."""
    n = strata.shape[0]
    idx = lo + jnp.arange(chunk)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    perms = jax.vmap(lambda k: strata_permutation(k, strata))(keys)
    if identity_first:
        eye = jnp.arange(n, dtype=jnp.int32)
        perms = jnp.where((idx == 0)[:, None], eye[None, :], perms)
    return perms


def strata_permutation_batch(key: jax.Array, strata: Array, lo: int,
                             hi: int, *, identity_first: bool = True) -> Array:
    """Strata-restricted index permutations for indices [lo, hi)."""
    return strata_permutation_batch_dyn(key, strata, lo, hi - lo,
                                        identity_first=identity_first)


def strata_label_batch_dyn(key: jax.Array, grouping: Array, strata: Array,
                           lo: Array, chunk: int, *,
                           identity_first: bool = True) -> Array:
    """Permuted LABEL vectors under strata restriction — the labels-mode
    generator for `strata=` designs: grouping composed with the index
    permutations, so every label-based s_W impl consumes it unchanged."""
    perms = strata_permutation_batch_dyn(key, strata, lo, chunk,
                                         identity_first=identity_first)
    return grouping[perms]


def masked_strata(strata: Array, n_valid: Array) -> Array:
    """Move the pad suffix [n_valid, n) into its own sentinel stratum so
    padded ragged studies permute pads only among themselves (pad rows
    carry zero design rows, so they contribute exactly nothing). The
    sentinel is max(strata)+1 — strata labels are arbitrary ints, so a
    fixed sentinel could collide with a real block and leak valid samples
    onto zero-basis pad slots. A None-equivalent free permutation is the
    all-zeros strata vector."""
    n = strata.shape[0]
    return jnp.where(jnp.arange(n) < n_valid, strata, jnp.max(strata) + 1)


# ---------------------------------------------------------------------------
# Masked permutations: ragged studies padded to a common length.
# ---------------------------------------------------------------------------

def masked_permute_grouping(key: jax.Array, grouping: Array,
                            n_valid: Array) -> Array:
    """One random relabeling of the VALID PREFIX [0, n_valid) only.

    Pad entries (the suffix, carrying a sentinel group) stay in place, so
    the permutation never mixes pad labels into valid positions — group
    sizes over the valid samples are invariant, exactly as an unpadded
    permutation. Draw: uniform keys on the prefix, +inf on the pad, one
    stable sort carrying the labels as its payload — positions
    [0, n_valid) receive a uniform random permutation of themselves, the
    pad suffix maps to itself in order. The labels are
    `grouping[jnp.argsort(u)]` bit for bit, with no gather. `n_valid` may
    be traced (one program serves every study of a ragged batch).
    """
    n = grouping.shape[0]
    u = jax.random.uniform(key, (n,))
    u = jnp.where(jnp.arange(n) < n_valid, u, jnp.inf)
    return jax.lax.sort_key_val(u, grouping)[1]


def masked_permutation_batch_dyn(key: jax.Array, grouping: Array,
                                 n_valid: Array, lo: Array, chunk: int, *,
                                 identity_first: bool = True) -> Array:
    """permutation_batch_dyn for a padded ragged study.

    Same global-index key folding (shard-position independent), but each
    draw permutes only the valid prefix via masked_permute_grouping. NOTE:
    the draws differ from the unpadded jax.random.permutation stream, so
    a ragged study's null is deterministic and independent per study but
    not bit-identical to an unpadded single-study run; the observed
    statistic (index 0, identity labels) IS identical.
    """
    idx = lo + jnp.arange(chunk)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    perms = jax.vmap(
        lambda k: masked_permute_grouping(k, grouping, n_valid))(keys)
    if identity_first:
        perms = jnp.where((idx == 0)[:, None], grouping[None, :], perms)
    return perms
