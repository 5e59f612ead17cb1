"""Inputs made from `--seed`, on the device, by the benchmark's own code.

Counts follow a gamma-Poisson-like model of amplicon tables: each entry is
present with probability `density`, and a present entry is
1 + floor(scale * Gamma(0.7) * Exp(1)), capped at 4095. A planted effect
multiplies the scale of a tenth of the features in each group. Every count
is a whole number, so the feature sums behind Bray-Curtis and Jaccard are
exact in float32 (at most 1024 features x 4095 < 2**24): the distances
below are the true ones, rounded once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

COUNT_CAP = 4095.0


def seeds(seed: int):
    """(data key, permutation key, numpy Generator) from one --seed of any
    size; the same seed gives the same three."""
    ss = np.random.SeedSequence(int(seed))
    data_word, perm_word = (int(w) for w in ss.generate_state(2))
    return (jax.random.key(data_word), jax.random.key(perm_word),
            np.random.default_rng(ss.spawn(1)[0]))


def test_key(perm_key, t: int):
    """Permutation key of test `t`: a fresh draw for every test."""
    return jax.random.fold_in(perm_key, t)


@functools.partial(jax.jit, static_argnames=("n", "d", "n_groups",
                                             "density", "scale", "effect"))
def counts(key, *, n: int, d: int, n_groups: int, density: float,
           scale: float, effect: float):
    """(x (n, d) float32 whole-number counts, grouping (n,) int32)."""
    k_grp, k_pres, k_gam, k_exp, k_bump = jax.random.split(key, 5)
    grouping = jax.random.randint(k_grp, (n,), 0, n_groups, jnp.int32)
    grouping = grouping.at[:n_groups].set(jnp.arange(n_groups, dtype=jnp.int32))
    rows = jnp.arange(n)[:, None]
    cols = jnp.arange(d)[None, :]
    # every row holds at least one feature, so no distance divides by 0
    present = (jax.random.uniform(k_pres, (n, d)) < density) | (cols == rows % d)
    bump = jax.random.uniform(k_bump, (n_groups, d)) < 0.1
    lam = scale * jax.random.gamma(k_gam, 0.7, (n, d)) \
        * jax.random.exponential(k_exp, (n, d)) \
        * (1.0 + effect * bump[grouping].astype(jnp.float32))
    x = jnp.where(present, 1.0 + jnp.floor(jnp.minimum(lam, COUNT_CAP - 1.0)),
                  0.0)
    return x.astype(jnp.float32), grouping


def _row_block(d: int) -> int:
    return max(8, 8192 // max(d, 1))


@functools.partial(jax.jit, static_argnames=("metric",))
def distances(x, *, metric: str):
    """(n, n) float32 distances of whole-number counts, built in row
    blocks: Bray-Curtis sum|xi - xj| / sum(xi + xj), or Jaccard on
    presence, (|A| + |B| - 2|A & B|) / |A | B|. Numerators and
    denominators are exact; each distance is rounded once."""
    n, d = x.shape
    block = min(_row_block(d), n)
    tot = jnp.sum(x, axis=1)
    pres = (x > 0).astype(jnp.bfloat16)      # 0/1: exact in bfloat16
    card = jnp.sum(pres.astype(jnp.float32), axis=1)
    xt = x.T

    def rows(lo):
        if metric == "braycurtis":
            xb = jax.lax.dynamic_slice_in_dim(x, lo, block, 0)
            num = jnp.sum(jnp.abs(xb[:, :, None] - xt[None, :, :]), axis=1)
            den = jax.lax.dynamic_slice_in_dim(tot, lo, block)[:, None] \
                + tot[None, :]
        elif metric == "jaccard":
            pb = jax.lax.dynamic_slice_in_dim(pres, lo, block, 0)
            inter = jnp.dot(pb, pres.T, preferred_element_type=jnp.float32)
            cb = jax.lax.dynamic_slice_in_dim(card, lo, block)[:, None]
            den = cb + card[None, :] - inter
            num = den - inter
        else:
            raise ValueError(f"no exact form for metric {metric!r}")
        return num / den

    def body(i, out):
        lo = jnp.minimum(i * block, n - block)   # last block overlaps
        return jax.lax.dynamic_update_slice_in_dim(out, rows(lo), lo, 0)

    return jax.lax.fori_loop(0, -(-n // block), body,
                             jnp.zeros((n, n), jnp.float32))
