"""The plain reference and the comparison that decides `correct`.

PERMANOVA (Anderson 2001): for a grouping g of n objects into a groups,
  s_T = sum_{i<j} d_ij^2 / n
  s_W = sum_groups (1/n_g) sum_{i<j in group} d_ij^2
  F   = ((s_T - s_W) / (a - 1)) / (s_W / (n - a))
  p   = (#{k >= 1 : F_k >= F_0} + 1) / (P + 1)
over P permutations of the labels; permutation 0 is the observed
grouping. Permutation k of test key K relabels by
grouping[jax.random.permutation(fold_in(K, k), n)], the documented draw
of the system under test; the reference makes it itself.

The reference sums in float64 on the host, from the float32 distance
matrix the benchmark made, streamed in row blocks. It imports nothing of
the program. The control is the same arithmetic in bfloat16 on the
device, put in the program's place: the comparison has to reject it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import data


@dataclasses.dataclass
class Answer:
    """What one test hands the host: F of every permutation and p."""
    test: int                 # index t of the test key (data.test_key)
    f: np.ndarray             # (P + 1,) float64, index 0 observed
    p: float
    s_t: float


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def labels(key, grouping: np.ndarray, perm_index: Sequence[int]) -> np.ndarray:
    """(k, n) relabelings for permutation indices of one test key."""
    grouping = np.asarray(grouping)
    out = np.empty((len(perm_index), grouping.shape[0]), np.int64)
    for r, k in enumerate(perm_index):
        if k == 0:
            out[r] = grouping
        else:
            perm = jax.random.permutation(jax.random.fold_in(key, int(k)),
                                          grouping.shape[0])
            out[r] = grouping[np.asarray(perm)]
    return out


def _row_blocks(dm, rows: int):
    """(global first row, float32 block) over the distinct row shards,
    leaving out pad rows past the n = dm.shape[1] samples."""
    n = dm.shape[1]
    seen = set()
    for shard in dm.addressable_shards:
        lo0 = shard.index[0].start or 0
        if lo0 in seen:                 # replicas along other mesh axes
            continue
        seen.add(lo0)
        block = shard.data
        for lo in range(0, min(block.shape[0], n - lo0), rows):
            hi = min(lo + rows, block.shape[0], n - lo0)
            yield lo0 + lo, np.asarray(block[lo:hi])


def s_w_fp64(dm, label_rows: np.ndarray, n_groups: int, rows: int = 2048):
    """float64 s_W of each label row, and s_T, over the device's D."""
    label_rows = np.asarray(label_rows)
    k, n = label_rows.shape
    onehot = np.zeros((n, k * n_groups), np.float64)
    for r in range(k):
        onehot[np.arange(n), r * n_groups + label_rows[r]] = 1.0
    w = 1.0 / np.bincount(label_rows[0], minlength=n_groups)
    tot = np.zeros(k * n_groups, np.float64)
    s_t = 0.0
    for lo, block in _row_blocks(dm, rows):
        d2 = block.astype(np.float64)
        d2 *= d2
        s_t += d2.sum()
        tot += np.einsum("ik,ik->k", d2 @ onehot, onehot[lo:lo + len(d2)])
    s_w = 0.5 * (tot.reshape(k, n_groups) * w).sum(axis=1)
    return s_w, s_t / 2.0 / n


def c_of(n: int, a: int) -> float:
    return (n - a) / (a - 1)


def sw_from_f(f, s_t: float, n: int, a: int) -> np.ndarray:
    """Invert F = c (s_T / s_W - 1); well conditioned, unlike F itself
    (s_T - s_W cancels at large n)."""
    return s_t / (1.0 + np.asarray(f, np.float64) / c_of(n, a))


def p_count(f: np.ndarray) -> int:
    return int(np.sum(f[1:] >= f[0])) + 1


def strata(n_perms: int, k: int):
    """k runs of permutation indices that tile 1..n_perms, as even as can
    be: (first, last + 1) of each."""
    edges = [1 + (i * n_perms) // k for i in range(k + 1)]
    return [(lo, hi) for lo, hi in zip(edges[:-1], edges[1:]) if hi > lo]


def sample(answers: Sequence[Answer], n_perms: int, k: int,
           rng: np.random.Generator):
    """(test, permutation) pairs to check: for every test its observed
    grouping and one permutation drawn from the seed in each of k strata
    of 1..n_perms. Any run of 2 n_perms / k consecutive permutations holds
    a whole stratum, so every chunk the program computes apart, if at
    least that long, is checked in every test; so is a last chunk of
    n_perms / k or more."""
    picks = []
    for a in answers:
        picks.append((a.test, 0))
        picks += [(a.test, int(rng.integers(lo, hi)))
                  for lo, hi in strata(n_perms, k)]
    return picks


def compare(answers: Sequence[Answer], dm, grouping: np.ndarray,
            perm_key, n_perms: int, n_groups: int,
            limits: dict, check_perms: int, rng: np.random.Generator):
    """The numbers compared, each beside its limit, and how many tests
    failed one of them.

    sw   worst relative s_W error, through F, over the sampled permutations
         (`sample`: check_perms strata in every test)
    s_t  worst relative s_T error over the tests
    p    tests whose p is not the count of their own F, or whose F has not
         P + 1 entries
    """
    n = int(np.asarray(grouping).shape[0])
    picks = sample(answers, n_perms, check_perms, rng)
    by_test: dict = {}
    for t, k in picks:
        by_test.setdefault(t, []).append(k)
    rows, index = [], []
    for t, ks in by_test.items():
        rows.append(labels(data.test_key(perm_key, t), grouping, ks))
        index += [(t, k) for k in ks]
    sw_ref, st_ref = s_w_fp64(dm, np.concatenate(rows), n_groups)
    ref = dict(zip(index, sw_ref))

    bad = set()
    st_err, sw_err, p_bad = 0.0, 0.0, 0
    by_index = {a.test: a for a in answers}
    for a in answers:
        e = abs(a.s_t - st_ref) / st_ref
        st_err = max(st_err, e) if math.isfinite(e) else math.inf
        if not e <= limits["s_t"]:
            bad.add(a.test)
        f = np.asarray(a.f, np.float64)
        if f.shape != (n_perms + 1,) or not math.isfinite(a.p) or \
                round(a.p * (n_perms + 1)) != p_count(f):
            p_bad += 1
            bad.add(a.test)
    for (t, k), s_ref in ref.items():
        a = by_index[t]
        f = np.asarray(a.f, np.float64)
        got = sw_from_f(f[k], a.s_t, n, n_groups) if k < f.shape[0] \
            else np.nan
        e = abs(float(got) - s_ref) / s_ref
        sw_err = max(sw_err, e) if math.isfinite(e) else math.inf
        if not e <= limits["sw"]:
            bad.add(t)
    checks = [Check("sw", float(sw_err), limits["sw"]),
              Check("s_t", float(st_err), limits["s_t"]),
              Check("p", float(p_bad), float(limits["p"]))]
    return checks, len(bad)


def check(state, answers: Sequence[Answer], dm):
    """`compare` with what an entry's state holds, over the reference D
    `dm`, which is freed after."""
    cfg = state.config
    try:
        return compare(answers, dm, np.asarray(state.grouping),
                       state.perm_key, state.n_perms, cfg["n_groups"],
                       cfg["limits"], cfg["check_perms"], state.rng)
    finally:
        if dm is not getattr(state, "dm", None):
            dm.delete()


# ---------------------------------------------------------------------------
# The control: the reference in bfloat16, in the program's place.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_groups", "chunk", "acc"))
def _control_chunk(d2, grouping, key, lo, *, n_groups: int, chunk: int, acc):
    n = grouping.shape[0]
    idx = lo + jnp.arange(chunk)
    perm = jax.vmap(lambda i: jax.random.permutation(
        jax.random.fold_in(key, i), n))(idx)
    lab = jnp.where((idx == 0)[:, None], grouping[None, :], grouping[perm])
    onehot = jax.nn.one_hot(lab, n_groups, dtype=jnp.bfloat16)   # (C, n, G)
    oh = onehot.transpose(1, 0, 2).reshape(n, chunk * n_groups)
    t = jnp.dot(d2, oh, preferred_element_type=acc)
    per = jnp.sum((t * oh.astype(acc)).reshape(n, chunk, n_groups), axis=0,
                  dtype=acc)                                     # (C, G)
    w = (1.0 / jnp.bincount(grouping, length=n_groups)).astype(acc)
    return 0.5 * jnp.sum(per * w[None, :], axis=1, dtype=acc)


def control_answer(dm, grouping, key, t: int, n_perms: int, n_groups: int,
                   acc=jnp.bfloat16, chunk: int = 128) -> Answer:
    """One test computed by the reference arithmetic on bfloat16 operands,
    with results and sums in `acc`: bfloat16 is the control; float32
    is the single-pass MXU form a later change might try."""
    grouping = jnp.asarray(grouping, jnp.int32)
    n = int(grouping.shape[0])
    d2 = (dm.astype(jnp.bfloat16)) ** 2
    s_t = float(jnp.sum(d2, dtype=acc) / (2 * n))
    s_w = np.concatenate([
        np.asarray(_control_chunk(d2, grouping, key, jnp.int32(lo),
                                  n_groups=n_groups, chunk=chunk, acc=acc),
                   np.float64)
        for lo in range(0, n_perms + 1, chunk)])[:n_perms + 1]
    f = c_of(n, n_groups) * (s_t / s_w - 1.0)
    return Answer(test=t, f=f, p=p_count(f) / (n_perms + 1), s_t=s_t)
