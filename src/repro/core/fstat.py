"""PERMANOVA pseudo-F partial statistic (`permanova_f_stat_sW`) — the paper's
hot loop — in several algorithmic forms.

The paper (Sfiligoi, PEARC25) studies exactly this computation:

    s_W[p] = sum_{row < col} mat[row,col]^2
             * 1[g_p[row] == g_p[col]] * inv_group_sizes[g_p[row]]

over 1k..1M permutations `p` of the grouping labels, with `mat` a distance
matrix of 1k^2..100k^2 elements. Variants implemented here:

  sw_algorithm1_numpy  literal numpy transcription of the paper's Algorithm 1
                       (brute force, scalar loops) — the correctness oracle.
  sw_brute_one         vectorized brute force for ONE permutation (the
                       GPU-style Algorithm 3: parallel over the (row,col)
                       triangle). jnp, O(n^2) intermediate.
  sw_tiled_one         structural transcription of the paper's Algorithm 2
                       (CPU-tiled): explicit TILE x TILE loop nest with the
                       inv_group_sizes hoist. Same math, tiled dataflow.
  sw_brute             brute force over a batch of permutations (scan over
                       permutation blocks x vmap inside a block).
  sw_matmul            beyond-paper one-hot matmul reformulation: for a block
                       of P permutations build E in {0,1}^{n x (P*G)}, compute
                       M2 @ E on the MXU and weight the per-group sums by w_g
                       afterwards. Raises the arithmetic intensity per M2
                       byte from ~3/4 flop/B to ~P*G/2 flop/B (see DESIGN.md
                       section 3).
  sw_rows_partial      row-sharded partial statistic for the distributed
                       runner (each shard owns a row block; triangle masking
                       uses global row offsets).

All functions take `mat2 = mat * mat` precomputed — squaring is a one-off
O(n^2) pass shared by every permutation, mirroring the paper's use of `val*val`
inside the loop only because OpenMP cannot hoist it; in JAX we hoist it.
`sw_*` results are identical either way (tests assert this).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# The contractions below sum millions of f32 products into statistics
# whose differences (s_T - s_W) cancel; on TPU a default-precision f32
# matmul takes one bf16 pass, which is too coarse for that. Full f32.
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Oracle: literal Algorithm 1 (numpy, scalar loops). Slow; tests only.
# ---------------------------------------------------------------------------

def sw_algorithm1_numpy(mat: np.ndarray, groupings: np.ndarray,
                        inv_group_sizes: np.ndarray) -> np.ndarray:
    """Literal transcription of the paper's ALGORITHM 1 (brute force)."""
    mat = np.asarray(mat, dtype=np.float32)
    groupings = np.asarray(groupings)
    inv_group_sizes = np.asarray(inv_group_sizes, dtype=np.float32)
    n_perms, n_dims = groupings.shape
    out = np.zeros((n_perms,), dtype=np.float32)
    for p in range(n_perms):
        grouping = groupings[p]
        s_w = np.float32(0.0)
        for row in range(n_dims - 1):          # no columns in last row
            group_idx = grouping[row]
            mat_row = mat[row]
            local = np.float32(0.0)
            for col in range(row + 1, n_dims):  # diagonal is always zero
                if grouping[col] == group_idx:
                    val = mat_row[col]
                    local += val * val
            s_w += local * inv_group_sizes[group_idx]
        out[p] = s_w
    return out


# ---------------------------------------------------------------------------
# Brute force (paper Algorithm 3 dataflow), one permutation, vectorized.
# ---------------------------------------------------------------------------

def sw_brute_one(mat2: Array, grouping: Array, inv_group_sizes: Array) -> Array:
    """Vectorized brute force over the strict upper triangle.

    Matches Algorithm 3: every (row < col) pair contributes
    mat2[row,col] * w[g[row]] iff g[col] == g[row].
    """
    n = mat2.shape[0]
    same = grouping[:, None] == grouping[None, :]
    w_row = inv_group_sizes[grouping][:, None]  # hoisted weight per row
    triu = jnp.triu(jnp.ones((n, n), dtype=bool), k=1)
    contrib = jnp.where(same & triu, mat2 * w_row, jnp.zeros((), mat2.dtype))
    return jnp.sum(contrib)


def sw_full_one(mat2: Array, grouping: Array, inv_group_sizes: Array) -> Array:
    """Full-matrix (i != j) form: exploits symmetry, sums everything and
    halves. The distance matrix has a zero diagonal so no correction term.
    This is the dataflow the TPU VPU prefers (no triangle mask)."""
    same = (grouping[:, None] == grouping[None, :]).astype(mat2.dtype)
    w_row = inv_group_sizes[grouping][:, None]
    return 0.5 * jnp.sum(mat2 * same * w_row)


# ---------------------------------------------------------------------------
# Tiled (paper Algorithm 2 dataflow), one permutation.
# ---------------------------------------------------------------------------

def sw_tiled_one(mat2: Array, grouping: Array, inv_group_sizes: Array,
                 *, tile: int = 64) -> Array:
    """Structural transcription of the paper's ALGORITHM 2 (CPU-tiled).

    Explicit TILE x TILE blocking of the upper triangle with the
    inv_group_sizes access hoisted per row-within-tile, expressed as a
    lax.fori_loop nest so the tiled dataflow survives tracing. When n is not
    a multiple of `tile` (e.g. prime n), the matrix is zero-padded up to the
    requested tile and the pad region carries a sentinel group (-1) with
    zero weight, so every pad pair contributes exactly 0 — the tiled
    dataflow is preserved instead of degrading toward tile=1.
    """
    n = mat2.shape[0]
    tile = min(tile, n)
    w = inv_group_sizes[grouping]  # (n,) hoisted per-row weight
    pad = (-n) % tile
    if pad:
        mat2 = jnp.pad(mat2, ((0, pad), (0, pad)))
        grouping = jnp.pad(grouping, (0, pad), constant_values=-1)
        w = jnp.pad(w, (0, pad))
        n = n + pad
    nt = n // tile
    row_ids = jnp.arange(tile)
    col_ids = jnp.arange(tile)

    def tile_body(carry, ij):
        s_w = carry
        ti, tj = ij
        r0 = ti * tile
        c0 = tj * tile
        m_tile = jax.lax.dynamic_slice(mat2, (r0, c0), (tile, tile))
        g_row = jax.lax.dynamic_slice(grouping, (r0,), (tile,))
        g_col = jax.lax.dynamic_slice(grouping, (c0,), (tile,))
        w_row = jax.lax.dynamic_slice(w, (r0,), (tile,))
        # strict upper triangle in GLOBAL coordinates
        gr = r0 + row_ids[:, None]
        gc = c0 + col_ids[None, :]
        mask = (gc > gr) & (g_col[None, :] == g_row[:, None])
        local = jnp.sum(jnp.where(mask, m_tile, 0.0), axis=1)  # per-row local_s_W
        return s_w + jnp.sum(local * w_row), None

    # only tiles with tj >= ti can contain upper-triangle entries
    tis, tjs = jnp.meshgrid(jnp.arange(nt), jnp.arange(nt), indexing="ij")
    keep = (tjs >= tis)
    order = jnp.argsort(~keep.ravel(), stable=True)[: nt * (nt + 1) // 2]
    ij = (tis.ravel()[order], tjs.ravel()[order])
    s_w, _ = jax.lax.scan(tile_body, jnp.zeros((), mat2.dtype), ij)
    return s_w


# ---------------------------------------------------------------------------
# Batched-permutation drivers.
# ---------------------------------------------------------------------------

def _scan_blocks(one_fn: Callable, mat2: Array, groupings: Array,
                 inv_group_sizes: Array, block: int) -> Array:
    """scan over permutation blocks, vmap(one_fn) inside a block."""
    n_perms = groupings.shape[0]
    block = min(block, n_perms)
    pad = (-n_perms) % block
    if pad:
        groupings = jnp.pad(groupings, ((0, pad), (0, 0)), mode="edge")
    gblocks = groupings.reshape(-1, block, groupings.shape[-1])

    def body(_, gb):
        return None, jax.vmap(lambda g: one_fn(mat2, g, inv_group_sizes))(gb)

    _, out = jax.lax.scan(body, None, gblocks)
    return out.reshape(-1)[:n_perms]


def sw_brute(mat2: Array, groupings: Array, inv_group_sizes: Array,
             *, block: int = 32) -> Array:
    """Brute-force s_W for a batch of permutations. (n_perms,) float."""
    return _scan_blocks(sw_brute_one, mat2, groupings, inv_group_sizes, block)


def sw_tiled(mat2: Array, groupings: Array, inv_group_sizes: Array,
             *, tile: int = 64, block: int = 8) -> Array:
    one = functools.partial(sw_tiled_one, tile=tile)
    return _scan_blocks(one, mat2, groupings, inv_group_sizes, block)


# ---------------------------------------------------------------------------
# Beyond-paper: one-hot matmul (MXU) formulation.
# ---------------------------------------------------------------------------

def onehot_perm_factors(groupings_block: Array, n_groups: int,
                        dtype) -> Array:
    """E[p,:,g] = 1[g_p[i] == g] — the (P, n, G) one-hot factor shared by
    every matmul-form s_W variant. It carries no group weight: 0 and 1 are
    exact in every MXU pass format, where a weight operand would be rounded
    the same way for a whole group (a bias, not noise). The weights ride on
    the row factor, outside the matmul (sw_matmul_contract)."""
    return jax.nn.one_hot(groupings_block, n_groups, dtype=dtype)


def sw_matmul_contract(mat2_rows: Array, e: Array, e_rows: Array,
                       inv_group_sizes: Array) -> Array:
    """The matmul-form contraction over a block of mat2 rows.

    s[p] = 1/2 * sum_g w_g sum_i (M2_rows @ E[p])[i,g] * E_rows[p,i,g]

    e: (P, n, G) column factors over ALL samples; e_rows: (P, n_local, G)
    row factors aligned with mat2_rows (e itself for the full matrix, a
    row-offset slice for sharded/fused partials). The distance diagonal is
    zero, so the full i!=j sum equals twice the triangle sum; summing the
    partials over disjoint row blocks reconstructs the global statistic.
    The contraction reuses every M2 element across P*G output columns —
    this is the MXU-native dataflow.
    """
    p, n, g = e.shape
    n_local = mat2_rows.shape[0]
    e2d = jnp.transpose(e, (1, 0, 2)).reshape(n, p * g)    # (n, P*G)
    y = jnp.matmul(mat2_rows, e2d, precision=HIGHEST)      # on MXU
    w_rows = jnp.transpose(e_rows, (1, 0, 2)) * inv_group_sizes  # exact
    s = jnp.sum(y.reshape(n_local, p, g) * w_rows, axis=(0, 2))
    return 0.5 * s


def sw_matmul_block(mat2: Array, groupings_block: Array,
                    inv_group_sizes: Array) -> Array:
    """s_W for a block of P permutations via one big matmul."""
    e = onehot_perm_factors(groupings_block, inv_group_sizes.shape[0],
                            mat2.dtype)
    return sw_matmul_contract(mat2, e, e, inv_group_sizes)


def sw_matmul(mat2: Array, groupings: Array, inv_group_sizes: Array,
              *, perm_block: int = 64) -> Array:
    """MXU formulation over all permutations (scan over perm blocks)."""
    n_perms = groupings.shape[0]
    perm_block = min(perm_block, n_perms)
    pad = (-n_perms) % perm_block
    if pad:
        groupings = jnp.pad(groupings, ((0, pad), (0, 0)), mode="edge")
    gblocks = groupings.reshape(-1, perm_block, groupings.shape[-1])

    def body(_, gb):
        return None, sw_matmul_block(mat2, gb, inv_group_sizes)

    _, out = jax.lax.scan(body, None, gblocks)
    return out.reshape(-1)[:n_perms]


# ---------------------------------------------------------------------------
# Row-sharded partial (for shard_map distribution).
# ---------------------------------------------------------------------------

def sw_rows_partial(mat2_rows: Array, row_offset: Array, groupings: Array,
                    inv_group_sizes: Array, *, block: int = 32) -> Array:
    """Partial s_W over a block of rows [row_offset, row_offset + n_local).

    Each shard sums pairs (i, j) with i local and j > i global. Summing the
    partials over shards (psum along the 'model' axis) yields the full s_W.
    groupings is the FULL (n_perms, n) label array (replicated).
    """
    n_local, n = mat2_rows.shape

    def one(grouping):
        # pad by one slab so the last shard's window never clamps back
        # onto real rows when n is not a multiple of the shard count
        g_rows = jax.lax.dynamic_slice(jnp.pad(grouping, (0, n_local)),
                                       (row_offset,), (n_local,))
        w_row = inv_group_sizes[g_rows][:, None]
        same = grouping[None, :] == g_rows[:, None]
        gi = row_offset + jnp.arange(n_local)[:, None]
        gj = jnp.arange(n)[None, :]
        mask = same & (gj > gi)
        return jnp.sum(jnp.where(mask, mat2_rows * w_row, 0.0))

    return _scan_blocks(lambda _m, g, _w: one(g), mat2_rows, groupings,
                        inv_group_sizes, block)


def sw_matmul_rows_partial(mat2_rows: Array, row_offset: Array,
                           groupings: Array, inv_group_sizes: Array,
                           *, perm_block: int = 64) -> Array:
    """Row-sharded partial of the MXU formulation.

    Uses the full (i != j) symmetric sum: each shard computes
    1/2 * sum over its rows i of (M2[i,:] @ E) . E[i,:] — psum over shards
    reconstructs the global statistic exactly (zero diagonal).
    """
    n_local, n = mat2_rows.shape

    def body(_, gb):  # gb: (P, n)
        e = onehot_perm_factors(gb, inv_group_sizes.shape[0],
                                mat2_rows.dtype)
        p, _, g = e.shape
        # zero-padded by one slab: the last shard's window never clamps
        e_pad = jnp.pad(e, ((0, 0), (0, n_local), (0, 0)))
        e_rows = jax.lax.dynamic_slice(e_pad, (0, row_offset, 0),
                                       (p, n_local, g))
        return None, sw_matmul_contract(mat2_rows, e, e_rows,
                                        inv_group_sizes)

    n_perms = groupings.shape[0]
    perm_block = min(perm_block, n_perms)
    pad = (-n_perms) % perm_block
    if pad:
        groupings = jnp.pad(groupings, ((0, pad), (0, 0)), mode="edge")
    gblocks = groupings.reshape(-1, perm_block, n)
    _, out = jax.lax.scan(body, None, gblocks)
    return out.reshape(-1)[:n_perms]


# ---------------------------------------------------------------------------
# Design-basis (hat-matrix) contraction: per-column quadratic forms.
#
# The design subsystem (core.design) generalizes the one-hot factor E to an
# arbitrary orthonormal basis V of a model's column space: SS_resid =
# 1/2 <mat2, V V'> = sum_k 1/2 v_k' mat2 v_k, and adonis2-style per-term
# partial SS are (minus) per-column-span sums of the same quadratic forms.
# The dataflow is IDENTICAL to sw_matmul_contract — a tiled matmul against
# mat2 — except the per-column sums are kept separate so the caller can
# slice them into terms.
# ---------------------------------------------------------------------------

def basis_perm_factors(basis: Array, perms: Array) -> Array:
    """V[p] = basis[perms[p], :] — the (P, n, K) row-permuted design-basis
    factor that replaces the one-hot E on the matmul paths (permuting the
    basis rows is vegan's permute-the-observations convention)."""
    return basis[perms]


def sw_cols_contract(mat2_rows: Array, v: Array, v_rows: Array) -> Array:
    """Per-column quadratic forms over a block of mat2 rows.

    s[p, k] = 1/2 * sum_i (M2_rows @ V[p])[i, k] * V_rows[p, i, k]

    v: (P, n, K) permuted basis over ALL samples; v_rows: (P, n_local, K)
    rows aligned with mat2_rows (v itself for the full matrix, a
    row-offset slice for sharded/fused partials). Zero diagonal makes the
    full i != j sum twice the triangle sum; summing partials over disjoint
    row blocks reconstructs the global per-column statistic — exactly the
    contract of sw_matmul_contract, with the column axis kept."""
    p, n, k = v.shape
    n_local = mat2_rows.shape[0]
    v2d = jnp.transpose(v, (1, 0, 2)).reshape(n, p * k)     # (n, P*K)
    y = jnp.matmul(mat2_rows, v2d, precision=HIGHEST)       # on MXU
    s = jnp.sum(y.reshape(n_local, p, k)
                * jnp.transpose(v_rows, (1, 0, 2)), axis=0)
    return 0.5 * s                                          # (P, K)


def sw_cols_block(mat2: Array, v: Array) -> Array:
    """(P, K) per-column statistic for one block of permuted bases."""
    return sw_cols_contract(mat2, v, v)


# ---------------------------------------------------------------------------
# Block-sparse basis contraction: one-hot and strata-indicator bases are
# block-sparse (each column's nonzeros live inside a few strata), and
# strata-restricted permutations preserve that support — perms[p][i] stays
# inside stratum(i), so v[p, i, k] can be nonzero only at rows whose
# stratum belongs to column k's unpermuted strata support. That makes the
# support a STATIC host-side property: gather the supported rows once and
# skip every all-zero tile of the contraction.
# ---------------------------------------------------------------------------

def sparse_col_groups(basis, strata):
    """Group basis columns by permutation-invariant row support.

    Returns ((cols, rows), ...): `cols` are column indices sharing one
    support set, `rows` the sorted sample indices whose stratum appears in
    any of those columns' nonzeros. The groups partition the columns.
    Host-side (numpy) — call once per design, outside jit."""
    b = np.asarray(basis)
    s = np.asarray(strata)
    by_support: dict[frozenset, list[int]] = {}
    for k in range(b.shape[1]):
        nz = np.flatnonzero(b[:, k] != 0)
        sup = frozenset(np.unique(s[nz]).tolist())
        by_support.setdefault(sup, []).append(k)
    groups = []
    for sup, cols in sorted(by_support.items(), key=lambda t: t[1][0]):
        rows = np.flatnonzero(np.isin(s, sorted(sup)))
        groups.append((tuple(cols), tuple(int(r) for r in rows)))
    return tuple(groups)


def sw_cols_contract_sparse(mat2_rows: Array, v: Array, v_rows: Array,
                            groups) -> Array:
    """Block-sparse sw_cols_contract: contract each column group against
    only its supported sample columns of mat2_rows.

    Every skipped (row j, column k) term has v[p, j, k] == 0 exactly, so
    each group's gathered contraction computes the dense path's sum; it
    agrees to f32 rounding, since the shorter gathered matmul is blocked
    (reassociated) differently by the backend. With one group spanning all
    rows this degrades to the dense contraction itself."""
    p, n, k = v.shape
    if len(groups) == 1 and len(groups[0][1]) == n:
        return sw_cols_contract(mat2_rows, v, v_rows)
    out = jnp.zeros((p, k), mat2_rows.dtype)
    for cols, rows in groups:
        cols_a = jnp.asarray(cols, jnp.int32)
        rows_a = jnp.asarray(rows, jnp.int32)
        sg = sw_cols_contract(mat2_rows[:, rows_a],
                              v[:, rows_a][:, :, cols_a],
                              v_rows[:, :, cols_a])
        out = out.at[:, cols_a].set(sg)
    return out


def _scan_v_blocks(one_fn: Callable, mat2, vperms: Array, block: int):
    p = vperms.shape[0]
    block = min(block, p)
    pad = (-p) % block
    if pad:
        vperms = jnp.pad(vperms, ((0, pad), (0, 0), (0, 0)), mode="edge")
    vb = vperms.reshape(-1, block, *vperms.shape[1:])

    def body(_, v):
        return None, one_fn(mat2, v)

    _, out = jax.lax.scan(body, None, vb)
    return out.reshape(-1, vperms.shape[-1])[:p]


def sw_cols_matmul(mat2: Array, vperms: Array, *,
                   perm_block: int = 64) -> Array:
    """Per-column statistic over all permutations, matmul form (scan over
    permutation blocks — the design-mode analogue of sw_matmul)."""
    return _scan_v_blocks(sw_cols_block, mat2, vperms, perm_block)


def sw_cols_brute(mat2: Array, vperms: Array, *, block: int = 16) -> Array:
    """Per-column statistic, brute dataflow: every permutation re-streams
    mat2 (the GPU-style Algorithm 3 analogue for dense designs)."""
    def one_block(m2, vb):
        return jax.vmap(
            lambda v: 0.5 * jnp.einsum("ik,ij,jk->k", v, m2, v,
                                       precision=HIGHEST))(vb)
    return _scan_v_blocks(one_block, mat2, vperms, block)


def sw_cols_rows_partial(mat2_rows: Array, row_offset: Array,
                         vperms: Array, *, perm_block: int = 64) -> Array:
    """Row-sharded partial of the per-column contraction: each shard
    contracts its row block; psum over shards reconstructs (P, K)."""
    n_local = mat2_rows.shape[0]

    def one(m2, vb):
        pb, _, k = vb.shape
        v_rows = jax.lax.dynamic_slice(vb, (0, row_offset, 0),
                                       (pb, n_local, k))
        return sw_cols_contract(m2, vb, v_rows)

    return _scan_v_blocks(one, mat2_rows, vperms, perm_block)
