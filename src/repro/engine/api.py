"""Engine entry points: every PERMANOVA path in the repo routes through here.

run()              single-host full test; planner-driven impl selection,
                   streaming scheduler for large permutation counts.
permanova_many()   batched multi-study API: vmaps one plan over a stack of
                   distance matrices (the many-users serving scenario).

core.permanova.permanova() and core.distributed.permanova_distributed()
remain the public signatures; they are thin wrappers over this module.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core import design as design_mod
from repro.core import fstat, permutations
# NOTE: `from repro.core import permanova` would resolve to the *function*
# (the package __init__ rebinds the submodule name); import symbols directly.
from repro.core.permanova import (PermanovaResult, TermResult, f_from_sw,
                                  p_value_from_null, s_total)
from repro.engine import planner, registry, scheduler

Array = jax.Array


def _sw_traffic_bytes(impl: str, n: int, n_total: int, chunk: int,
                      n_cols: int = 0) -> float:
    """Predicted stage-2 traffic for the s_W sweep, per the paper's
    dataflow distinction: 'brute' re-streams the full f32 mat2 once PER
    PERMUTATION (the GPU-style massive-bandwidth layout), everything else
    (tiled/matmul/pallas) reads mat2 once per CHUNK and amortizes it over
    the chunk's permutations. Plus the regenerated (chunk, n) int32 labels
    per chunk — (k+1)-wide on the dense-design per-column path."""
    n_chunks = -(-n_total // max(chunk, 1))
    mat2_passes = n_total if impl == "brute" else n_chunks
    label_bytes = 4 * chunk * n * (n_cols + 1)
    return float(mat2_passes) * 4.0 * n * n + float(n_chunks) * label_bytes


def _sw_span_attrs(impl: str, n: int, n_total: int, chunk: int,
                   n_cols: int = 0):
    """Span attrs for the s_W stage (None while tracing is off, so the
    disabled path allocates nothing)."""
    if not _obs.trace_enabled():
        return None
    return {"impl": impl, "chunk": chunk,
            "predicted_bytes": _sw_traffic_bytes(impl, n, n_total, chunk,
                                                 n_cols)}


def _plan_labels(mat2, grouping, inv_gs, n_total: int, n_groups: int, *,
                 impl: str, sw_fn: Optional[Callable], key: jax.Array,
                 memory_budget_bytes: Optional[float], chunk: Optional[int],
                 autotune: bool, backend: Optional[str],
                 tuning: Optional[dict]):
    """run()'s plan and bound s_W callable for a plain label design."""
    n = mat2.shape[0]
    if sw_fn is not None:
        pl = planner.plan(n, n_total, n_groups, backend=backend,
                          impl="matmul",  # footprint stand-in for budgeting
                          memory_budget_bytes=memory_budget_bytes,
                          chunk=chunk)
        pl = dataclasses.replace(pl, impl="<custom sw_fn>",
                                 reason="caller-supplied sw_fn")
        return pl, sw_fn
    pinned = None if impl == "auto" else impl
    tuned = False
    if autotune and pinned is not None:
        warnings.warn(
            f"autotune=True ignored: impl is pinned to {impl!r} "
            "(use impl='auto' to let measurements pick)", stacklevel=3)
    if pinned is None and autotune:
        pinned = planner.autotune(mat2, grouping, inv_gs,
                                  backend=backend, key=key)
        tuned = True
    pl = planner.plan(n, n_total, n_groups, backend=backend, impl=pinned,
                      memory_budget_bytes=memory_budget_bytes,
                      chunk=chunk, tuning=tuning)
    if tuned:
        pl = dataclasses.replace(
            pl, reason="empirical autotune winner (measured on operands)")
    return pl, registry.get(pl.impl).bound(**pl.tuning)


def run(dm: Array, grouping: Array, *, n_perms: int = 999,
        key: Optional[jax.Array] = None, n_groups: Optional[int] = None,
        impl: str = "auto", sw_fn: Optional[Callable] = None,
        memory_budget_bytes: Optional[float] = None,
        chunk: Optional[int] = None, autotune: bool = False,
        backend: Optional[str] = None, tuning: Optional[dict] = None,
        squared: bool = False,
        covariates=None, strata=None, weights=None,
        s_t: Optional[float] = None) -> "PermanovaResult":
    """Full PERMANOVA through the hardware-aware engine.

    impl:  'auto' (planner heuristics; `autotune=True` upgrades to the
           empirical measure-and-cache pass) or any registry name.
    tuning: override the chosen impl's tuning knobs (unknown keys ignored).
    sw_fn: escape hatch — bypass the registry with a custom batch callable.
    memory_budget_bytes / chunk: bound the live label tensor; sweeps larger
           than the chunk run through the streaming scheduler.
    squared: `dm` is already the element-squared matrix mat2 = D∘D (the
           pipeline's streaming builder produces mat2 directly so the raw
           distance matrix is never resident alongside it).
    s_t:   precomputed total sum of squares (the streaming builder
           accumulates it as a Gower marginal); skips one full-matrix
           reduction when provided.

    grouping may also be a core.design.Design. Every label-array call
    site routes through Design.from_labels — a plain single-factor design
    (no strata/covariates/weights) unwraps to the exact pre-design label
    path below (same programs, same bits); anything else dispatches to
    run_design().
    """
    if key is None:
        key = jax.random.key(0)
    # host phases of a test, each an obs span: engine.plan, engine.sw
    # (its chunks split into dispatch and fetch), engine.finalize
    with _obs.span("engine.plan"):
        if (covariates is not None or strata is not None
                or weights is not None):
            if isinstance(grouping, design_mod.Design):
                raise ValueError("pass covariates/strata/weights either to "
                                 "run() or inside the Design, not both")
            design = design_mod.build(
                grouping=grouping, covariates=covariates, strata=strata,
                weights=weights, n_groups=n_groups)
        else:
            design = design_mod.Design.from_labels(grouping,
                                                   n_groups=n_groups)
        if design.is_plain_labels:
            dm = jnp.asarray(dm)
            grouping = design.grouping
            n = dm.shape[0]
            n_groups = design.n_groups
            mat2 = dm if squared else dm * dm
            inv_gs = permutations.inv_group_sizes(grouping, n_groups)
            n_total = n_perms + 1
            pl, fn = _plan_labels(mat2, grouping, inv_gs, n_total, n_groups,
                                  impl=impl, sw_fn=sw_fn, key=key,
                                  memory_budget_bytes=memory_budget_bytes,
                                  chunk=chunk, autotune=autotune,
                                  backend=backend, tuning=tuning)
    if not design.is_plain_labels:
        if sw_fn is not None:
            raise ValueError("sw_fn is not supported with strata/covariate/"
                             "weighted designs; use a registry impl")
        return run_design(dm, design, n_perms=n_perms, key=key, impl=impl,
                          memory_budget_bytes=memory_budget_bytes,
                          chunk=chunk, autotune=autotune, backend=backend,
                          tuning=tuning, squared=squared, s_t=s_t)

    ch = pl.chunk if pl.streaming else n_total
    with _obs.span("engine.sw", _sw_span_attrs(pl.impl, n, n_total, ch)):
        if pl.streaming:
            s_w_all, stats = scheduler.sw_streaming(
                mat2, grouping, inv_gs, key, n_total, fn, chunk=pl.chunk)
        else:
            s_w_all, stats = scheduler.sw_batch(
                mat2, grouping, inv_gs, key, n_total, fn)

    with _obs.span("engine.finalize"):
        s_w_all = jnp.asarray(s_w_all)
        s_t = s_total(mat2) if s_t is None else jnp.float32(s_t)
        f_all = f_from_sw(s_w_all, s_t, n, n_groups)
        p_value = p_value_from_null(f_all)
    return PermanovaResult(
        f_stat=f_all[0],
        p_value=p_value,
        s_t=s_t,
        s_w=s_w_all[0],
        f_perms=f_all,
        n_objects=n,
        n_groups=n_groups,
        n_perms=n_perms,
        method=f"permanova[{pl.impl}]",
        plan=f"{pl.describe()} chunks={stats.n_chunks}",
    )


# ---------------------------------------------------------------------------
# Design path: strata-restricted label sweeps and dense hat-matrix designs.
# ---------------------------------------------------------------------------

def design_result(s_cols, design: "design_mod.Design", *, n_objects: int,
                  n_perms: int, method: str, plan: str,
                  ordination=None) -> PermanovaResult:
    """Assemble the per-term results contract from the per-column sweep.

    s_cols: (n_total, K) per-column quadratic forms (index 0 = observed).
    Headline f_stat/p_value are the LAST term's (the covariate-adjusted
    factor of interest); every non-intercept term lands in `.terms`.
    """
    s_cols = jnp.asarray(s_cols)
    ts = design_mod.term_stats(s_cols, design)
    terms = []
    for i, t in enumerate(design.terms[1:]):
        f_p = ts.f_terms[:, i]
        terms.append(TermResult(
            name=t.name, kind=t.kind, df=t.df, ss=ts.ss_terms[0, i],
            f_stat=f_p[0], p_value=p_value_from_null(f_p),
            r2=ts.ss_terms[0, i] / ts.s_t, f_perms=f_p))
    last = terms[-1]
    return PermanovaResult(
        f_stat=last.f_stat, p_value=last.p_value, s_t=ts.s_t,
        s_w=ts.ss_resid[0], f_perms=last.f_perms, n_objects=n_objects,
        n_groups=(design.n_groups if design.n_groups is not None
                  else design.rank),
        n_perms=n_perms, method=method, plan=plan, terms=tuple(terms),
        ordination=ordination)


def label_design_result(s_w_all, s_t, design: "design_mod.Design", *,
                        n_objects: int, n_perms: int, method: str,
                        plan: str, ordination=None) -> PermanovaResult:
    """Result assembly for LABELS-mode designs (single factor + strata):
    classic F from s_W, with the factor reported as the one term."""
    n_groups = design.n_groups
    f_all = f_from_sw(s_w_all, s_t, n_objects, n_groups)
    factor = design.terms[-1]
    ss_a = s_t - s_w_all[0]
    p_val = p_value_from_null(f_all)
    terms = (TermResult(
        name=factor.name, kind=factor.kind, df=factor.df, ss=ss_a,
        f_stat=f_all[0], p_value=p_val, r2=ss_a / s_t, f_perms=f_all),)
    return PermanovaResult(
        f_stat=f_all[0], p_value=p_val, s_t=s_t, s_w=s_w_all[0],
        f_perms=f_all, n_objects=n_objects, n_groups=n_groups,
        n_perms=n_perms, method=method, plan=plan, terms=terms,
        ordination=ordination)


def design_many_result(s_cols, design: "design_mod.Design", *,
                       dof_resid, n_objects: int, n_groups: int,
                       n_perms: int, n_valid=None, ordination=None,
                       plan: str = "") -> "PermanovaManyResult":
    """Multi-study result assembly from stacked (S, n_total, K) per-column
    sweeps (shared by engine.permanova_many and pipeline_many)."""
    ts = design_mod.term_stats(s_cols, design, dof_resid=dof_resid)
    terms = []
    for i, t in enumerate(design.terms[1:]):
        f_p = ts.f_terms[:, :, i]                 # (S, n_total)
        terms.append(TermResult(
            name=t.name, kind=t.kind, df=t.df, ss=ts.ss_terms[:, 0, i],
            f_stat=f_p[:, 0], p_value=jax.vmap(p_value_from_null)(f_p),
            r2=ts.ss_terms[:, 0, i] / ts.s_t, f_perms=f_p))
    last = terms[-1]
    return PermanovaManyResult(
        f_stat=last.f_stat, p_value=last.p_value, s_t=ts.s_t,
        s_w=ts.ss_resid[:, 0], f_perms=last.f_perms, n_objects=n_objects,
        n_groups=n_groups, n_perms=n_perms, n_valid=n_valid,
        ordination=ordination, terms=tuple(terms), plan=plan)


def run_design(dm: Array, design: "design_mod.Design", *,
               n_perms: int = 999, key: Optional[jax.Array] = None,
               impl: str = "auto",
               memory_budget_bytes: Optional[float] = None,
               chunk: Optional[int] = None, autotune: bool = False,
               backend: Optional[str] = None, tuning: Optional[dict] = None,
               squared: bool = False,
               s_t: Optional[float] = None) -> "PermanovaResult":
    """Full PERMANOVA for a non-plain design (strata / covariates /
    weights / multi-factor) on a resident (squared-)distance matrix.

    Labels-mode designs (single factor + strata=) run the SAME registry
    impls as run() — the paper's brute/tiled/matmul/Pallas dataflows all
    consume strata-permuted labels unchanged. Dense designs run the
    per-column matmul contraction (hat-matrix blocks replacing the
    one-hot G), with the planner's workset model sized for K design
    columns and impl choice restricted to matmul-family companions.
    """
    if key is None:
        key = jax.random.key(0)
    dm = jnp.asarray(dm)
    n = dm.shape[0]
    if design.n != n:
        raise ValueError(f"design is for n={design.n}, matrix is {n}x{n}")
    mat2 = dm if squared else dm * dm
    n_total = n_perms + 1
    pinned = None if impl == "auto" else impl

    if design.mode == design_mod.MODE_LABELS:
        # strata-restricted single factor: every label impl applies
        grouping, n_groups = design.grouping, design.n_groups
        inv_gs = permutations.inv_group_sizes(grouping, n_groups)
        if autotune and pinned is None:
            pinned = planner.autotune(mat2, grouping, inv_gs,
                                      backend=backend, key=key)
        pl = planner.plan(n, n_total, n_groups, backend=backend,
                          impl=pinned,
                          memory_budget_bytes=memory_budget_bytes,
                          chunk=chunk, tuning=tuning)
        fn = registry.get(pl.impl).bound(**pl.tuning)
        ch = pl.chunk if pl.streaming else n_total
        with _obs.span("engine.sw",
                       _sw_span_attrs(pl.impl, n, n_total, ch)):
            if pl.streaming:
                s_w_np, stats = scheduler.sw_streaming(
                    mat2, grouping, inv_gs, key, n_total, fn, chunk=pl.chunk,
                    strata=design.strata)
                s_w_all = jnp.asarray(s_w_np)
            else:
                s_w_all, stats = scheduler.sw_batch(
                    mat2, grouping, inv_gs, key, n_total, fn,
                    strata=design.strata)
        s_t = s_total(mat2) if s_t is None else jnp.float32(s_t)
        return label_design_result(
            s_w_all, s_t, design, n_objects=n, n_perms=n_perms,
            method=f"permanova[{pl.impl}+strata]",
            plan=f"{pl.describe()} chunks={stats.n_chunks} strata")

    # dense design: per-column contraction against the basis operand
    if autotune:
        warnings.warn(
            "autotune=True ignored for dense designs: the contraction is "
            "the per-column matmul form on every backend", stacklevel=2)
    k = design.k_cols
    pl = planner.plan(n, n_total,
                      design.n_groups if design.n_groups else k,
                      backend=backend, impl=pinned,
                      memory_budget_bytes=memory_budget_bytes,
                      chunk=chunk, tuning=tuning, n_cols=k)
    cols_fn = registry.bound_cols(pl.impl, **pl.tuning)
    strata = (design.strata if design.strata is not None
              else jnp.zeros((n,), jnp.int32))
    with _obs.span("engine.sw",
                   _sw_span_attrs(pl.impl, n, n_total, pl.chunk,
                                  n_cols=k)):
        s_cols, stats = scheduler.sw_cols_streaming(
            mat2, design.basis, strata, key, n_total, cols_fn,
            chunk=pl.chunk)
    return design_result(
        s_cols, design, n_objects=n, n_perms=n_perms,
        method=f"permanova-design[{pl.impl}]",
        plan=f"{pl.describe()} chunks={stats.n_chunks} cols={k}")


# ---------------------------------------------------------------------------
# Batched multi-study API (serving scenario).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PermanovaManyResult:
    """Stacked results over S studies (leading axis S on every array).

    The shared multi-study result contract for `engine.permanova_many`
    AND `pipeline.pipeline_many`: F, p, effect size R^2, and (when the
    caller asked for ordination) top-k PCoA coordinates with explained
    variance per study.
    """
    f_stat: Array        # (S,)
    p_value: Array       # (S,)
    s_t: Array           # (S,)
    s_w: Array           # (S,)
    f_perms: Array       # (S, n_perms + 1)
    n_objects: int       # common (ragged input: padded) study size n
    n_groups: int
    n_perms: int
    plan: str = ""
    n_valid: Optional[Array] = None   # (S,) per-study sample counts when
                                      # the input was a ragged list
    ordination: object = None         # pipeline.ordination.PCoAResult with
                                      # stacked (S, n, k) coords, or None
    terms: object = None              # Optional[tuple[TermResult, ...]] on
                                      # the design path — each TermResult
                                      # carries (S,)-leading arrays

    @property
    def r2(self) -> Array:
        """(S,) effect sizes R^2 = s_A / s_T = 1 - s_W / s_T."""
        return 1.0 - self.s_w / self.s_t

    def __len__(self):
        return int(self.f_stat.shape[0])

    def study(self, s: int) -> "PermanovaResult":
        """View one study as a standard PermanovaResult."""
        n_obj = (self.n_objects if self.n_valid is None
                 else int(self.n_valid[s]))
        terms_s = None
        if self.terms is not None:
            terms_s = tuple(dataclasses.replace(
                t, ss=t.ss[s], f_stat=t.f_stat[s], p_value=t.p_value[s],
                r2=t.r2[s], f_perms=t.f_perms[s]) for t in self.terms)
        return PermanovaResult(
            f_stat=self.f_stat[s], p_value=self.p_value[s], s_t=self.s_t[s],
            s_w=self.s_w[s], f_perms=self.f_perms[s],
            n_objects=n_obj, n_groups=self.n_groups,
            n_perms=self.n_perms, method="permanova_many", plan=self.plan,
            terms=terms_s,
            ordination=(None if self.ordination is None
                        else self.ordination.study(s)))


def _pad_ragged_studies(dms: Sequence, groupings: Sequence, n_groups: int,
                        n_pad: Optional[int] = None):
    """Pad a ragged study list to one (S, n_max, n_max) stack.

    Pad distance rows/cols are zero and pad labels carry the SENTINEL
    group `n_groups` — one past the one-hot width, so every s_W form
    sees them contribute exactly nothing (zero one-hot row on the matmul
    path; zero mat2 entries everywhere else).

    n_pad: optional FIXED bucket width — pad to `n_pad` rows instead of
    the batch max, so successive calls with different study mixes keep
    hitting the same compiled program (the serving bucket contract)."""
    if len(dms) != len(groupings):
        raise ValueError(f"ragged input: {len(dms)} matrices vs "
                         f"{len(groupings)} groupings")
    sizes = [int(np.asarray(d).shape[0]) for d in dms]
    n = max(sizes)
    if n_pad is not None:
        if int(n_pad) < n:
            raise ValueError(
                f"n_pad={n_pad} is smaller than the largest study (n={n})")
        n = int(n_pad)
    s_count = len(dms)
    dm_stack = np.zeros((s_count, n, n), np.float32)
    g_stack = np.full((s_count, n), n_groups, np.int32)     # sentinel pad
    for i, (d, g) in enumerate(zip(dms, groupings)):
        m = sizes[i]
        d = np.asarray(d, np.float32)
        if d.shape != (m, m):
            raise ValueError(f"study {i}: expected square matrix, "
                             f"got {d.shape}")
        dm_stack[i, :m, :m] = d
        g_stack[i, :m] = np.asarray(g, np.int32)
    return (jnp.asarray(dm_stack), jnp.asarray(g_stack),
            jnp.asarray(sizes, jnp.int32))


@functools.lru_cache(maxsize=64)
def _many_program(impl: str, tuning: tuple, ch: int, n_chunks: int,
                  n_total: int, n: int, n_groups: int, ragged: bool):
    """The jitted vmapped multi-study program, cached per static config.

    Rebuilding jax.jit(...) per call would re-trace and re-compile the
    whole chunk-scanned program on every request — fatal for the serving
    scenario this entry point exists for. The registry fn is recreated
    from (impl, tuning) so the cache key is hashable and stable."""
    fn = registry.get(impl).bound(**dict(tuning))

    def one(dm, grouping, study_key, nv):
        mat2 = dm * dm
        inv_gs = permutations.inv_group_sizes(grouping, n_groups)

        def body(_, lo):
            if ragged:   # static: one branch is ever traced
                g = permutations.masked_permutation_batch_dyn(
                    study_key, grouping, nv, lo, ch)
            else:
                g = permutations.permutation_batch_dyn(study_key, grouping,
                                                       lo, ch)
            return None, fn(mat2, g, inv_gs)

        _, sws = jax.lax.scan(body, None, jnp.arange(n_chunks) * ch)
        s_w_all = sws.reshape(-1)[:n_total]
        if ragged:
            s_t = jnp.sum(mat2) / 2.0 / nv
            f_all = f_from_sw(s_w_all, s_t, nv, n_groups)
        else:
            s_t = s_total(mat2)
            f_all = f_from_sw(s_w_all, s_t, n, n_groups)
        return f_all, s_t, s_w_all[0]

    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=64)
def _many_program_design(ch: int, n_chunks: int, n_total: int, n: int,
                         k: int, ragged: bool):
    """The jitted vmapped multi-study DENSE-DESIGN program.

    One program per static config (mirrors _many_program): per study, the
    chunk scan draws strata-restricted index permutations by GLOBAL
    permutation index, gathers basis rows, and runs the per-column matmul
    contraction. Ragged studies fold their pad suffix into a sentinel
    stratum (pads permute among themselves; their zero basis rows
    contribute exactly +0.0, so the observed per-term statistics
    bit-match the unpadded study)."""

    def one(dm, basis, strata, study_key, nv_i):
        mat2 = dm * dm
        if ragged:   # static: one branch is ever traced
            strata = permutations.masked_strata(strata, nv_i)

        def body(_, lo):
            perms = permutations.strata_permutation_batch_dyn(
                study_key, strata, lo, ch)
            return None, fstat.sw_cols_block(
                mat2, fstat.basis_perm_factors(basis, perms))

        _, sc = jax.lax.scan(body, None, jnp.arange(n_chunks) * ch)
        return sc.reshape(-1, k)[:n_total]

    return jax.jit(jax.vmap(one))


def _build_study_designs(groupings, covariates, strata, weights, *,
                         n_groups: int, n: int, s_count: int, sizes=None):
    """Per-study dense Designs (padded to n rows), with a shared-structure
    check: every study must compile to the same term spans (same ranks),
    or the stacked program cannot share one column layout."""
    def pick(what, x, s, m):
        if x is None:
            return None
        arr = np.asarray(x[s])
        if arr.shape[0] != m:
            raise ValueError(
                f"study {s}: {what} has {arr.shape[0]} rows, expected "
                f"{m} (per-study design columns must be UNPADDED, aligned "
                "with that study's samples)")
        return arr

    designs = []
    for s in range(s_count):
        m = n if sizes is None else int(sizes[s])
        g_s = pick("groupings", groupings, s, m)
        cov_s = pick("covariates", covariates, s, m)
        if cov_s is not None:
            cov_s = cov_s.astype(np.float64)
        st_s = pick("strata", strata, s, m)
        w_s = pick("weights", weights, s, m)
        if w_s is not None:
            w_s = w_s.astype(np.float64)
        d = design_mod.build(grouping=g_s, covariates=cov_s, strata=st_s,
                             weights=w_s, n_groups=n_groups,
                             force_dense=True)
        designs.append(design_mod.pad_design(d, n))
    spans = [tuple((t.name, t.kind, t.df, t.lo, t.hi) for t in d.terms)
             for d in designs]
    if any(sp != spans[0] for sp in spans[1:]):
        raise ValueError(
            "stacked studies compiled to different design structures "
            "(per-study term ranks differ — e.g. a covariate collinear in "
            "one study only); run such studies individually: "
            f"{sorted(set(spans))}")
    return designs


def _permanova_many_design(dms, groupings, *, covariates, strata, weights,
                           n_groups: int, n_perms: int, key,
                           impl: str, chunk, memory_budget_bytes, backend,
                           mesh, ordination,
                           n_pad=None) -> "PermanovaManyResult":
    """Multi-study dense-design path: stacked or ragged studies, one
    vmapped per-column contraction, study axis shardable over 'data'.

    Every design shape (including strata-only single factors) runs the
    ONE dense program here, so sharded == single-host stays bit-identical
    for the whole design feature set; per-study keys fold by GLOBAL study
    index before sharding, exactly like the label path."""
    ragged = isinstance(dms, (list, tuple))
    if ragged:
        sizes = [int(np.asarray(d).shape[0]) for d in dms]
        dms_pad, _, n_valid = _pad_ragged_studies(dms, groupings, n_groups,
                                                  n_pad=n_pad)
        dms = dms_pad
        s_count, n = (int(v) for v in dms.shape[:2])
    else:
        dms = jnp.asarray(dms)
        sizes = None
        n_valid = None
        s_count, n = (int(v) for v in dms.shape[:2])
    designs = _build_study_designs(
        groupings, covariates, strata, weights, n_groups=n_groups, n=n,
        s_count=s_count, sizes=sizes)
    d0 = designs[0]
    k = d0.k_cols
    n_total = n_perms + 1

    basis_stack = jnp.stack([d.basis for d in designs])
    strata_stack = jnp.stack([
        d.strata if d.strata is not None else jnp.zeros((n,), jnp.int32)
        for d in designs])

    total_budget = (planner.DEFAULT_STREAM_BUDGET_BYTES
                    if memory_budget_bytes is None else memory_budget_bytes)
    pl = planner.plan(n, n_total, n_groups, backend=backend,
                      impl=None if impl == "auto" else impl,
                      memory_budget_bytes=total_budget / s_count,
                      chunk=chunk, n_cols=k)
    ch = pl.chunk
    n_chunks = -(-n_total // ch)
    run_many = _many_program_design(ch, n_chunks, n_total, n, k, ragged)

    nv_i = (jnp.full((s_count,), n, jnp.int32) if n_valid is None
            else n_valid.astype(jnp.int32))
    study_idx = jnp.arange(s_count)
    args = (dms, basis_stack, strata_stack, nv_i)
    where = "vmap"
    data_ways, s_pad, wrap_idx = study_axis_padding(mesh, s_count)
    if wrap_idx is not None:
        args = tuple(jnp.take(a, wrap_idx, axis=0) for a in args)
        study_idx = wrap_idx
    # GLOBAL study index -> per-study key, folded ONCE before any sharding
    # (the shard_map key-folding note in streaming.fused_sw_sharded)
    study_keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(study_idx)
    args = (args[0], args[1], args[2], study_keys, args[3])
    if data_ways > 1:
        args = put_study_sharded(mesh, args)
        where = (f"vmap@data[{data_ways}]"
                 + (f"+pad{s_pad}" if s_pad else ""))

    attrs = None
    if _obs.trace_enabled():
        attrs = {"studies": s_count, "impl": pl.impl, "where": where,
                 "predicted_bytes": s_count * _sw_traffic_bytes(
                     pl.impl, n, n_total, ch, n_cols=k)}
    with _obs.span("engine.studies", attrs):
        s_cols = _obs.maybe_block(run_many(*args))[:s_count]  # (S, nt, K)
    _obs.metrics.inc("engine.studies", s_count)

    dof_resid = ((nv_i if n_valid is None else n_valid).astype(jnp.float32)
                 - jnp.float32(d0.rank))

    ord_res = None
    if ordination is not None:
        from repro.pipeline import ordination as _ord  # deferred: cycle
        ord_res = _ord.pcoa_many(dms, int(ordination), n_valid=n_valid)

    return design_many_result(
        s_cols, d0, dof_resid=dof_resid, n_objects=n, n_groups=n_groups,
        n_perms=n_perms, n_valid=n_valid, ordination=ord_res,
        plan=(f"{pl.describe()} studies={s_count} cols={k}"
              f"{' ragged' if ragged else ''} chunks={n_chunks} "
              f"[{where}] ({d0.describe()})"))


def study_axis_padding(mesh, s_count: int):
    """(data_ways, s_pad, wrap_idx) for sharding a study axis over 'data'.

    Study counts that do not divide the axis are wrap-padded (any S
    works, even S < data_ways); callers slice results back to S. Shared
    by engine.permanova_many and pipeline_many's fused path so the two
    multi-study entry points keep one divisibility contract."""
    data_ways = int(mesh.shape.get("data", 1)) if mesh is not None else 0
    if data_ways <= 1:
        return data_ways, 0, None
    s_pad = (-s_count) % data_ways
    idx = jnp.arange(s_count + s_pad) % s_count if s_pad else None
    return data_ways, s_pad, idx


def put_study_sharded(mesh, args):
    """device_put every array with a leading-'data' NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec(a):
        return NamedSharding(mesh, P(*(["data"] + [None] * (a.ndim - 1))))

    return tuple(jax.device_put(a, spec(a)) for a in args)


def permanova_many(dms: Union[Array, Sequence[Array]],
                   groupings: Union[Array, Sequence[Array]], *,
                   n_groups: int,
                   n_perms: int = 999, key: Optional[jax.Array] = None,
                   impl: str = "auto", chunk: Optional[int] = None,
                   memory_budget_bytes: Optional[float] = None,
                   backend: Optional[str] = None,
                   mesh=None,
                   covariates=None, strata=None, weights=None,
                   ordination: Optional[int] = None,
                   n_pad: Optional[int] = None) -> PermanovaManyResult:
    """PERMANOVA over a stack of studies in one planned, shardable program.

    dms:        (S, n, n) distance matrices — or a RAGGED list of
                (n_s, n_s) matrices, padded internally under one plan
                (pad rows zero, pad labels a sentinel group; per-study
                dof/s_T use the true n_s, recorded in `n_valid`).
    n_pad:      optional fixed BUCKET width for ragged input: studies are
                padded to `n_pad` rows (not the batch max), so repeated
                calls with different study mixes of the same bucket reuse
                one compiled program — the batched-serving entry point
                (`n_valid` stays a traced per-study vector, so no shape
                in the program depends on the mix). Ignored for stacked
                input, which is already uniformly shaped.
    groupings:  (S, n) int labels in [0, n_groups) (a list for ragged
                input); n_groups must be shared — it sets the one-hot
                width (the serving scenario runs many users through one
                study design).
    mesh:       optional jax.sharding.Mesh with a 'data' axis — shards
                the STUDY axis over 'data' (same convention as
                pipeline_many's fused path). Study counts that do not
                divide the axis are padded and sliced. Per-study PRNG
                keys are folded by GLOBAL study index ONCE per dispatch
                before any sharding (the shard_map key-folding note in
                streaming.fused_sw_sharded), so
                sharded == single-host == S separate run() calls,
                bit-identically, regardless of which shard runs a study.
    ordination: optional k — also compute top-k PCoA coordinates per
                study (pipeline.ordination; implicit centered operator,
                no Gower matrix materialized) into `result.ordination`.

    Stacked study s draws its null from fold_in(key, s), so results match
    S independent run(..., key=fold_in(key, s)) calls exactly. Ragged
    studies draw from the masked generator instead (deterministic and
    independent per study, observed F identical to run(); the draws are
    not the unpadded stream — see permutations.masked_permutation_batch_dyn).

    Permutations are chunk-scanned inside the jitted program, so the live
    label tensor is (S, chunk, n) — the same fixed-memory contract as the
    streaming scheduler, vectorized over studies; the engine planner
    still picks the s_W impl and chunk per backend, so each shard runs
    the hardware-aware plan.

    covariates / strata / weights: per-study design columns — stacked
    (S, n, c) / (S, n) arrays, or ragged lists aligned with `dms`. Any of
    them routes the batch through the dense-design program (per-column
    hat-matrix contraction, strata-restricted index permutations; per-
    term statistics in `result.terms`); every study must compile to the
    same design structure. Padded sentinel rows carry zero design rows,
    so observed per-term F bit-matches the unpadded study.
    """
    if key is None:
        key = jax.random.key(0)
    if covariates is not None or strata is not None or weights is not None:
        return _permanova_many_design(
            dms, groupings, covariates=covariates, strata=strata,
            weights=weights, n_groups=n_groups, n_perms=n_perms, key=key,
            impl=impl, chunk=chunk,
            memory_budget_bytes=memory_budget_bytes, backend=backend,
            mesh=mesh, ordination=ordination, n_pad=n_pad)
    ragged = isinstance(dms, (list, tuple))
    if ragged:
        dms, groupings, n_valid = _pad_ragged_studies(dms, groupings,
                                                      n_groups, n_pad=n_pad)
    else:
        dms = jnp.asarray(dms)
        groupings = jnp.asarray(groupings, dtype=jnp.int32)
        n_valid = None
    s_count, n = (int(v) for v in groupings.shape)
    n_total = n_perms + 1

    pinned = None if impl == "auto" else impl
    # vmap holds every study's (chunk, n) labels + working set live at once,
    # so the per-study plan gets 1/S of the budget (default included).
    total_budget = (planner.DEFAULT_STREAM_BUDGET_BYTES
                    if memory_budget_bytes is None else memory_budget_bytes)
    per_study_budget = total_budget / s_count
    pl = planner.plan(n, n_total, n_groups, backend=backend, impl=pinned,
                      memory_budget_bytes=per_study_budget, chunk=chunk)
    ch = pl.chunk
    n_chunks = -(-n_total // ch)
    run_many = _many_program(pl.impl, tuple(sorted(pl.tuning.items())),
                             ch, n_chunks, n_total, n, n_groups, ragged)

    nv_arg = (jnp.full((s_count,), n, jnp.float32) if n_valid is None
              else n_valid.astype(jnp.float32))
    study_idx = jnp.arange(s_count)
    args = (dms, groupings, nv_arg)
    where = "vmap"
    data_ways, s_pad, wrap_idx = study_axis_padding(mesh, s_count)
    if wrap_idx is not None:
        # pad the STUDY axis (wrapping, so any S works) by replaying
        # studies; padded results are computed and sliced off below
        args = tuple(jnp.take(a, wrap_idx, axis=0) for a in args)
        study_idx = wrap_idx
    # GLOBAL study index -> per-study key, folded ONCE here, before any
    # sharding (never inside the sharded program, where it has
    # miscompiled);
    # a padded slot replays its source study's key, so the pad is inert.
    study_keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(study_idx)
    args = (args[0], args[1], study_keys, args[2])
    if data_ways > 1:
        args = put_study_sharded(mesh, args)
        where = (f"vmap@data[{data_ways}]"
                 + (f"+pad{s_pad}" if s_pad else ""))

    attrs = None
    if _obs.trace_enabled():
        attrs = {"studies": s_count, "impl": pl.impl, "where": where,
                 "predicted_bytes": s_count * _sw_traffic_bytes(
                     pl.impl, n, n_total, ch)}
    with _obs.span("engine.studies", attrs):
        f_perms, s_t, s_w = _obs.maybe_block(run_many(*args))
    _obs.metrics.inc("engine.studies", s_count)
    f_perms, s_t, s_w = (a[:s_count] for a in (f_perms, s_t, s_w))
    p_vals = jax.vmap(p_value_from_null)(f_perms)

    ord_res = None
    if ordination is not None:
        # computed OUTSIDE the sharded dispatch (deterministic subspace
        # iteration), so sharded and single-host embeddings are identical
        from repro.pipeline import ordination as _ord  # deferred: cycle
        ord_res = _ord.pcoa_many(dms, int(ordination), n_valid=n_valid)

    return PermanovaManyResult(
        f_stat=f_perms[:, 0], p_value=p_vals, s_t=s_t, s_w=s_w,
        f_perms=f_perms, n_objects=n, n_groups=n_groups, n_perms=n_perms,
        n_valid=n_valid, ordination=ord_res,
        plan=(f"{pl.describe()} studies={s_count}"
              f"{' ragged' if ragged else ''} chunks={n_chunks} [{where}]"))
