"""Hardware-aware planner + empirical autotuner for the s_W registry.

The heuristics encode the paper's Figure 1 result as dispatch rules:

  backend   choice                       why
  -------   -------------------------   -------------------------------------
  gpu       brute                       MI300A GPU cores prefer the brute
                                        Algorithm 3 (massive thread-level
                                        parallelism hides the re-stream)
  cpu       tiled  (mat2 > LLC)         MI300A CPU cores want the cache-tiled
            matmul (mat2 fits cache)    Algorithm 2 once the matrix spills
                                        the last-level cache; below that the
                                        BLAS/MXU one-hot form dominates
  tpu       pallas_matmul (n >= 256)    MXU one-hot contraction is the only
            matmul        (small n)     form past the v5e ridge point

`plan()` is pure shape/backend arithmetic — no timing. `autotune()` is the
optional measure-and-cache pass: it times every candidate on a small
permutation sample of the *actual* problem and memoizes the winner per
(backend, shape-bucket), so serving paths pay the measurement once.

The plan also fixes the streaming-permutation chunk: the scheduler executes
`n_perms` in fixed-memory chunks, so the label tensor held live is
(chunk, n) int32 instead of (n_perms, n) — that is what lets single-host
100k..1M-permutation runs fit any memory budget.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax

from repro.engine import registry
from repro.obs import metrics as _metrics

_log = logging.getLogger(__name__)
_WARNED: set = set()


def _warn_once(tag: str, msg: str) -> None:
    """Log a cache-health warning once per process (not once per plan():
    a 1000-study serving sweep hitting a disabled cache must not emit
    1000 lines). logging, not warnings — tier-1 runs warning-free."""
    if tag in _WARNED:
        return
    _WARNED.add(tag)
    _log.warning(msg)

def record_candidate_failure(where: str, name: str,
                             exc: BaseException) -> None:
    """A shoot-out candidate that failed to lower or run: counted
    (`autotune.candidate_failed`, and per candidate) and logged with its
    exception. It leaves the shoot-out, but never in silence — on TPU a
    kernel Mosaic refuses must not just vanish and let XLA win."""
    _metrics.inc("autotune.candidate_failed")
    _metrics.inc(f"autotune.candidate_failed.{name}")
    _log.warning("%s: candidate %r failed and is excluded: %s: %s", where,
                 name, type(exc).__name__, exc)


# Model constants (bytes). LLC: an MI300A CCD carries 32 MiB L3; once mat2
# spills it the paper's tiled dataflow wins on CPU.
CPU_LLC_BYTES = 32 * 1024 ** 2
DEFAULT_STREAM_BUDGET_BYTES = 256 * 1024 ** 2
MIN_CHUNK = 64
PALLAS_MIN_N = 256


@dataclasses.dataclass(frozen=True)
class Plan:
    """A resolved execution plan for one PERMANOVA problem."""
    impl: str                 # registry name
    backend: str
    tuning: Dict[str, int]    # resolved knobs passed to SwImpl.make
    chunk: int                # permutations per scheduler dispatch
    streaming: bool           # True when n_perms+1 > chunk
    reason: str

    def describe(self) -> str:
        t = ",".join(f"{k}={v}" for k, v in sorted(self.tuning.items()))
        mode = f"stream(chunk={self.chunk})" if self.streaming else "batch"
        how = ""
        if self.impl in registry.PALLAS_IMPLS:   # say how the kernel runs
            how = (" Mosaic-compiled" if self.backend == "tpu"
                   else " interpreted")
        return (f"{self.impl}[{t}]{how} {mode} on {self.backend}: "
                f"{self.reason}")


def default_backend() -> str:
    return jax.default_backend()


def _pick_impl(backend: str, n: int,
               n_groups: Optional[int] = None) -> Tuple[str, str]:
    if n_groups is not None:
        measured = measured_impl(backend, n, n_groups)
        if measured is not None:
            return measured, ("persisted autotune measurement "
                              f"({autotune_cache_path()})")
    if backend == "gpu":
        return "brute", "GPU cores prefer brute force (paper Fig. 1)"
    if backend == "tpu":
        if n >= PALLAS_MIN_N:
            return "pallas_matmul", "MXU one-hot contraction past ridge point"
        return "matmul", "problem too small for kernel tiles; XLA matmul form"
    # cpu and anything unknown
    mat2_bytes = 4 * n * n
    if backend == "cpu" and mat2_bytes > CPU_LLC_BYTES:
        return "tiled", (f"mat2 {mat2_bytes/2**20:.0f}MiB spills the "
                         f"{CPU_LLC_BYTES/2**20:.0f}MiB LLC; cache-tiled "
                         "Algorithm 2 wins on CPU (paper Fig. 1)")
    return "matmul", "mat2 cache-resident; one-hot BLAS form amortizes reads"


def chunk_for_budget(n: int, n_perms: int, impl: registry.SwImpl,
                     n_groups: int,
                     budget_bytes: Optional[float] = None,
                     n_cols: Optional[int] = None) -> int:
    """Largest permutation chunk whose LABEL tensor fits the budget.

    The budget governs the streamed state — (chunk, n) int32 labels plus the
    per-perm output — which is the only term that scales with n_perms. The
    resident mat2 and the impl's per-block working set are paid regardless
    of chunking and are deliberately not charged against it (n_groups and
    impl are kept in the signature for footprint-aware callers/tests).

    Dense designs (n_cols = K basis columns) stream a bigger state: the
    (chunk, n) int32 index permutations PLUS the gathered (chunk, n, K)
    f32 basis factor — the workset is sized for K design columns instead
    of G groups, so the chunk shrinks accordingly."""
    del n_groups  # labels dominate the streamed state; see docstring
    budget = DEFAULT_STREAM_BUDGET_BYTES if budget_bytes is None else budget_bytes
    per_perm = 4.0 * n + 8.0
    if n_cols is not None:
        per_perm += 4.0 * n * n_cols + 4.0 * n_cols
    if MIN_CHUNK * per_perm > budget:
        warnings.warn(
            f"label budget {budget/2**20:.2f}MiB cannot hold even the "
            f"minimum chunk ({MIN_CHUNK} perms x {4*n} label bytes) at "
            f"n={n}; proceeding with chunk={MIN_CHUNK} — label memory will "
            f"exceed the budget (impl {impl.name!r})",
            stacklevel=2)
        return min(MIN_CHUNK, n_perms)
    chunk = max(MIN_CHUNK, int(budget // per_perm))
    return min(chunk, n_perms)


def plan(n: int, n_perms: int, n_groups: int, *,
         backend: Optional[str] = None,
         memory_budget_bytes: Optional[float] = None,
         chunk: Optional[int] = None,
         impl: Optional[str] = None,
         tuning: Optional[Dict[str, int]] = None,
         n_cols: Optional[int] = None) -> Plan:
    """Resolve impl + tuning + streaming chunk for one problem.

    n_perms counts TOTAL permutation slots (i.e. n_perms_requested + 1 for
    the observed labels at index 0). `impl`/`chunk` pin those choices and
    let the planner fill in the rest.

    n_cols: set to the design-basis width K for DENSE designs
    (covariates/weights/multi-factor): impl choice is restricted to the
    matmul-family forms that carry a dense companion (the contraction is
    matmul-native; label-equality dataflows like `tiled` do not apply),
    and the streaming chunk is sized for the (chunk, n, K) basis factor.
    """
    backend = backend or default_backend()
    if impl is None:
        if n_cols is not None:
            name, reason = _pick_impl_design(backend)
        else:
            name, reason = _pick_impl(backend, n, n_groups)
    else:
        name, reason = impl, "caller-pinned impl"
    if n_cols is not None:
        resolved_name, _ = registry.resolve_cols(name)
        if resolved_name != name:
            reason += (f"; {name!r} is label-only, dense design runs its "
                       f"{resolved_name!r} companion")
            name = resolved_name
    spec = registry.get(name)
    resolved = dict(spec.tuning)
    if tuning:
        resolved.update({k: v for k, v in tuning.items() if k in resolved})
    if chunk is None:
        chunk = chunk_for_budget(n, n_perms, spec, n_groups,
                                 memory_budget_bytes, n_cols=n_cols)
    chunk = max(1, min(int(chunk), n_perms))
    return Plan(impl=name, backend=backend, tuning=resolved, chunk=chunk,
                streaming=chunk < n_perms, reason=reason)


def _pick_impl_design(backend: str) -> Tuple[str, str]:
    """Impl for DENSE designs: the per-column contraction is a tiled
    matmul against mat2 on every backend except the GPU, where the
    re-streaming brute dataflow mirrors the paper's Fig. 1 result."""
    if backend == "gpu":
        return "brute", ("dense design, GPU: per-perm re-stream "
                         "(Fig. 1 brute analogue)")
    return "matmul", ("dense design: per-column matmul contraction "
                      "(hat-matrix blocks on the MXU/BLAS path)")


# ---------------------------------------------------------------------------
# Empirical autotuner: measure-and-cache on the real operands. Winners are
# memoized in-process AND persisted per host to a JSON cache, which is
# loaded lazily at first plan() and fed back into the heuristic defaults —
# so a serving host pays each measurement once EVER, not once per process.
# ---------------------------------------------------------------------------

_AUTOTUNE_CACHE: Dict[tuple, str] = {}
_PERSIST: Optional[Dict[str, dict]] = None   # lazy-loaded disk cache
_DIRTY: set = set()                          # keys THIS process measured
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# Entry schema for the dist|/fusedk| key families. Schema 2 adds the
# precision knobs (feat_bf16/feat_fp8/feat_packed) to the fused cache
# keys and tuning payloads; pre-PR6 entries carry no schema field and
# could silently pin fp32 tile shapes onto fp8/packed runs, so they are
# dropped on load (migrate-or-drop). The s_W shoot-out keys
# ('<backend>|n..|g..') predate and outlive the schema — they are kept.
CACHE_SCHEMA = 2


def _valid_entry(key: str, val) -> bool:
    if not (isinstance(val, dict) and "impl" in val):
        return False
    if key.startswith(("dist|", "fusedk|")):
        return val.get("schema") == CACHE_SCHEMA
    return True


def _bucket(n: int) -> int:
    """Shape bucket: next power of two (timings are stable within one)."""
    b = 1
    while b < n:
        b *= 2
    return b


def autotune_cache_path() -> Optional[str]:
    """Per-host cache file; $REPRO_AUTOTUNE_CACHE overrides ('off' disables)."""
    override = os.environ.get(AUTOTUNE_CACHE_ENV)
    if override:
        return None if override.lower() in ("off", "none", "0") else override
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def _persist_key(backend: str, n: int, n_groups: int) -> str:
    return f"{backend}|n{_bucket(n)}|g{n_groups}"


def measured_entry(key: str) -> Optional[dict]:
    """One persisted measurement by raw domain key.

    The cache is shared beyond the s_W shoot-outs: the pipeline planner
    persists stage-1 distance and fused-kernel candidate timings under
    'dist|<backend>|<metric>|<impl>' / 'fusedk|<backend>|<metric>|<impl>'
    keys (satellite of the megakernel PR) and reads them back through
    this accessor to seed its defaults."""
    return load_autotune_cache().get(key)


def record_entry(key: str, entry: dict) -> None:
    """Persist one measurement under an arbitrary domain key.

    `entry` must carry an 'impl' field (the load/save filters key on it);
    dist|/fusedk| entries are stamped with the current CACHE_SCHEMA so
    stale-schema entries from older code are dropped on load.
    Same merge-on-save/best-effort semantics as the s_W autotune path."""
    if "impl" not in entry:
        raise ValueError("autotune cache entries must carry an 'impl' field")
    entry = dict(entry)
    entry.setdefault("schema", CACHE_SCHEMA)
    cache = load_autotune_cache()   # BEFORE marking dirty: the first load
    _DIRTY.add(key)                 # in a process clears _DIRTY
    cache[key] = entry
    _save_autotune_cache()


def load_autotune_cache(*, reload: bool = False) -> Dict[str, dict]:
    """Measurements persisted by previous processes on this host."""
    global _PERSIST
    if _PERSIST is not None and not reload:
        return _PERSIST
    _PERSIST = {}
    _DIRTY.clear()   # fresh view: prior writes belong to the old file
    path = autotune_cache_path()
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError(f"expected a JSON object, got "
                                 f"{type(data).__name__}")
            _PERSIST = {k: v for k, v in data.items()
                        if _valid_entry(k, v)}
            dropped = len(data) - len(_PERSIST)
            if dropped:
                _metrics.inc("autotune.cache.stale_dropped", dropped)
                _warn_once(
                    "stale", f"autotune cache {path}: dropped {dropped} "
                    f"entr{'y' if dropped == 1 else 'ies'} with a stale "
                    f"schema (current schema {CACHE_SCHEMA}); they will "
                    "be re-measured")
        except (OSError, ValueError) as e:
            # Corrupt or unreadable (typically a crash mid-write truncated
            # the document): QUARANTINE the file so the next writer starts
            # clean and the evidence survives for debugging, then proceed
            # with an empty cache — a serving process must never die over
            # a cache. Warn once per process.
            _quarantine_corrupt_cache(path, e)
    return _PERSIST


def _quarantine_corrupt_cache(path: str, err: Exception) -> None:
    quarantined = f"{path}.corrupt"
    try:
        os.replace(path, quarantined)
        where = f"; quarantined to {quarantined}"
    except OSError:
        where = " (quarantine rename failed; leaving in place)"
    _metrics.inc("autotune.cache.corrupt_quarantined")
    _warn_once("corrupt",
               f"autotune cache {path} is corrupt ({err}); continuing "
               f"with an empty cache{where}. Entries will be re-measured.")


def _save_autotune_cache() -> None:
    global _PERSIST
    path = autotune_cache_path()
    if not path:
        _warn_once(
            "disabled", f"autotune cache disabled (${AUTOTUNE_CACHE_ENV}); "
            "measurements will not persist across processes")
        return
    if _PERSIST is None:
        return
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # Merge-on-save: re-read and overlay only the keys THIS process
        # measured (not stale loaded copies). Best-effort, not locked — two
        # processes replacing simultaneously can still drop one bucket
        # (TOCTOU between the read and os.replace); the loser simply
        # re-measures on its next run.
        on_disk: Dict[str, dict] = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
                if isinstance(data, dict):
                    on_disk = {k: v for k, v in data.items()
                               if _valid_entry(k, v)}
            except (OSError, ValueError):
                pass
        ours = {k: v for k, v in _PERSIST.items() if k in _DIRTY}
        _PERSIST = {**on_disk, **ours}
        # Atomic publish: serialize to a per-pid temp file, fsync, then
        # os.replace. Readers (and the merge-read above) can only ever
        # observe a complete JSON document — concurrent writers cannot
        # interleave partial writes (the two-writer regression test in
        # tests/test_autotune_cache.py hammers exactly this).
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(_PERSIST, f, indent=2, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)            # atomic on POSIX
        except BaseException:
            try:
                os.unlink(tmp)               # never leave partial temps
            except OSError:
                pass
            raise
    except OSError:  # read-only home etc. — cache is best-effort
        pass


def _default_candidates(backend: str):
    cands = registry.names(kind="jnp")
    if backend == "tpu":
        cands = list(cands) + registry.names(kind="pallas")
    return cands


def measured_impl(backend: str, n: int, n_groups: int,
                  candidates: Optional[Sequence[str]] = None) -> Optional[str]:
    """Persisted winner for this (backend, shape-bucket, groups), if any.

    Only trusted when it was measured over (at least) the requested
    candidate set — a winner from a restricted shoot-out must not
    short-circuit a broader one — and when the impl is still registered."""
    entry = load_autotune_cache().get(_persist_key(backend, n, n_groups))
    if not entry:
        _metrics.inc("autotune.cache.miss")
        return None
    wanted = set(candidates if candidates is not None
                 else _default_candidates(backend))
    if not wanted <= set(entry.get("candidates", ())):
        _metrics.inc("autotune.cache.miss")
        return None
    name = entry.get("impl")
    try:
        registry.get(name)
    except KeyError:
        _metrics.inc("autotune.cache.miss")
        return None
    _metrics.inc("autotune.cache.hit")
    return name


def autotune(mat2, grouping, inv_gs, *,
             candidates: Optional[Sequence[str]] = None,
             sample_perms: int = 16,
             key: Optional[jax.Array] = None,
             backend: Optional[str] = None,
             use_cache: bool = True) -> str:
    """Time each candidate impl on a small permutation sample of the actual
    operands and return the fastest name. Winners are memoized per
    (backend, n-bucket, n_groups) so steady-state callers measure once."""
    from repro.core import permutations  # local: avoid import cycle at load

    backend = backend or default_backend()
    n = int(mat2.shape[0])
    n_groups = int(inv_gs.shape[0])
    if candidates is None:
        candidates = _default_candidates(backend)
    cache_key = (backend, _bucket(n), n_groups, tuple(sorted(candidates)))
    if use_cache:
        if cache_key in _AUTOTUNE_CACHE:
            _metrics.inc("autotune.cache.hit")
            return _AUTOTUNE_CACHE[cache_key]
        persisted = measured_impl(backend, n, n_groups, candidates)
        if persisted in candidates:
            _AUTOTUNE_CACHE[cache_key] = persisted
            return persisted

    if key is None:
        key = jax.random.key(0)
    gperms = permutations.permutation_batch(key, grouping, 0, sample_perms)
    best_name, best_t = None, float("inf")
    times_us: Dict[str, float] = {}
    for name in candidates:
        fn = jax.jit(registry.get(name).bound())
        try:
            jax.block_until_ready(fn(mat2, gperms, inv_gs))  # compile+warm
            t0 = time.perf_counter()
            jax.block_until_ready(fn(mat2, gperms, inv_gs))
            t = time.perf_counter() - t0
        except Exception as exc:  # noqa: BLE001 — counted and logged
            record_candidate_failure("autotune", name, exc)
            continue
        times_us[name] = round(t * 1e6, 1)
        if t < best_t:
            best_name, best_t = name, t
    if best_name is None:
        raise RuntimeError("autotune: no candidate impl ran successfully")
    _metrics.inc("autotune.measured")
    if use_cache:
        _AUTOTUNE_CACHE[cache_key] = best_name
        pkey = _persist_key(backend, n, n_groups)
        prior = load_autotune_cache().get(pkey)
        # never let a restricted shoot-out overwrite a broader measurement
        if prior is None or not \
                set(candidates) < set(prior.get("candidates", ())):
            _DIRTY.add(pkey)
            load_autotune_cache()[pkey] = {
                "impl": best_name,
                "candidates": sorted(candidates),
                "times_us": times_us,
                "n": n,
                "n_groups": n_groups,
                "sample_perms": sample_perms,
            }
            _save_autotune_cache()
    return best_name
