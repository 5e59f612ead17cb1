"""Where compiled programs are kept between processes.

`enable()` is called by every entry point before its first compile
(`launch/permanova.py`, `launch/serve.py`, `benchmarks/run.py`,
`chip_smoke.py`). If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing here names another directory. Otherwise the cache is
one fixed directory inside the checkout, `<repo>/.jax_cache` (git ignores
it). The path is fixed because it is part of the cache key: a directory
that moves never hits.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
