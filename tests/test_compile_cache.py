"""The persistent compile cache: $JAX_COMPILATION_CACHE_DIR when set
(nothing else named in code), else one fixed directory in the checkout
that git ignores."""

import os

import jax

from repro import compile_cache

from conftest import REPO


def _restore(prev):
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def test_env_dir_wins_and_code_sets_none(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    try:
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        _restore(prev)


def test_fixed_dir_in_checkout_is_ignored(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.enable()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        _restore(prev)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
