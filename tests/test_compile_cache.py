"""The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
else to one fixed, git-ignored directory of the checkout."""

import os

import jax

from repro.utils import compile_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(compile_cache.DEFAULT_DIR)
    assert path == os.path.join(CHECKOUT, ".jax_cache")
    with open(os.path.join(CHECKOUT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
