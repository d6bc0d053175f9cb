"""The entry points' persistent compilation cache: JAX's own variable
when it is set, else one fixed, git-ignored path inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, "x"))
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_ignored(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    path = jax.config.jax_compilation_cache_dir
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
