"""Persistent compilation cache for the entry points.

A cold process recompiles the whole decode step for every prefill and
accept-width bucket; the cache lets the next process on the same
machine load them instead. Entry points call `enable_compile_cache()`
before their first compile; importing this module changes nothing.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
other directory is set here. Otherwise the cache lives at the fixed
path `<repo>/.jax_cache` (git-ignored): JAX keys entries by what is
compiled, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
