"""JAX's persistent compilation cache, placed where the caller can find it.

A cold TPU run compiles every program from scratch; with the cache on, a
later process that builds the same programs reads them back instead.  The
cache key includes the directory, so the directory must not move between
runs: it is `JAX_COMPILATION_CACHE_DIR` when that is set (JAX reads the
variable itself, and nothing here overrides it), and otherwise the fixed
`<checkout>/.jax_cache`, which .gitignore lists.  Entry points call
`enable_compile_cache()` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    jax.config.update("jax_enable_compilation_cache", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
