"""JAX's persistent compilation cache, kept at one fixed path.

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()   # before the first compile

The cache key includes the directory, so a directory that moves never
hits.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here changes it; otherwise the cache lives in ``.jax_cache/``
at the checkout root, resolved from this file's own path, so every
process of every run from one checkout shares it.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
