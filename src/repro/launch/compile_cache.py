"""Placement of JAX's persistent compilation cache.

Entry points call `enable_compile_cache` once at start-up (never at import):
a cold run then writes every compiled program to disk, and a later process
on the same checkout loads them instead of compiling again.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout: the cache directory is part of what a
# later run must find again, so it never depends on a temp name, pid or time
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    When ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    `DEFAULT_CACHE_DIR` (``<repo>/.jax_cache``, listed in .gitignore).
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
