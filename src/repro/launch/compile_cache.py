"""Where JAX's persistent compilation cache lives.

Entry points call ``setup_compile_cache()`` before their first compile —
never at import. A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it
itself and nothing is set here. Otherwise the cache goes to ``.jax_cache``
at the root of the checkout, resolved from this file, so every process of
every run of this checkout finds the same entries (the directory is part
of the cache key: a path that moves never hits).
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on at its fixed place and
    return the directory JAX will use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
