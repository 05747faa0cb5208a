"""Where compiled programs persist between runs: the one compile-cache policy.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout. The path is part of every cache entry's key, so it is fixed: a
directory named after a PID, a time or a temporary name would never hit.
"""
from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout's root
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
