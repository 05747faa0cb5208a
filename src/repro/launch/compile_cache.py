"""Where compiled programs persist between runs, and how many were made: the
one compile policy.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout. The path is part of every cache entry's key, so it is fixed: a
directory named after a PID, a time or a temporary name would never hit.

The key includes each program's metadata (its op names, where the
``repro.*`` named scopes live): under a metadata-blind key a build whose
scopes changed would load an older build's executable, and a profile of it
would name the older build's phases.

``compile_count`` counts the executables JAX compiled or loaded from the
persistent cache in this process, from a ``jax.monitoring`` listener
registered when this module is imported.
"""
from __future__ import annotations

import os
import threading

import jax
import jax.monitoring

# src/repro/launch/compile_cache.py -> the checkout's root
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))

# fired once per executable JAX obtains, compiled or read from the cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_counts = {"all": 0, "traced": 0}
_counts_lock = threading.Lock()


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    traced = jax.profiler.TraceAnnotation.is_enabled()
    with _counts_lock:
        _counts["all"] += 1
        _counts["traced"] += int(traced)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count(*, traced: bool = False) -> int:
    """Executables compiled or loaded from the persistent cache since this
    module was imported; with ``traced=True`` only those obtained while a
    profiler session was recording (a compile inside a traced window)."""
    with _counts_lock:
        return _counts["traced" if traced else "all"]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
