"""Executables JAX compiled or loaded from the persistent cache while the
profiler recorded, which in a ``--trace 1`` run is the window: the program's
``repro.launch.compile_cache.compile_count(traced=True)``. Warm-up runs every
program the window runs, so this should read 0. Nothing to read where the
program has no such counter."""


def read(run):
    if run.trace is None:
        return None
    try:
        from repro.launch.compile_cache import compile_count
    except ImportError:
        return None
    return compile_count(traced=True)
