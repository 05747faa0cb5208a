"""Paper models A & B: shared-memory parallel sort (OpenMP -> single-chip SPMD).

The OpenMP "threads" of Fig 2 become T independent tiles of one device's
array. Phase 1 sorts every tile in parallel (vmapped local sort / Pallas
kernel); phase 2 runs the paper's binary merge tree — log2(T) rounds where
round r merges adjacent sorted runs of width n/T * 2^r. On a vector machine
all surviving "threads" of a round execute as one vectorized ``merge_adjacent``
call, so the paper's idling of half the threads per round costs nothing here —
but the *schedule* (width-doubling pairwise merges) is exactly Fig 2.

Model A: local sort = non-recursive merge sort     (paper 3.2 first variant)
Model B: local sort = "quicksort" role (XLA sort / bitonic) — the hybrid that
         wins in the paper (Fig 6) and that we default to everywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .bitonic import next_pow2, sentinel_for
from .merge import merge_adjacent
from .seqsort import fast_local_sort

__all__ = ["shared_memory_sort"]


@partial(jax.jit, static_argnames=("n_threads", "local_impl", "ascending", "block_n"))
def shared_memory_sort(
    x: jax.Array,
    *,
    n_threads: int = 8,
    local_impl: str = "xla",
    ascending: bool = True,
    block_n: int | None = None,
) -> jax.Array:
    """Sort the last axis with the paper's shared-memory algorithm.

    n_threads must be a power of two (paper: "works with a power of two number
    of threads"). Arbitrary n is handled by sentinel padding. ``block_n`` is
    the VMEM tile width for ``local_impl='pallas'`` (ignored otherwise).
    """
    if n_threads & (n_threads - 1) or n_threads < 1:
        raise ValueError("n_threads must be a power of two (paper §3.2)")
    *lead, n = x.shape
    np2 = max(next_pow2(n), n_threads)
    if np2 != n:
        # pad with +sentinel; ascending internal sort keeps pads at the end
        pad = [(0, 0)] * (x.ndim - 1) + [(0, np2 - n)]
        x = jnp.pad(x, pad, constant_values=sentinel_for(x.dtype, largest=True))
    tile = np2 // n_threads

    # Phase 1 — every "thread" sorts its tile (Fig 2 step: call sorting function)
    tiles = x.reshape(*lead, n_threads, tile)
    with jax.named_scope("repro.tile_sort"):
        tiles = fast_local_sort(tiles, ascending=True, impl=local_impl, block_n=block_n)
    x = tiles.reshape(*lead, np2)

    # Phase 2 — binary merge tree (Fig 2 steps a–d), one round per doubling
    width = tile
    while width < np2:
        with jax.named_scope("repro.merge"):
            x = merge_adjacent(x, width)
        width *= 2
    x = x[..., :n]
    return x if ascending else jnp.flip(x, axis=-1)
