"""Sequential sort models from paper Fig 1, in TPU-expressible form.

The paper compares three sequential sorts (Fig 5) and picks Quicksort as the
per-worker sort. On a vector machine the roles map as:

* Fig 1(a) recursive Merge sort      -> host-side reference (numpy), used only
  by the Fig-5 benchmark as the paper's slow baseline. Recursion is not
  jax-traceable and is precisely what the paper itself moves away from.
* Fig 1(b) non-recursive Merge sort  -> ``nonrecursive_merge_sort``: bottom-up
  width-doubling rounds of vectorized stable rank-merges. Fixed schedule,
  jit-compatible — this *is* a TPU-idiomatic algorithm as published.
* Fig 1(c) recursive Quicksort       -> ``fast_local_sort``: the role "fastest
  available sequential sort" is played by XLA's variadic sort on CPU/TPU and
  by the Pallas bitonic kernel inside kernels/. (DESIGN.md §7: the hybrid
  structure, not quicksort's recursion, is the paper's transferable insight.)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .bitonic import bitonic_sort
from .merge import merge_adjacent

__all__ = [
    "recursive_merge_sort_host",
    "nonrecursive_merge_sort",
    "pallas_local_sort",
    "fast_local_sort",
    "LOCAL_SORTS",
]


def recursive_merge_sort_host(x: np.ndarray) -> np.ndarray:
    """Paper Fig 1(a), host-side reference implementation (numpy, recursive)."""
    x = np.asarray(x)
    if x.shape[-1] <= 2:
        return np.sort(x, axis=-1, kind="stable")
    mid = x.shape[-1] // 2
    left = recursive_merge_sort_host(x[..., :mid])
    right = recursive_merge_sort_host(x[..., mid:])
    out = np.empty_like(x)
    # vectorized two-list merge via ranks (same identity as merge.py)
    la = left.shape[-1]
    pos_a = np.arange(la) + _np_searchsorted(right, left, side="left")
    pos_b = np.arange(right.shape[-1]) + _np_searchsorted(left, right, side="right")
    np.put_along_axis(out, pos_a, left, axis=-1)
    np.put_along_axis(out, pos_b, right, axis=-1)
    return out


def _np_searchsorted(sorted_arr, query, side):
    flat_s = sorted_arr.reshape(-1, sorted_arr.shape[-1])
    flat_q = query.reshape(-1, query.shape[-1])
    out = np.stack(
        [np.searchsorted(s, q, side=side) for s, q in zip(flat_s, flat_q)]
    )
    return out.reshape(query.shape)


@partial(jax.jit, static_argnames=("ascending",))
def nonrecursive_merge_sort(x: jax.Array, *, ascending: bool = True) -> jax.Array:
    """Paper Fig 1(b): bottom-up merge sort, each round fully vectorized.

    Pads to a power of two with sentinels; log2(n) rounds of ``merge_adjacent``.
    Stable (every merge keeps left-run-first ties).
    """
    from .bitonic import next_pow2, sentinel_for

    n = x.shape[-1]
    np2 = next_pow2(n)
    if np2 != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, np2 - n)]
        x = jnp.pad(x, pad, constant_values=sentinel_for(x.dtype, largest=True))
    width = 1
    while width < np2:
        x = merge_adjacent(x, width)
        width *= 2
    x = x[..., :n]
    return x if ascending else jnp.flip(x, axis=-1)


def pallas_local_sort(
    x: jax.Array, *, ascending: bool = True, block_n: int | None = None
) -> jax.Array:
    """Shape-safe wrapper over the Pallas VMEM bitonic kernel.

    Accepts any last-axis length >= 1 and arbitrary leading batch dims:
    non-pow2 lengths are padded with +sentinel keys (``pallas_sort`` does the
    pad/slice), batches run via ``vmap`` over a flattened leading axis, and
    descending order flips the valid prefix after the ascending kernel so
    pad sentinels never leak to the front.  Off-TPU the kernels execute in
    interpret mode (``pallas_sort``'s auto-detection), so the same code path
    is testable on CPU and fast on real TPUs.
    """
    from repro.kernels.bitonic_sort.ops import (
        DEFAULT_BLOCK_N,
        pallas_sort,
        vmap_last_axis,
    )

    bn = DEFAULT_BLOCK_N if block_n is None else block_n
    out = vmap_last_axis(partial(pallas_sort, block_n=bn), x)
    return out if ascending else jnp.flip(out, axis=-1)


def fast_local_sort(
    x: jax.Array,
    *,
    ascending: bool = True,
    impl: str = "xla",
    block_n: int | None = None,
) -> jax.Array:
    """The "sequential Quicksort" role: fastest single-worker sort available.

    impl='xla'     -> XLA variadic sort (the platform's tuned local sort)
    impl='bitonic' -> our branch-free network, pure-jnp form
    impl='pallas'  -> the same network as a VMEM-tiled Pallas kernel
                      (``block_n`` tunes the tile width; interpret mode off-TPU)
    impl='merge'   -> paper Fig 1(b) non-recursive merge sort

    NaN keys: only 'xla' totally orders NaN; the network impls ('bitonic',
    'pallas') leave output unspecified for NaN — reject NaN upstream
    (SortService does) or use 'xla'.
    """
    if impl == "xla":
        out = jnp.sort(x, axis=-1)
        return out if ascending else jnp.flip(out, axis=-1)
    if impl == "bitonic":
        return bitonic_sort(x, ascending=ascending)
    if impl == "pallas":
        return pallas_local_sort(x, ascending=ascending, block_n=block_n)
    if impl == "merge":
        return nonrecursive_merge_sort(x, ascending=ascending)
    raise ValueError(f"unknown local sort impl {impl!r}")


LOCAL_SORTS = ("xla", "bitonic", "pallas", "merge")
