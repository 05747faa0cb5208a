"""Vectorized merge of sorted runs — the paper's "merge & sort function".

``merge_pairs`` merges two sorted lists of length w with one stable
``lax.sort`` of their concatenation: the left run comes first, so equal keys
keep left-run-first order, which is merge sort's defining stability.  On TPU
this is XLA's sort network over the pair; the rank formulation it replaced
(output position = own index + rank in the other run, then a scatter) ran at
a few million keys per second on a v5e, so one merge round at 2^27 keys took
over a minute.

``bitonic`` merge (see ``bitonic.py``) is the branch-free compare-exchange
network used inside the Pallas kernel.

``merge_adjacent`` performs one round of the paper's bottom-up merge: an array
viewed as ``r`` sorted runs of width ``w`` becomes ``r/2`` sorted runs of width
``2w``. Repeating it is exactly Fig 1(b)'s non-recursive merge sort and the
"All Threads" merge loop of Fig 2/Fig 3.

A round whose sort has fewer than 8 rows (the run pairs times any leading
batch dims) sorts each pair as its own 1-D array instead. A v5e int32 tile
has 8 sublanes, and a sort along the last axis of ``[k, m]`` with k < 8 left
8/k of them idle: model B's rounds at 2^27 keys cost 4.5, 8.7 and 17 ns a
key as ``[4, 2^25]``, ``[2, 2^26]`` and ``[1, 2^27]``, against 2.8 for the
``[8, 2^24]`` tile sort, where a 1-D sort of 2^27 keys costs about 3.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["merge_pairs", "merge_adjacent", "merge_sorted_pair"]


# int32 sublanes of a v5e vector tile: a sort along the last axis of fewer
# rows than this leaves the rest of the tile idle
_SUBLANES = 8


@partial(jax.jit, static_argnames=("has_values",))
def _merge(pairs, values, *, has_values: bool):
    """pairs: (..., 2, w) two sorted runs -> (..., 2w) merged, stable."""
    *lead, _, w = pairs.shape
    m = 2 * w
    leaves, treedef = jax.tree.flatten(values)
    ops = [a.reshape(*lead, m) for a in (pairs, *leaves)]
    rows = math.prod(lead)
    if 0 < rows < _SUBLANES:
        # each row its own 1-D sort, sliced from and joined back into 1-D
        flat = [a.reshape(-1) for a in ops]
        parts = [
            jax.lax.sort([a[i * m:(i + 1) * m] for a in flat], is_stable=True, num_keys=1)
            for i in range(rows)
        ]
        out = [jnp.concatenate(p).reshape(*lead, m) for p in zip(*parts)]
    else:
        out = jax.lax.sort(ops, dimension=len(lead), is_stable=True, num_keys=1)
    return out[0], (jax.tree.unflatten(treedef, out[1:]) if has_values else None)


def merge_pairs(pairs, values=None):
    """Merge (..., 2, w) sorted-run pairs into (..., 2w) stably; ``values``
    leaves shaped like ``pairs`` ride along."""
    out, vals = _merge(pairs, values, has_values=values is not None)
    return out if values is None else (out, vals)


def merge_sorted_pair(a, b, va=None, vb=None):
    """Stable merge of two sorted arrays along the last axis (equal length)."""
    pairs = jnp.stack([a, b], axis=-2)
    if va is None:
        return merge_pairs(pairs)
    values = jax.tree.map(lambda x, y: jnp.stack([x, y], axis=-2), va, vb)
    return merge_pairs(pairs, values)


def merge_adjacent(x, width: int, values=None):
    """One bottom-up merge round: sorted runs of ``width`` -> runs of ``2*width``.

    ``x``: (..., n) with n % (2*width) == 0 and each aligned ``width`` slice
    already sorted. Vectorizes the paper's per-round pairwise merges across all
    run pairs at once (all "threads" of a round in one shot).
    """
    *lead, n = x.shape
    assert n % (2 * width) == 0, (n, width)
    # keep XLA from fusing whatever produced the runs (a sort, the previous
    # round) into this round's merge: for a v5e that fusion compiled for
    # minutes at 2^27 keys
    x, values = jax.lax.optimization_barrier((x, values))
    pairs = x.reshape(*lead, n // (2 * width), 2, width)
    if values is None:
        merged = merge_pairs(pairs)
        return merged.reshape(*lead, n)
    vals = jax.tree.map(lambda v: v.reshape(*lead, n // (2 * width), 2, width), values)
    merged, mvals = merge_pairs(pairs, vals)
    return merged.reshape(*lead, n), jax.tree.map(
        lambda v: v.reshape(*lead, n), mvals
    )
