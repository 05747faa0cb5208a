"""Paper model C: Distributed Memory Parallel Hybrid Quicksort and Merge Sort.

MPI nodes -> mesh devices; MPI send/recv -> ``jax.lax.ppermute`` inside
``shard_map``. The schedule is Fig 3 verbatim:

  1. every node sorts its partition with the fast local sort ("Quicksort"),
  2. log2(P) rounds: node ``i`` with ``i % 2^(r+1) == 2^r`` ships its whole
     buffer to node ``i - 2^r``, which merges it into its own buffer,
  3. after the last round node 0 holds the fully sorted data.

We keep the paper's flaw on purpose (DESIGN.md §7): every device must hold an
n-sized buffer and half the active devices idle each round — this is the
*faithful distributed baseline* that model D (cluster_sort.py) beats. SPMD has
no variable-length sends, so idle devices carry sentinel-padded buffers and the
merge happens unconditionally with a ``where`` select (uniform cost, same as
the paper's lock-step rounds).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.exchange import compact_slabs

from .bitonic import sentinel_for
from .merge import merge_sorted_pair
from .seqsort import fast_local_sort

__all__ = ["distributed_merge_sort", "merge_tree_local"]


def merge_tree_local(
    local: jax.Array,
    axis_name: str,
    *,
    local_impl: str = "xla",
    block_n: int | None = None,
):
    """Body to run inside shard_map. ``local``: (m,) shard of the global array.

    Returns the (n,)-sized buffer per device; device 0's buffer is the sorted
    result, other devices' tails are sentinels (the paper's idle nodes).
    """
    P_ = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = local.shape[-1]
    n = m * P_
    sent = sentinel_for(local.dtype, largest=True)

    # Fig 3 step 2: local "Quicksort"
    local = fast_local_sort(local, ascending=True, impl=local_impl, block_n=block_n)
    buf = jnp.concatenate([local, jnp.full((n - m,), sent, local.dtype)])

    # Fig 3 steps 3–5: binary merge tree
    rounds = P_.bit_length() - 1
    for r in range(rounds):
        d = 1 << r
        perm = [(i, i - d) for i in range(P_) if i % (2 * d) == d]
        received = jax.lax.ppermute(buf, axis_name, perm)  # zeros if not a target
        merged = merge_sorted_pair(buf, received)[..., :n]
        is_receiver = idx % (2 * d) == 0
        buf = jnp.where(is_receiver, merged, buf)
    return buf


def distributed_merge_sort(
    x: jax.Array,
    mesh,
    axis: str,
    *,
    local_impl: str = "xla",
    block_n: int | None = None,
):
    """Sort 1-D ``x`` (length divisible by mesh axis size) across ``mesh[axis]``.

    Returns the sorted array (device 0's buffer, sharded on ``axis``). Memory
    cost is O(n) *per device* — the paper's design; use ``cluster_sort`` for
    the scalable path. ``block_n`` tunes ``local_impl='pallas'``.
    """
    n = x.shape[-1]
    P_ = mesh.shape[axis]
    if n % P_:
        raise ValueError(f"n={n} must divide device count {P_}")

    out = _compiled_merge_tree(mesh, axis, local_impl, block_n)(x)
    # device 0's buffer occupies the first n entries of the (P*n,) output
    return compact_slabs(out, jnp.arange(P_ * n) < n, n, mesh, axis)


@lru_cache(maxsize=64)
def _compiled_merge_tree(mesh, axis, local_impl, block_n=None):
    """Cache the jitted shard_map so repeated calls don't re-trace."""
    body = partial(
        merge_tree_local, axis_name=axis, local_impl=local_impl, block_n=block_n
    )
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    )
