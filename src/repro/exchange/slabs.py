"""Slab and capacity math shared by model-D sort and MoE dispatch.

SPMD has no ragged sends, so every exchange ships fixed-capacity,
sentinel-padded slabs per (sender, bucket) pair.  All capacity rounding in
the codebase flows through ``slab_capacity`` — ``slab_geometry`` (model-D
sort) and ``expert_capacity`` (MoE dispatch) are two keyings of the same
formula, so the two paths can never drift apart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "compact_slabs",
    "expert_capacity",
    "sentinel_for",
    "slab_capacity",
    "slab_geometry",
    "slab_valid",
]


def sentinel_for(dtype, *, largest: bool):
    """Value that sorts after (largest) / before (smallest) all real keys —
    what exchange slabs and sort paddings are filled with.

    >>> import jax.numpy as jnp
    >>> int(sentinel_for(jnp.int32, largest=True)) == jnp.iinfo(jnp.int32).max
    True
    >>> float(sentinel_for(jnp.float32, largest=False))
    -inf
    """
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        v = jnp.inf if largest else -jnp.inf
    elif jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        v = info.max if largest else info.min
    else:
        raise TypeError(f"unsupported key dtype {dtype}")
    return jnp.asarray(v, dtype)


def slab_capacity(m: int, buckets: int, capacity_factor: float) -> int:
    """Per-(sender, bucket) slab capacity — THE capacity formula.

    A uniform sender spreads its ``m`` elements evenly, ~``m / buckets``
    per bucket; ``capacity_factor`` is the over-provisioning margin on top.
    Clamped below by 1 slot (a zero-capacity slab can never drain — and the
    retry driver's capacity doubling would pin 0 forever) and above by ``m``
    (one sender cannot put more than all its elements into a single bucket —
    ``capacity == m`` is the loss-free guarantee both the model-D retry
    driver and the MoE drop path rely on).  The 1-slot floor wins over the
    ``m`` ceiling for an *empty* sender: a drained rank (``m == 0``) still
    ships well-formed 1-slot slabs through the collective.

    >>> slab_capacity(1000, 8, 1.5)     # ceil(1500 / 8)
    188
    >>> slab_capacity(64, 4, 8.0)       # clamped to the loss-free bound m
    64
    >>> slab_capacity(64, 4, 0.001)     # floored at one slot
    1
    >>> slab_capacity(0, 8, 1.25)       # empty sender: floor beats the bound
    1
    """
    return max(1, min(m, -(-int(capacity_factor * m) // max(buckets, 1))))


def slab_geometry(mode: str, m: int, P_: int, capacity_factor: float):
    """Exchange geometry for model D: (part_buckets, n_buckets, capacity).

    ``part_buckets`` is what the partitioner emits (10 in the paper's decimal
    mode, P otherwise); ``n_buckets`` rounds it up to the nearest multiple of
    P so ``partition_exchange``'s ``B % P == 0`` contract holds for any node
    count (buckets 10..n_buckets-1 simply stay empty).  ``capacity`` is sized
    per *bucket* via ``slab_capacity`` — a uniform load puts ~m/part_buckets
    keys in each (sender, bucket) pair, so deriving it from P (the old
    behaviour) under-provisioned exactly when buckets outnumber shards.

    >>> slab_geometry("decimal", 1000, 4, 2.0)
    (10, 12, 200)
    >>> slab_geometry("splitters", 1000, 8, 1.5)
    (8, 8, 188)
    """
    part_buckets = 10 if mode == "decimal" else P_
    n_buckets = -(-part_buckets // P_) * P_
    return part_buckets, n_buckets, slab_capacity(m, part_buckets, capacity_factor)


def expert_capacity(tokens: int, top_k: int, n_experts: int,
                    capacity_factor: float) -> int:
    """Per-(sender, expert) token capacity for MoE dispatch.

    The MoE keying of ``slab_capacity``: a sender dispatches
    ``tokens * top_k`` (token, expert) assignments over ``n_experts``
    buckets.  Hoisted here so the GShard-style formula in ``models/moe.py``
    shares the sort path's rounding rules exactly (same ceil, same
    [1, m] clamp) instead of drifting as a re-derived copy.

    >>> expert_capacity(32, 2, 4, 2.0)      # ceil(2.0 * 64 / 4)
    32
    >>> expert_capacity(32, 2, 4, 0.01)     # floors at one slot
    1
    >>> expert_capacity(32, 2, 4, 8.0)      # clamped to tokens * top_k
    64
    >>> expert_capacity(0, 2, 8, 1.25)      # empty shard/microbatch: never 0
    1
    """
    return slab_capacity(tokens * top_k, n_experts, capacity_factor)


def slab_valid(total: int, counts, P_: int):
    """Validity mask over a gathered (P_ * C_total,) result slab.

    ``counts[p]`` is shard p's real element count; entries past it in shard
    p's ``C_total``-slot range are sentinel/zero padding.  This is how the
    retry driver's callers turn per-shard counts into the dense mask the
    engine compacts slabs with.

    >>> import jax.numpy as jnp
    >>> [bool(b) for b in slab_valid(4, jnp.array([1, 2]), 2)]
    [True, False, True, True]
    """
    C_total = total // P_
    pos = jnp.arange(total) % C_total
    return pos < jnp.repeat(counts, C_total)


def compact_slabs(tree, valid, n: int, mesh, axis: str):
    """Dense ``(n, ...)`` form of a gathered result slab, sharded on ``axis``.

    ``tree`` is an array or pytree of arrays laid out like a ``slab_valid``
    slab (``(P_ * C_total, ...)``, sharded ``P(axis)``) and ``valid`` marks a
    prefix of every shard's ``C_total`` slots, ``n`` entries in all.  The
    valid entries come back in slab order as ``(n, ...)`` arrays sharded
    ``P(axis)``.  The compaction is one jitted ``shard_map`` with static
    shapes, so it runs the same on Auto and Explicit mesh axes (an eager
    boolean-mask index does not).

    Source shard p's valid prefix is one run of dense positions, so the
    ``m = n / P_`` positions a shard keeps are at most ``P_`` contiguous
    pieces, one from each source's prefix.  Each shard all-gathers the slab,
    takes one length-``m`` ``dynamic_slice`` per source at the offset where
    that source's prefix meets its range, and picks between the ``P_``
    windows by comparing each position with the prefix starts: no index
    array, gather or scatter.  Trailing dims of a leaf ride along.

    >>> import jax, jax.numpy as jnp
    >>> mesh = jax.make_mesh((1,), ("x",))
    >>> slab = jnp.array([4, 7, 0, 0])
    >>> [int(v) for v in compact_slabs(slab, slab_valid(4, jnp.array([2]), 1), 2, mesh, "x")]
    [4, 7]
    """
    with jax.profiler.TraceAnnotation("repro.compact.dispatch"):
        return _compiled_compact(mesh, axis, n)(tree, valid)


@functools.lru_cache(maxsize=64)
def _compiled_compact(mesh, axis: str, n: int):
    P_ = mesh.shape[axis]
    if n % P_:
        raise ValueError(f"n={n} must divide axis size {P_}")
    m = n // P_

    @jax.named_scope("repro.compact")
    def body(tree, valid):
        C_total = valid.shape[0]
        counts = jax.lax.all_gather(jnp.sum(valid, dtype=jnp.int32), axis)
        starts = jnp.cumsum(counts) - counts
        lo = jax.lax.axis_index(axis) * m
        g = lo + jnp.arange(m, dtype=jnp.int32)
        # Dense position g of source p sits at slot p * C_total + g - starts[p]
        # of the gathered slab.  Where p owns any of [lo, lo + m) its window
        # lies inside the slab (starts[p] <= p * C_total, and the range's end
        # is at most n, which is starts[p] plus what shards p.. hold), so
        # dynamic_slice never clamps, and so never shifts, a piece in use.
        offsets = [lo + p * C_total - starts[p] for p in range(P_)]
        # the owner of g is the last source whose prefix starts at or before
        # g; an empty source ties with the next one, which then wins
        later = [g >= starts[p] for p in range(1, P_)]

        def leaf(a):
            whole = jax.lax.all_gather(a, axis, tiled=True)
            out = jax.lax.dynamic_slice_in_dim(whole, offsets[0], m)
            for p in range(1, P_):
                piece = jax.lax.dynamic_slice_in_dim(whole, offsets[p], m)
                pick = later[p - 1].reshape((m,) + (1,) * (a.ndim - 1))
                out = jnp.where(pick, piece, out)
            return out

        return jax.tree.map(leaf, tree)

    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis))
    )
