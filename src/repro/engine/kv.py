"""Key–value sorting — the cluster model finally sorts records, not just keys.

``sort_kv`` / ``argsort`` / ``sort_pairs`` ride the existing
``partition_exchange`` values path (model D's one-step MSD-radix all_to_all),
so an arbitrary pytree of per-record payloads ships alongside the keys in the
same collective — including ``compress=True`` int8 wire mode.  Stability falls
out of the slab layout: within a bucket, receive order is (sender shard, slot
in sender's slab) which *is* global arrival order, so a stable local argsort
of the received slab reproduces ``np.argsort(kind='stable')`` exactly.

Single-device calls (``mesh=None``) run a jitted stable argsort under
``jax.named_scope("repro.kv_order")``, then one jitted gather per leaf (keys
and each payload leaf) under ``repro.kv_permute``; the host span
``repro.kv.dispatch`` covers each ``sort_kv`` call. The distributed path
requires 1-D keys with length divisible by the axis size.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.radix import make_partitioner
from repro.exchange import (
    compact_slabs,
    partition_exchange,
    partition_of,
    run_with_capacity_retries,
    slab_geometry,
    slab_valid,
)

__all__ = ["sort_kv", "sort_pairs", "argsort", "topk", "cluster_sort_kv"]


# --------------------------------------------------------------- local path ---
def _rev_key(keys: jax.Array) -> jax.Array:
    """Order-reversing self-inverse bijection: negation for floats, bitwise
    NOT for ints (~x = -x-1 is strictly decreasing; even INT_MIN is safe)."""
    if jnp.issubdtype(keys.dtype, jnp.integer):
        return ~keys
    return -keys


@partial(jax.jit, static_argnames=("ascending", "impl", "block_n"))
def _order_keys(
    keys: jax.Array,
    *,
    ascending: bool,
    impl: str = "xla",
    block_n: Optional[int] = None,
) -> jax.Array:
    """Stable argsort along the last axis, either direction.

    Descending stability (ties keep original order) sorts the reversed-order
    key transform ascending. ``impl='pallas'`` routes through the kernel's
    stable (key, rank) network — identical permutation, VMEM-tiled execution
    (but unspecified output for NaN keys, which only 'xla' totally orders).
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(f"argsort impl must be 'xla' or 'pallas', got {impl!r}")
    with jax.named_scope("repro.kv_order"):
        k = keys if ascending else _rev_key(keys)
        if impl == "pallas":
            from repro.kernels.bitonic_sort.ops import (
                DEFAULT_BLOCK_N,
                pallas_argsort,
                vmap_last_axis,
            )

            return vmap_last_axis(
                partial(pallas_argsort, block_n=block_n or DEFAULT_BLOCK_N), k
            )
        return jnp.argsort(k, axis=-1, stable=True)


@jax.jit
def _gather_last(v: jax.Array, order: jax.Array) -> jax.Array:
    """Index ``v`` (shaped like keys + optional trailing dims) by ``order``.

    A program of its own per leaf when called outside a jit: alone, the
    compiler brings the gathered array into the chip's fast memory. With all
    five gathers of a four-column record sort in one program it gathered
    three from HBM, and a v5e took 1.09 s a call at 2^24 records, not 0.76."""
    with jax.named_scope("repro.kv_permute"):
        extra = v.ndim - order.ndim
        idx = order.reshape(order.shape + (1,) * extra)
        return jnp.take_along_axis(v, idx, axis=order.ndim - 1)


def _sort_records(keys: jax.Array, values: Any, *, ascending: bool,
                  impl: str = "xla", block_n: Optional[int] = None):
    """One-device record sort: the stable order of ``keys``, then the keys
    and every leaf of ``values`` gathered by it. ``sort_kv`` calls it on one
    device; the service's ``sort_kv`` kind traces it into one program."""
    order = _order_keys(keys, ascending=ascending, impl=impl, block_n=block_n)
    return _gather_last(keys, order), jax.tree.map(
        lambda v: _gather_last(v, order), values
    )


# ------------------------------------------------------------- cluster path ---
def cluster_kv_local(
    local_keys: jax.Array,
    local_values: Any,
    axis_name: str,
    *,
    capacity: int,
    partitioner,
    n_buckets: int,
    compress: bool = False,
):
    """shard_map body: exchange (key, value) records, stable-sort the slab.

    Returns (sorted_keys (B/P*C,), sorted_values pytree, my_count, peak,
    overflow).  Entries [0, my_count) are this shard's contiguous range of
    the global stable sort; the tail is sentinel/zero padding; ``peak`` is
    the mesh-wide max per-(sender, bucket) count (capacity-learning signal).
    """
    P_ = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    bucket = partitioner(local_keys).astype(jnp.int32)
    ex = partition_exchange(
        local_keys,
        local_values,
        bucket,
        axis_name,
        capacity=capacity,
        n_buckets=n_buckets,
        compress=compress,
    )
    flat_k = ex.recv_keys.reshape(-1)
    # slab flat index = (sender, local bucket, slot): within one bucket this is
    # global arrival order, so a stable sort here == the global stable sort.
    order = jnp.argsort(flat_k, stable=True)
    sorted_k = flat_k[order]
    sorted_v = jax.tree.map(
        lambda v: v.reshape((flat_k.shape[0],) + v.shape[2:])[order], ex.recv_values
    )
    global_counts = jax.lax.psum(ex.counts, axis_name)  # (n_buckets,)
    owner = (jnp.arange(n_buckets, dtype=jnp.int32) * P_) // n_buckets
    my_count = jnp.sum(jnp.where(owner == idx, global_counts, 0)).astype(jnp.int32)
    peak = jax.lax.pmax(jnp.max(ex.counts), axis_name)
    return sorted_k, sorted_v, my_count[None], peak, ex.overflow


@lru_cache(maxsize=256)
def _compiled_cluster_kv(
    mesh, axis, mode, capacity, part_buckets, n_buckets, digits, lo, hi, compress
):
    """One jitted shard_map per static config (jit still specializes per
    values-pytree structure internally) — repeat traffic never re-traces."""
    # stable=True: the kv contract is a *stable* sort, so sample mode must use
    # arrival-order tie ids (bucket boundaries inside tie runs keep arrival
    # order across buckets; the slab layout keeps it within buckets)
    part = make_partitioner(
        mode, n_buckets=part_buckets, digits=digits, lo=lo, hi=hi, axis_name=axis,
        stable=True,
    )
    body = partial(
        cluster_kv_local,
        axis_name=axis,
        capacity=capacity,
        partitioner=part,
        n_buckets=n_buckets,
        compress=compress,
    )
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis), P(), P()),
        )
    )


def cluster_sort_kv(
    keys: jax.Array,
    values: Any,
    mesh,
    axis: str,
    *,
    mode: str = "splitters",
    capacity_factor: float = 2.0,
    digits: int = 3,
    lo=0,
    hi=1,
    compress: bool = False,
    max_retries: int = 4,
    telemetry=None,
):
    """Distributed stable key–value sort (model D with a values payload).

    Returns (slab_keys (P*C_total,), slab_values pytree, valid mask); shard
    p's range of the globally sorted records sits in its slab prefix.  Retries
    with doubled capacity on overflow, like ``cluster_sort`` — and like it,
    reports per-call exchange telemetry (peak bucket count, overflow/retry/
    recompile events) through the optional ``telemetry`` callback that
    ``repro.engine.adapt`` turns into learned capacity factors.

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> keys = jnp.arange(16)[::-1]
    >>> slab, vals, valid = cluster_sort_kv(keys, {"i": jnp.arange(16)}, mesh, "x")
    >>> k, v = compact_slabs((slab, vals), valid, 16, mesh, "x")
    >>> [int(x) for x in np.asarray(k)[:4]]
    [0, 1, 2, 3]
    >>> [int(x) for x in np.asarray(v["i"])[:4]]   # payload rides along
    [15, 14, 13, 12]
    """
    P_ = mesh.shape[axis]
    n = keys.shape[-1]
    if n % P_:
        raise ValueError(f"n={n} must divide axis size {P_}")
    m = n // P_
    part_buckets, n_buckets, cap = slab_geometry(mode, m, P_, capacity_factor)

    (slab_k, slab_v), counts = run_with_capacity_retries(
        lambda c: _compiled_cluster_kv(
            mesh, axis, mode, c, part_buckets, n_buckets, digits, lo, hi, compress
        ),
        lambda fn: fn(keys, values),
        m=m,
        part_buckets=part_buckets,
        cap=cap,
        max_retries=max_retries,
        telemetry=telemetry,
        lru=_compiled_cluster_kv,
        label="cluster_sort_kv",
        partition=partition_of(mode),
        path="scatter",
    )
    return slab_k, slab_v, slab_valid(slab_k.shape[0], counts, P_)


# ---------------------------------------------------------------- front API ---
@partial(jax.profiler.annotate_function, name="repro.kv.dispatch")
def sort_kv(
    keys: jax.Array,
    values: Any,
    *,
    mesh=None,
    axis: Optional[str] = None,
    ascending: bool = True,
    compress: bool = False,
    impl: str = "xla",
    block_n: Optional[int] = None,
    **cluster_kw,
):
    """Stable sort of ``keys`` carrying an arbitrary ``values`` pytree along.

    Single device: any leading batch dims, sorts the last axis; ``impl=``
    picks the local argsort engine ('xla' or 'pallas', ``block_n`` = kernel
    tile width; only 'xla' totally orders NaN keys).  With ``mesh=``/
    ``axis=``: 1-D keys, model-D exchange of full records, returns dense
    (n,)-shaped results sharded on ``axis`` (``compact_slabs``).  The mesh path
    closes the capacity-learning loop by default — it runs at the default
    planner's learned ``capacity_factor`` for this (size, dtype, mesh) cell
    and reports exchange telemetry back (pass ``capacity_factor=`` or
    ``telemetry=`` to opt out; see repro.engine.adapt).

    >>> import jax.numpy as jnp
    >>> k, v = sort_kv(jnp.array([3, 1, 2]), {"p": jnp.array([0, 1, 2])})
    >>> [int(i) for i in v["p"]]
    [1, 2, 0]
    """
    if mesh is None:
        return _sort_records(keys, values, ascending=ascending, impl=impl, block_n=block_n)
    if axis is None:
        raise ValueError("sort_kv with mesh= requires axis=")
    if not ascending:
        # sort the order-reversed keys ascending so ties keep arrival order
        # (a flip of the ascending result would reverse them); decimal/range
        # bucketing assumes the untransformed key space, the data-adaptive
        # modes (splitters/sample/auto-ranged radix) don't care.
        if cluster_kw.get("mode", "splitters") not in ("splitters", "sample", "radix"):
            raise ValueError(
                "descending distributed sort_kv needs a data-adaptive mode "
                "('splitters', 'sample', or 'radix')"
            )
        k, v = sort_kv(
            _rev_key(keys), values, mesh=mesh, axis=axis, ascending=True,
            compress=compress, **cluster_kw,
        )
        return _rev_key(k), v
    if "capacity_factor" not in cluster_kw and "telemetry" not in cluster_kw:
        # close the capacity-learning loop through the default planner; an
        # explicit capacity_factor= or telemetry= opts out of the whole loop
        from .planner import default_planner

        cluster_kw.update(
            default_planner().cluster_kwargs(
                keys.shape[-1], keys.dtype, mesh, mode=cluster_kw.get("mode")
            )
        )
    slab_k, slab_v, valid = cluster_sort_kv(
        keys, values, mesh, axis, compress=compress, **cluster_kw
    )
    return compact_slabs((slab_k, slab_v), valid, keys.shape[-1], mesh, axis)


def sort_pairs(keys: jax.Array, values: jax.Array, **kwargs):
    """(keys, values) -> (sorted_keys, aligned_values) for a single payload
    array — the record-sort convenience wrapper over ``sort_kv``.

    >>> import jax.numpy as jnp
    >>> k, v = sort_pairs(jnp.array([2, 1]), jnp.array([10, 20]))
    >>> [int(x) for x in v]
    [20, 10]
    """
    k, v = sort_kv(keys, {"v": values}, **kwargs)
    return k, v["v"]


def argsort(
    keys: jax.Array,
    *,
    mesh=None,
    axis: Optional[str] = None,
    ascending: bool = True,
    impl: str = "xla",
    block_n: Optional[int] = None,
    **cluster_kw,
):
    """Stable argsort (indices into the original array), matching
    ``np.argsort(kind='stable')``. Distributed path carries the global index
    as the exchange payload; single-device ``impl='pallas'`` runs the kernel's
    stable (key, rank) network.

    >>> import jax.numpy as jnp
    >>> [int(i) for i in argsort(jnp.array([30, 10, 20]))]
    [1, 2, 0]
    """
    if mesh is None:
        return _order_keys(keys, ascending=ascending, impl=impl, block_n=block_n)
    iota = jnp.arange(keys.shape[-1], dtype=jnp.int32)
    _, idx = sort_pairs(
        keys, iota, mesh=mesh, axis=axis, ascending=ascending, **cluster_kw
    )
    return idx


def topk(
    x: jax.Array,
    k: int,
    *,
    largest: bool = True,
    impl: str = "xla",
    block_n: Optional[int] = None,
):
    """Top-k (values, indices) along the last axis via the engine argsort.

    Matches ``jax.lax.top_k`` tie behaviour (lowest index wins) because the
    descending argsort is stable — with ``impl='pallas'`` included, since the
    kernel's (key, rank) comparator is stable by construction.

    >>> import jax.numpy as jnp
    >>> vals, idx = topk(jnp.array([1.0, 9.0, 4.0]), 2)
    >>> [float(v) for v in vals], [int(i) for i in idx]
    ([9.0, 4.0], [1, 2])
    """
    order = _order_keys(x, ascending=not largest, impl=impl, block_n=block_n)
    top_idx = order[..., :k]
    return jnp.take_along_axis(x, top_idx, axis=-1), top_idx
