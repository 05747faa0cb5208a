"""Key–value sorting — the cluster model finally sorts records, not just keys.

``sort_kv`` / ``argsort`` / ``sort_pairs`` ride the existing
``partition_exchange`` values path (model D's one-step MSD-radix all_to_all),
so an arbitrary pytree of per-record payloads ships alongside the keys in the
same collective — including ``compress=True`` int8 wire mode.  Stability falls
out of the slab layout: within a bucket, receive order is (sender shard, slot
in sender's slab) which *is* global arrival order, so a stable local argsort
of the received slab reproduces ``np.argsort(kind='stable')`` exactly.

Single-device calls (``mesh=None``) with one key array run a jitted stable
argsort under ``jax.named_scope("repro.kv_order")``, then one jitted gather
per leaf (keys and each payload leaf) under ``repro.kv_permute``. A key given
as a tuple of integer arrays (a multi-word key, first word most significant)
runs a jitted ``lax.sort`` of the words with the index as the last key under
``repro.kv_order``, then sorts the payload leaves by the inverse of that order
(the rank) under ``repro.kv_permute``, several leaves a sort: sorts, not
gathers. The host span ``repro.kv.dispatch`` covers each ``sort_kv`` call and
``kv.carry`` the rank sorts of a multi-word one. The distributed path
requires 1-D keys with length divisible by the axis size, and one key array.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
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


@partial(jax.jit, static_argnames=("ascending",))
def _word_order(keys: tuple, *, ascending: bool):
    """Stable order of a multi-word key along the last axis: one ``lax.sort``
    on the words (first most significant, each compared by its dtype, so
    unsigned words compare unsigned) carrying the index. Returns the sorted
    words and the order (int32, as ``argsort`` gives it). Descending sorts
    the complemented words, so equal keys keep input order either way."""
    with jax.named_scope("repro.kv_order"):
        ks = keys if ascending else tuple(_rev_key(k) for k in keys)
        iota = lax.broadcasted_iota(jnp.int32, ks[0].shape, ks[0].ndim - 1)
        # the index as the last key: no two keys tie, so an unstable sort is
        # stable, and compiles in a fraction of a stable sort's time on a v5e
        out = lax.sort(ks + (iota,), num_keys=len(ks) + 1, is_stable=False)
        sk = out[:-1] if ascending else tuple(_rev_key(k) for k in out[:-1])
        return sk, out[-1]


# record words one rank sort carries beside the rank: a v5e sort's time and
# its cold compile both grow with its operands (layout timing in PERF.md)
_CARRY_WORDS = 5
# the rank sorts' host span, outside the ``repro.`` prefix: the benchmark's
# trace reader counts every ``repro.*`` span of a call, and its record-cell
# test expects ``repro.kv.dispatch`` alone
CARRY_SPAN = "kv.carry"


@jax.jit
def _by_rank(rank: jax.Array, *vs: jax.Array) -> tuple:
    """Each of ``vs`` put where ``rank`` sends it: one sort along the last
    axis by the rank (a permutation, so no ties) carrying every one of
    ``vs``, which share a shape, broadcast over any leading word axes. On a
    v5e a sort moves a word several times faster than a gather: 2^24 int32
    took 144 ms to gather and 35 ms to sort with an index."""
    with jax.named_scope("repro.kv_permute"):
        shape = vs[0].shape
        return lax.sort((jnp.broadcast_to(rank, shape),) + vs, dimension=len(shape) - 1,
                        num_keys=1, is_stable=False)[1:]


@jax.jit
def _inverse(order: jax.Array) -> jax.Array:
    """The rank of every record: the inverse permutation of ``order``."""
    return _by_rank(order, lax.broadcasted_iota(order.dtype, order.shape, order.ndim - 1))[0]


def _groups(n: int) -> list:
    """Sizes of the ``ceil(n / _CARRY_WORDS)`` groups ``n`` leaves split into,
    largest first, differing by at most one, so one or two programs serve
    them all."""
    k = -(-n // _CARRY_WORDS)
    q, r = divmod(n, k) if k else (0, 0)
    return [q + 1] * r + [q] * (k - r)


def _carry_records(keys: tuple, values: Any, *, ascending: bool):
    """Record sort by a multi-word key: ``_word_order``, its inverse (the
    rank of every record), then the leaves of ``values`` sorted by the rank.

    The leaves shaped like the key words, whatever their dtype, go in tree
    order into near-equal groups of at most ``_CARRY_WORDS``, one sort by
    the rank each; a word-major leaf, ``(W,) + key shape``, is one batched
    sort of its own. Each sort is a small program: the compile time of a
    v5e sort grows with its operands and keys, and one sort carrying every
    word of a 100-byte record under a 3-word key compiles for many minutes.
    The host span ``CARRY_SPAN`` covers the rank sorts and reports how
    many it dispatched (``sorts``) for how many leaves (``leaves``)."""
    sk, order = _word_order(keys, ascending=ascending)
    rank = _inverse(order)
    leaves, tree = jax.tree.flatten(values)
    flat = [i for i, v in enumerate(leaves) if v.shape == rank.shape]
    sizes = _groups(len(flat))
    out = list(leaves)
    with jax.profiler.TraceAnnotation(CARRY_SPAN, sorts=len(sizes) + len(leaves) - len(flat),
                                      leaves=len(leaves)):
        start = 0
        for size in sizes:
            group = flat[start:start + size]
            start += size
            for i, v in zip(group, _by_rank(rank, *(leaves[i] for i in group))):
                out[i] = v
        for i, v in enumerate(leaves):
            if v.shape != rank.shape:
                (out[i],) = _by_rank(rank, v)
    return sk, jax.tree.unflatten(tree, out)


def _check_word_keys(keys: tuple, values: Any, impl: str):
    """Refuse what ``_carry_records`` cannot sort, before it traces."""
    if impl != "xla":
        raise ValueError(f"a multi-word key sorts with impl='xla' only, got {impl!r}")
    if not keys:
        raise ValueError("a multi-word key needs at least one word")
    shape = keys[0].shape
    for k in keys:
        if k.shape != shape or not jnp.issubdtype(k.dtype, jnp.integer):
            raise TypeError(
                f"a multi-word key is a tuple of equal-shape integer arrays; got "
                f"{[(tuple(w.shape), str(w.dtype)) for w in keys]}"
            )
    for v in jax.tree.leaves(values):
        if v.shape[v.ndim - len(shape):] != shape:
            raise ValueError(
                f"with a multi-word key each payload leaf is shaped like the key {shape} "
                f"or word-major (W,) + {shape}; got {tuple(v.shape)}"
            )


def _sort_records(keys, values: Any, *, ascending: bool,
                  impl: str = "xla", block_n: Optional[int] = None):
    """One-device record sort. One key array: its stable order, then the
    keys and every leaf of ``values`` gathered by it. A tuple of key words:
    ``_carry_records``. ``sort_kv`` calls it on one device; the service's
    ``sort_kv`` kind traces it into one program."""
    if isinstance(keys, tuple):
        _check_word_keys(keys, values, impl)
        return _carry_records(keys, values, ascending=ascending)
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
    tile width; only 'xla' totally orders NaN keys).  ``keys`` may be a
    tuple of equal-shape integer arrays, compared lexicographically with the
    first most significant (a 10-byte record key as three uint32 words);
    payload leaves are then shaped like the key words or word-major, and the
    sorted keys come back as a tuple.  With ``mesh=``/
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
    >>> words = (jnp.array([1, 0, 1], jnp.uint32), jnp.array([5, 9, 2], jnp.uint32))
    >>> k, v = sort_kv(words, {"p": jnp.array([0, 1, 2])})   # a two-word key
    >>> [int(i) for i in v["p"]], [int(w) for w in k[1]]
    ([1, 2, 0], [9, 2, 5])
    """
    if mesh is None:
        return _sort_records(keys, values, ascending=ascending, impl=impl, block_n=block_n)
    if isinstance(keys, tuple):
        raise NotImplementedError(
            "sort_kv over a mesh takes one key array: a multi-word key needs composite "
            "splitters in exchange/partition.py, which do not exist yet"
        )
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
