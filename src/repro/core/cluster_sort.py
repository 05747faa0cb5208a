"""Paper model D: Hybrid-memory sort — one-step MSD-Radix scatter, local sort.

This is the paper's headline algorithm and the framework's production path:

  1. every device computes each key's destination from its most significant
     digit/bits (or sample splitters) — ``radix.py``;
  2. one ``all_to_all`` ships every key to its destination shard — after this
     step key ranges are disjoint, so **no inter-device merging ever happens**
     (the paper's "eliminate all internal data transfers" insight);
  3. each device sorts what it received with the fast local sort (the paper's
     per-node OpenMP hybrid = our vmapped XLA/bitonic sort).

The exchange machinery itself — ``sorted_runs_exchange`` (this module's
wire: each bucket leaves as a slice of the sorted shard), ``slab_geometry``,
the capacity-retry driver — lives in ``repro.exchange``
(the unified adaptive exchange layer, docs/exchange.md); this module is the
*sort* consumer of that layer, MoE dispatch (``models/moe.py``) is the other.
The names are re-exported here for back-compat with pre-extraction callers.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.exchange import (  # noqa: F401  (re-exported for back-compat)
    ExchangeResult,
    bucket_counts,
    combine_exchange,
    partition_exchange,
    partition_of,
    run_with_capacity_retries,
    slab_geometry,
    slab_valid,
    sorted_runs_exchange,
)

from .radix import make_partitioner
from .seqsort import fast_local_sort

__all__ = [
    "ExchangeResult",
    "partition_exchange",
    "combine_exchange",
    "cluster_sort_local",
    "cluster_sort",
    "slab_geometry",
]


def cluster_sort_local(
    local: jax.Array,
    axis_name: str,
    *,
    capacity: int,
    partitioner: Callable[..., jax.Array],
    n_buckets: int,
    local_impl: str = "xla",
    block_n: Optional[int] = None,
):
    """shard_map body for model D. local: (m,) shard. Returns
    (sorted_slab (B/P*C per shard,), my_count, peak, overflow): entries
    [0, my_count) of the slab are this shard's contiguous range of the
    globally sorted output; ``peak`` is the mesh-wide max per-(sender,
    bucket) element count — the exchange-telemetry signal capacity learning
    feeds on (repro.engine.adapt). ``n_buckets`` must be a multiple of the
    axis size; the contiguous bucket -> shard map keeps global order
    (DESIGN.md §2).

    ``partitioner(keys, sorted_keys=...)`` must be monotone in the key (every
    ``make_partitioner`` mode is): the shard is sorted once, and each bucket
    leaves as a slice of it (``sorted_runs_exchange``)."""
    P_ = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    with jax.named_scope("repro.partition"):
        local_sorted = jnp.sort(local)
        bucket = partitioner(local, sorted_keys=local_sorted).astype(jnp.int32)
        counts = bucket_counts(bucket, n_buckets)
    recv_keys, counts, overflow = sorted_runs_exchange(
        local_sorted, counts, axis_name, capacity=capacity
    )
    flat = recv_keys.reshape(-1)
    with jax.named_scope("repro.local_sort"):
        sorted_slab = fast_local_sort(flat, ascending=True, impl=local_impl, block_n=block_n)
    with jax.named_scope("repro.counts"):
        global_counts = jax.lax.psum(counts, axis_name)  # (n_buckets,)
        owner = (jnp.arange(n_buckets, dtype=jnp.int32) * P_) // n_buckets
        my_count = jnp.sum(jnp.where(owner == idx, global_counts, 0)).astype(jnp.int32)
        peak = jax.lax.pmax(jnp.max(counts), axis_name)
    return sorted_slab, my_count[None], peak, overflow


@lru_cache(maxsize=256)
def _compiled_cluster_sort(
    mesh, axis, mode, capacity, part_buckets, n_buckets, digits, lo, hi, local_impl,
    block_n=None,
):
    """One jitted shard_map per static config — repeated cluster_sort calls
    (serving traffic, autotune reps) reuse the traced executable instead of
    rebuilding fresh closures every call."""
    part = make_partitioner(
        mode, n_buckets=part_buckets, digits=digits, lo=lo, hi=hi, axis_name=axis
    )
    body = partial(
        cluster_sort_local,
        axis_name=axis,
        capacity=capacity,
        partitioner=part,
        n_buckets=n_buckets,
        local_impl=local_impl,
        block_n=block_n,
    )
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P(axis), out_specs=(P(axis), P(axis), P(), P())
        )
    )


def cluster_sort(
    x: jax.Array,
    mesh,
    axis: str,
    *,
    mode: str = "splitters",
    capacity_factor: float = 2.0,
    digits: int = 3,
    lo=0,
    hi=1,
    local_impl: str = "xla",
    block_n: Optional[int] = None,
    max_retries: int = 4,
    telemetry: Optional[Callable[..., None]] = None,
):
    """Sort 1-D ``x`` across ``mesh[axis]`` with the paper's cluster algorithm.

    Returns (sorted_x, valid) where ``sorted_x`` is (P*C_total,) with shard p's
    contiguous range in slots [p*C_total + 0, p*C_total + counts[p]); ``valid``
    masks real entries. Retries with doubled capacity on overflow (the
    fault-tolerant wrapper promised in DESIGN.md §2). ``block_n`` tunes
    ``local_impl='pallas'``.

    ``telemetry`` is an optional callback invoked once per call (including a
    failing one) with keyword args ``m``, ``part_buckets``, ``capacity``
    (final attempt), ``peak`` (max per-(sender, bucket) count observed),
    ``overflowed``, ``retries``, ``recompiles`` (fresh executables the
    capacity-doubling retries forced — a first-call warmup compile doesn't
    count), ``partition`` (the mode's family, ``"radix"``/``"sample"``)
    and ``path`` (``"sorted_runs"``: the exchange that slices the sorted
    shard) — the feedback ``repro.engine.adapt`` turns into learned capacity
    factors and, for persistently skewed radix keys, sample-mode promotion.
    """
    P_ = mesh.shape[axis]
    n = x.shape[-1]
    if n % P_:
        raise ValueError(f"n={n} must divide axis size {P_}")
    m = n // P_
    part_buckets, n_buckets, cap = slab_geometry(mode, m, P_, capacity_factor)

    (slab,), counts = run_with_capacity_retries(
        lambda c: _compiled_cluster_sort(
            mesh, axis, mode, c, part_buckets, n_buckets, digits, lo, hi,
            local_impl, block_n,
        ),
        lambda fn: fn(x),
        m=m,
        part_buckets=part_buckets,
        cap=cap,
        max_retries=max_retries,
        telemetry=telemetry,
        lru=_compiled_cluster_sort,
        label="cluster_sort",
        partition=partition_of(mode),
        path="sorted_runs",
    )
    return slab, slab_valid(slab.shape[0], counts, P_)
