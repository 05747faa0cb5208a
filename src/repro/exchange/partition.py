"""Partition-mode policy: radix vs sample — skew-proof bucketing for the wire.

The paper's model D assigns every key a destination from its most
significant digit — a **radix** partition: fast, stateless, and wrong for
skewed key distributions, where a hot digit overloads one bucket and the
fixed-capacity slabs overflow.  The classic remedy is samplesort: each
shard contributes a strided sample of its sorted keys, the gathered sample
is sorted, and its quantiles become splitters — a **sample** partition
whose buckets are balanced by construction, whatever the distribution.

This module is the single home of that two-valued policy:

* ``PARTITION_MODES`` / ``partition_of`` — every partitioner mode name in
  the codebase (``decimal``, ``range``, ``radix``, ``splitters``,
  ``sample``) classified into its family, the value ``SortPlan.partition``
  persists and the ``CapacityLearner`` promotes on.
* ``radix_bucket_ids`` — the auto-ranged radix partition: equal-width
  buckets over the collectively observed ``[min, max]`` key range, so radix
  mode needs no static ``lo``/``hi`` hints and the autotuner can sweep it.
* ``sample_partition_ids`` — the upgraded sample partition over composite
  ``(key, id)`` splitters: ties are split by a per-element id, so even
  all-equal or duplicate-heavy distributions divide into near-perfectly
  balanced buckets (a plain key splitter sends an entire tie run to one
  bucket).  ``stable=True`` uses arrival-order ids, preserving the slab
  layout's stability guarantee for key-value sorts.
* ``choose_splitters`` / ``splitter_bucket`` / ``splitters_from_sample`` —
  the plain key-splitter primitives (``core/radix.py``'s ``splitters``
  mode, re-exported there for back-compat) plus the host-side derivation
  helper the property tests pin down.

Everything is shard_map-friendly: pure jnp on local shards, one small
``all_gather`` for the sample (negligible next to the data exchange).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PARTITION_MODES",
    "DEFAULT_OVERSAMPLE",
    "partition_of",
    "radix_bucket_ids",
    "sample_partition_ids",
    "choose_splitters",
    "splitter_bucket",
    "splitters_from_sample",
]

# the two partition families the planner persists and the learner promotes
# between; every concrete partitioner mode belongs to exactly one of them
PARTITION_MODES = ("radix", "sample")

_FAMILY = {
    "decimal": "radix",     # the paper's MSD decimal digit (static)
    "range": "radix",       # equal-width over a static [lo, hi) hint
    "radix": "radix",       # equal-width over the collective [min, max]
    "splitters": "sample",  # plain key-quantile splitters
    "sample": "sample",     # composite (key, id) splitters
}

# sample size per shard = oversample * n_buckets; 16 keeps the splitter
# rank error well under half a mean bucket at the sizes the bench sweeps
DEFAULT_OVERSAMPLE = 16


def partition_of(mode: str) -> str:
    """Classify a partitioner mode name into its partition family.

    The family — ``'radix'`` or ``'sample'`` — is what ``SortPlan.partition``
    persists, what exchange telemetry tags observations with, and what the
    ``CapacityLearner``'s skew-promotion policy reasons about.

    >>> [partition_of(m) for m in ("decimal", "range", "radix")]
    ['radix', 'radix', 'radix']
    >>> [partition_of(m) for m in ("splitters", "sample")]
    ['sample', 'sample']
    >>> partition_of("quantum")
    Traceback (most recent call last):
        ...
    ValueError: unknown partitioner mode 'quantum'
    """
    try:
        return _FAMILY[mode]
    except KeyError:
        raise ValueError(f"unknown partitioner mode {mode!r}") from None


def radix_bucket_ids(
    keys: jax.Array, n_buckets: int, axis_name: str
) -> jax.Array:
    """Auto-ranged radix partition (call inside shard_map).

    Equal-width buckets over the mesh-wide ``[min, max]`` key range,
    collectively computed with one ``pmin``/``pmax`` pair — the ``range``
    mode without its static ``lo``/``hi`` hints, so it is usable (and
    autotunable) on data whose range nobody declared.  Monotone by
    construction: ``k1 <= k2`` implies ``bucket(k1) <= bucket(k2)``, which
    is all the exchange's contiguous bucket -> shard map needs for global
    sortedness.  Degenerate ranges (all keys equal) collapse into bucket 0;
    ±inf endpoints squash every finite key into one bucket — both *correct*
    (monotone) but maximally skewed, which is exactly the failure mode the
    sample partition exists to fix.

    >>> import jax, jax.numpy as jnp, repro
    >>> from jax.sharding import PartitionSpec as P
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> keys = jnp.arange(16.0)
    >>> f = jax.jit(jax.shard_map(
    ...     lambda k: radix_bucket_ids(k, 4, "x"),
    ...     mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    >>> [int(b) for b in f(keys)]       # 16 keys, 4 equal-width buckets
    [0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3]
    """
    kf = keys.astype(jnp.float32)
    lo = jax.lax.pmin(jnp.min(kf), axis_name)
    hi = jax.lax.pmax(jnp.max(kf), axis_name)
    span = jnp.maximum(hi - lo, jnp.float32(np.finfo(np.float32).tiny))
    scaled = (kf - lo) * (n_buckets / span)
    # inf endpoints produce inf*0 / inf-inf NaNs; a NaN here can only come
    # from that degeneracy, and bucket 0 keeps the map monotone for the
    # finite keys (the where() pins below handle the infinities themselves)
    scaled = jnp.where(jnp.isnan(scaled), 0.0, scaled)
    b = jnp.clip(scaled, 0, n_buckets - 1).astype(jnp.int32)
    b = jnp.where(kf >= hi, n_buckets - 1, b)
    return jnp.where(kf <= lo, 0, b).astype(jnp.int32)


def splitter_bucket(keys: jax.Array, splitters: jax.Array) -> jax.Array:
    """bucket = rank of key among B-1 sorted splitters (plain samplesort).

    >>> import jax.numpy as jnp
    >>> spl = jnp.array([10, 20, 30])
    >>> [int(b) for b in splitter_bucket(jnp.array([5, 10, 25, 99]), spl)]
    [0, 1, 2, 3]
    """
    return jnp.searchsorted(splitters, keys, side="right").astype(jnp.int32)


def splitters_from_sample(
    sample, n_buckets: int, *, unique: bool = False
) -> jax.Array:
    """B-1 interior quantile splitters from a gathered key sample.

    The host-side half of splitter derivation, shared by
    ``choose_splitters`` (in-jit, fixed shapes) and tooling/tests that
    derive splitters from a numpy sample.  ``unique=True`` additionally
    deduplicates (numpy path only — dedup is data-dependent and cannot run
    under jit), returning possibly fewer than ``n_buckets - 1`` splitters;
    ``splitter_bucket`` then emits correspondingly fewer distinct buckets.
    Deterministic: the same sample always yields the same splitters.

    >>> import numpy as np
    >>> [int(s) for s in splitters_from_sample(np.arange(100), 4)]
    [25, 50, 75]
    >>> [int(s) for s in splitters_from_sample(
    ...     np.array([7, 7, 7, 7, 9]), 4, unique=True)]
    [7]
    """
    flat = jnp.sort(jnp.asarray(sample).reshape(-1))
    spl = _quantiles(flat, n_buckets)
    if unique:
        return jnp.asarray(np.unique(np.asarray(spl)))
    return spl


def _strided(x: jax.Array, stride: int, s: int) -> jax.Array:
    """``x[..., ::stride][..., :s]`` as one strided slice (jnp's strided
    indexing lowers to a gather)."""
    return jax.lax.slice_in_dim(x, 0, (s - 1) * stride + 1, stride, axis=-1)


def _quantiles(flat: jax.Array, n_buckets: int) -> jax.Array:
    """Entries ``(j * len) // n_buckets`` for j in 1..B-1 of a sorted array.

    The positions are static, so each is a plain slice: no gather.
    """
    total = flat.shape[0]
    picks = [flat[(j * total) // n_buckets] for j in range(1, n_buckets)]
    return jnp.stack(picks) if picks else flat[:0]


def choose_splitters(
    local_keys: jax.Array,
    n_buckets: int,
    axis_name: str,
    *,
    oversample: int = 8,
    sorted_keys: Optional[jax.Array] = None,
) -> jax.Array:
    """Distributed quantile-splitter selection (samplesort), inside shard_map.

    Every device contributes ``oversample * n_buckets`` strided samples of
    its *sorted* shard; the all-gathered sample is sorted and B-1 quantiles
    become the splitters.  One small all_gather — negligible next to the
    data exchange.  ``sorted_keys``, if given, is ``local_keys`` already
    sorted ascending (the keys-only model-D sort has it at hand), and the
    shard is not sorted again.

    >>> import jax, jax.numpy as jnp, repro
    >>> from jax.sharding import PartitionSpec as P
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> f = jax.jit(jax.shard_map(
    ...     lambda k: choose_splitters(k, 4, "x"),
    ...     mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False))
    >>> spl = f(jnp.arange(64.0))
    >>> bool(jnp.all(spl[:-1] <= spl[1:]))     # sorted, B-1 of them
    True
    """
    m = local_keys.shape[-1]
    s = min(m, oversample * n_buckets)
    stride = max(1, m // s)
    local_sorted = jnp.sort(local_keys, axis=-1) if sorted_keys is None else sorted_keys
    sample = _strided(local_sorted, stride, s)
    gathered = jax.lax.all_gather(sample, axis_name)  # (P, s)
    return splitters_from_sample(gathered, n_buckets)


def _composite_splitters(
    local_keys: jax.Array,
    gid: jax.Array,
    n_buckets: int,
    axis_name: str,
    oversample: int,
) -> Tuple[jax.Array, jax.Array]:
    """(key, id) quantile splitters over the gathered composite sample."""
    m = local_keys.shape[-1]
    s = min(m, oversample * n_buckets)
    stride = max(1, m // s)
    # ids ride through the sort as a second operand: the same order as a
    # stable argsort, with no gather through it
    sk, sid = jax.lax.sort((local_keys, gid), num_keys=1, is_stable=True)
    sk, sid = _strided(sk, stride, s), _strided(sid, stride, s)
    gk = jax.lax.all_gather(sk, axis_name).reshape(-1)
    gi = jax.lax.all_gather(sid, axis_name).reshape(-1)
    # composite order: key major, id minor (ids are unique)
    gk, gi = jax.lax.sort((gk, gi), num_keys=2)
    return _quantiles(gk, n_buckets), _quantiles(gi, n_buckets)


def sample_partition_ids(
    local_keys: jax.Array,
    n_buckets: int,
    axis_name: str,
    *,
    oversample: int = DEFAULT_OVERSAMPLE,
    stable: bool = False,
) -> jax.Array:
    """Balanced bucket ids from composite ``(key, id)`` splitters.

    Plain key splitters cannot split a tie: an all-equal or duplicate-heavy
    distribution sends each whole tie run to a single bucket, and the slabs
    overflow no matter how well the splitters were chosen.  Here every
    element carries a unique id, the splitter space is the composite
    ``(key, id)`` — totally ordered, duplicate-free — and a bucket boundary
    can land *inside* a tie run, so bucket loads track the sample quantiles
    for every distribution.

    ``stable=False`` (keys-only sorts, where tie order is unobservable)
    interleaves ids across shards (``id = position * P + shard``), so even a
    globally constant key spreads each sender's elements evenly over all
    buckets.  ``stable=True`` (key-value sorts) uses arrival-order ids
    (``id = shard * m + position``): cross-bucket tie order then equals
    arrival order, and within a bucket the slab layout's (sender, slot)
    order is arrival order too — the stable-sort guarantee survives with
    bucket boundaries inside tie runs.  The cost: arrival ids are
    shard-contiguous, so a tie run still buckets shard-by-shard (balanced
    globally, not per sender).

    Monotone in the composite order, hence in key order:
    ``k1 <= k2`` implies ``bucket(k1) <= bucket(k2)``.

    >>> import jax, jax.numpy as jnp, numpy as np, repro
    >>> from jax.sharding import PartitionSpec as P
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> f = jax.jit(jax.shard_map(
    ...     lambda k: sample_partition_ids(k, 4, "x"),
    ...     mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    >>> b = f(jnp.zeros(64, jnp.int32))        # all-equal keys still balance
    >>> [int(c) for c in np.bincount(np.asarray(b), minlength=4)]  # even to within one
    [17, 16, 16, 15]
    """
    P_ = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = local_keys.shape[-1]
    pos = jnp.arange(m, dtype=jnp.int32)
    if stable:
        gid = idx * m + pos          # global arrival order (shard-major)
    else:
        gid = pos * P_ + idx         # shard-interleaved (balance-optimal)
    spl_k, spl_id = _composite_splitters(
        local_keys, gid, n_buckets, axis_name, oversample
    )
    k, i = local_keys[:, None], gid[:, None]
    above = (k > spl_k[None, :]) | ((k == spl_k[None, :]) & (i > spl_id[None, :]))
    return above.sum(axis=-1).astype(jnp.int32)
