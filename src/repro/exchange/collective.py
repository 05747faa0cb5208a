"""The exchange wire: one fixed-capacity ``all_to_all`` each way.

``partition_exchange`` ships every element to the shard owning its bucket;
``combine_exchange`` is the exact inverse (MoE's return trip).  Buckets are
generic: key-value sorts pass radix digits / splitter ranks, MoE dispatch
passes expert ids — same slabs, same overflow semantics, same telemetry
signal (``ExchangeResult.counts`` / ``.overflow``).

``sorted_runs_exchange`` is the keys-only model-D sort's wire.  Its buckets
are monotone in the key, so in a sorted shard each bucket is one contiguous
run, and each send-slab row is a slice of that run: no argsort, gather or
scatter (docs/exchange.md, "Two paths").  ``bucket_counts`` gives it the
per-bucket counts without a scatter.

SPMD adaptation (DESIGN.md §2): MPI's variable-length messages become
fixed-capacity slabs of ``capacity`` elements per (src, dst) pair, padded
with sentinels.  Overflow is detected collectively and surfaced; capacity
policy lives one layer up (``retry.py`` doubles and retries,
``models/moe.py`` may drop, ``repro.engine.adapt`` learns).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .partition import radix_bucket_ids, sample_partition_ids
from .slabs import sentinel_for

__all__ = [
    "ExchangeResult",
    "bucket_counts",
    "combine_exchange",
    "partition_exchange",
    "sorted_runs_exchange",
]


@dataclass
class ExchangeResult:
    """Everything ``partition_exchange`` learned while scattering one batch.

    ``recv_*`` are what this shard received (slab layout, sentinel/zero
    padded); ``send_slot``/``counts``/``overflow`` describe what this shard
    sent — ``counts`` and ``overflow`` are the raw telemetry the adaptive
    capacity loop feeds on.

    >>> import jax.numpy as jnp
    >>> ex = ExchangeResult(recv_keys=jnp.zeros(4), recv_values=None,
    ...                     recv_src_slot=jnp.full(4, -1), send_slot=None,
    ...                     counts=jnp.array([3, 1]), overflow=False)
    >>> int(ex.counts.max()), bool(ex.overflow)
    (3, False)
    """

    recv_keys: jax.Array        # (P, C) keys received, sentinel-padded
    recv_values: Any            # pytree of (P, C, ...) or None
    recv_src_slot: jax.Array    # (P, C) flat slot id in the *sender's* slab
    send_slot: jax.Array        # (m,) my element's slab slot, -1 if dropped
    counts: jax.Array           # (n_buckets,) my element count per bucket
    overflow: jax.Array         # scalar bool: any (src,dst) bucket overflowed


def _stable_argsort_by(dest: jax.Array) -> jax.Array:
    """Stable order grouping elements by destination (XLA sort = local 'quicksort')."""
    return jnp.argsort(dest, stable=True)


def _quantize_rows(v: jax.Array):
    """bf16/f32 (N, ...) -> (int8 payload, f32 per-row scale) for the wire."""
    vf = v.astype(jnp.float32)
    flat = vf.reshape(v.shape[0], -1)
    scale = jnp.max(jnp.abs(flat), axis=-1) / 127.0
    q = jnp.round(vf / jnp.maximum(scale, 1e-12).reshape((-1,) + (1,) * (v.ndim - 1)))
    return q.astype(jnp.int8), scale


def _dequantize_rows(q: jax.Array, scale: jax.Array, dtype):
    return (
        q.astype(jnp.float32) * scale.reshape((-1,) + (1,) * (q.ndim - 1))
    ).astype(dtype)


def _compressed_a2a(axis_name: str, P_: int, row: int):
    """int8-on-the-wire all_to_all with a straight-through backward.

    Forward ships (int8 payload, f32 per-row scale) — ~0.53x the bf16 bytes.
    ``round`` has zero gradient, so the custom VJP routes cotangents through
    the (self-transpose) all_to_all uncompressed.
    """
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=0, concat_axis=0, tiled=False
    )

    @jax.custom_vjp
    def qa2a(v):  # v: (P_*row, ...) flat slab
        q, s = _quantize_rows(v)
        rq = a2a(q.reshape((P_, row) + v.shape[1:]))
        rs = a2a(s.reshape(P_, row))
        return _dequantize_rows(
            rq.reshape((P_ * row,) + v.shape[1:]), rs.reshape(-1), v.dtype
        )

    def fwd(v):
        return qa2a(v), None

    def bwd(_, g):
        back = a2a(g.reshape((P_, row) + g.shape[1:]))
        return (back.reshape((P_ * row,) + g.shape[1:]),)

    qa2a.defvjp(fwd, bwd)
    return qa2a


def partition_exchange(
    keys: jax.Array,
    values: Any,
    bucket_ids: Optional[jax.Array],
    axis_name: str,
    *,
    capacity: int,
    n_buckets: Optional[int] = None,
    compress: bool = False,
    partition: Optional[str] = None,
    oversample: Optional[int] = None,
) -> ExchangeResult:
    """Ship every element to the shard owning its bucket (call inside shard_map).

    keys: (m,); values: pytree of (m, ...) moved alongside; bucket_ids: (m,)
    int32 in [0, n_buckets). ``n_buckets`` defaults to the axis size P and must
    be a multiple of it; buckets map to shards contiguously (shard =
    bucket * P // n_buckets) so bucket order == shard order (global sortedness
    / expert grouping both rely on this). ``capacity`` is per (sender, bucket).

    ``bucket_ids=None`` derives the ids in-graph from ``partition``:
    ``"radix"`` auto-ranged equal-width buckets, ``"sample"`` balanced
    composite splitters (``oversample`` tunes the sample size; values ride
    stably, so the sample partition uses arrival-order tie ids whenever
    ``values`` travel).  Passing explicit ``bucket_ids`` keeps the historic
    contract — MoE routers and custom partitioners are unaffected.

    ``compress=True`` ships *float* value payloads as int8 with a per-element
    f32 scale (beyond-paper: ~0.53x wire bytes for bf16 tokens; quantization
    is straight-through for autodiff — the dequantized values carry
    gradients). Integer leaves always travel uncompressed: quantization is
    lossy and would corrupt indices/ids.

    Returns slabs of shape (P, B_loc * capacity): row j = what shard j sent me,
    laid out as (B_loc, capacity) for my local buckets.

    >>> import jax, jax.numpy as jnp, repro
    >>> from jax.sharding import PartitionSpec as P
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> keys = jnp.arange(16, dtype=jnp.int32) % jax.device_count()
    >>> def body(k):  # bucket id == destination shard
    ...     ex = partition_exchange(k, None, k, "x", capacity=16)
    ...     return ex.recv_keys.reshape(-1), ex.overflow
    >>> recv, ovf = jax.jit(jax.shard_map(
    ...     body, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P())))(keys)
    >>> int((recv < 16).sum()), bool(ovf)   # all 16 keys arrived, no overflow
    (16, False)
    """
    P_ = jax.lax.axis_size(axis_name)
    m = keys.shape[-1]
    C = capacity
    B = P_ if n_buckets is None else n_buckets
    if B % P_:
        raise ValueError(f"n_buckets={B} must be a multiple of axis size {P_}")
    with jax.named_scope("repro.partition"):
        if bucket_ids is None:
            if partition == "radix":
                bucket_ids = radix_bucket_ids(keys, B, axis_name)
            elif partition == "sample":
                kw = {} if oversample is None else {"oversample": oversample}
                bucket_ids = sample_partition_ids(
                    keys, B, axis_name, stable=values is not None, **kw
                )
            else:
                raise ValueError(
                    f"bucket_ids=None needs partition in ('radix', 'sample'), got {partition!r}"
                )
        sent = sentinel_for(keys.dtype, largest=True)

        # --- group by bucket (stable: preserves arrival order per bucket) ---
        order = _stable_argsort_by(bucket_ids)
        sorted_bkt = bucket_ids[order]
        counts = jnp.bincount(bucket_ids, length=B).astype(jnp.int32)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        pos_in_bucket = jnp.arange(m, dtype=jnp.int32) - offsets[sorted_bkt]
        valid = pos_in_bucket < C
        slot_sorted = jnp.where(valid, sorted_bkt * C + pos_in_bucket, B * C)

        # --- build fixed-capacity send slab (scatter, OOB slots dropped) ---
        slab_keys = jnp.full((B * C,), sent, keys.dtype)
        slab_keys = slab_keys.at[slot_sorted].set(keys[order], mode="drop")

        def to_slab(v):
            buf = jnp.zeros((B * C,) + v.shape[1:], v.dtype)
            return buf.at[slot_sorted].set(v[order], mode="drop")

        slab_values = None if values is None else jax.tree.map(to_slab, values)

        # remember where each *original* element went (for combine_exchange)
        send_slot = (
            jnp.full((m,), -1, jnp.int32)
            .at[order]
            .set(jnp.where(valid, slot_sorted, -1).astype(jnp.int32))
        )
        # receiver-side validity mask rides along as slot ids (-1 = padding)
        slab_src_slot = (
            jnp.full((B * C,), -1, jnp.int32)
            .at[slot_sorted]
            .set(slot_sorted.astype(jnp.int32), mode="drop")
        )

    # --- the one MSD-radix all_to_all (paper Fig 4 arrow: master -> nodes) ---
    row = (B // P_) * C
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=0, concat_axis=0, tiled=False
    )
    with jax.named_scope("repro.all_to_all"):
        recv_keys = a2a(slab_keys.reshape(P_, row))
        recv_src_slot = a2a(slab_src_slot.reshape(P_, row))
        if values is None:
            recv_values = None
        elif compress:
            # int8 quantization is lossy and only meaningful for float payloads;
            # integer leaves (indices, ids) ship uncompressed to stay exact
            recv_values = jax.tree.map(
                lambda v: (
                    _compressed_a2a(axis_name, P_, row)(v).reshape((P_, row) + v.shape[1:])
                    if jnp.issubdtype(v.dtype, jnp.floating)
                    else a2a(v.reshape((P_, row) + v.shape[1:]))
                ),
                slab_values,
            )
        else:
            recv_values = jax.tree.map(
                lambda v: a2a(v.reshape((P_, row) + v.shape[1:])), slab_values
            )

    with jax.named_scope("repro.counts"):
        overflow = jax.lax.pmax(jnp.max(counts) > C, axis_name)
    return ExchangeResult(
        recv_keys=recv_keys,
        recv_values=recv_values,
        recv_src_slot=recv_src_slot,
        send_slot=send_slot,
        counts=counts,
        overflow=overflow,
    )


def bucket_counts(bucket_ids: jax.Array, n_buckets: int) -> jax.Array:
    """``jnp.bincount(bucket_ids, length=n_buckets)`` as compare-and-sum
    reductions: ``n_buckets`` is static and small, and a scatter-add of m
    ones is the slow way to count on a TPU.  Ids outside
    ``[0, n_buckets)`` are counted nowhere.

    >>> import jax.numpy as jnp
    >>> [int(c) for c in bucket_counts(jnp.array([0, 2, 2, 3, 2]), 4)]
    [1, 0, 3, 1]
    """
    ids = jnp.arange(n_buckets, dtype=bucket_ids.dtype)
    return jnp.sum(bucket_ids[None, :] == ids[:, None], axis=1, dtype=jnp.int32)


def sorted_runs_exchange(
    sorted_keys: jax.Array,
    counts: jax.Array,
    axis_name: str,
    *,
    capacity: int,
):
    """Ship a sorted shard's bucket runs to their shards (call inside shard_map).

    The keys-only twin of ``partition_exchange``.  ``sorted_keys`` is this
    shard's keys in ascending order, and ``counts`` (``(n_buckets,)``
    int32) how many of them fall in each bucket, for a bucket map that is
    monotone in the key (``k1 <= k2`` implies ``bucket(k1) <=
    bucket(k2)``).  Then bucket b's keys are, as a multiset, entries
    ``[start_b, start_b + counts[b])`` of ``sorted_keys`` with ``start_b =
    counts[:b].sum()``, even where a bucket boundary splits a run of equal
    keys; and slab row b is a static-length slice there.  No index array of
    length m is built: no gather and no scatter.  Within a bucket the keys
    arrive in key order, not arrival order, so only a keys-only sort (where
    equal keys cannot be told apart) may use it.

    Buckets map to shards contiguously, as in ``partition_exchange``, and
    ``capacity`` is per (sender, bucket).  Returns ``(recv_keys, counts,
    overflow)``: ``recv_keys`` ``(P, B_loc * capacity)`` in
    ``partition_exchange``'s slab layout, sentinel-padded, and ``overflow``
    true on every shard if any (sender, bucket) count exceeded
    ``capacity`` (the row then holds its first ``capacity`` keys).

    >>> import jax, jax.numpy as jnp, repro
    >>> from jax.sharding import PartitionSpec as P
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> def body(k):  # bucket id == destination shard, which is monotone
    ...     k = jnp.sort(k)
    ...     counts = bucket_counts(k * jax.device_count() // 16, jax.device_count())
    ...     recv, _, ovf = sorted_runs_exchange(k, counts, "x", capacity=16)
    ...     return recv.reshape(-1), ovf
    >>> recv, ovf = jax.jit(jax.shard_map(
    ...     body, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P())))(jnp.arange(16))
    >>> int((recv < 16).sum()), bool(ovf)   # all 16 keys arrived, no overflow
    (16, False)
    """
    P_ = jax.lax.axis_size(axis_name)
    C = capacity
    B = counts.shape[0]
    if B % P_:
        raise ValueError(f"n_buckets={B} must be a multiple of axis size {P_}")
    with jax.named_scope("repro.partition"):
        sent = sentinel_for(sorted_keys.dtype, largest=True)
        # C sentinels behind the shard, so no slice is clamped back into it
        padded = jnp.concatenate([sorted_keys, jnp.full((C,), sent, sorted_keys.dtype)])
        starts = jnp.cumsum(counts) - counts
        slot = jnp.arange(C, dtype=jnp.int32)
        slab_keys = jnp.stack([
            jnp.where(slot < counts[b], jax.lax.dynamic_slice(padded, (starts[b],), (C,)), sent)
            for b in range(B)
        ])

    row = (B // P_) * C
    with jax.named_scope("repro.all_to_all"):
        recv_keys = jax.lax.all_to_all(
            slab_keys.reshape(P_, row), axis_name, 0, 0, tiled=False
        )
    with jax.named_scope("repro.counts"):
        overflow = jax.lax.pmax(jnp.max(counts) > C, axis_name)
    return recv_keys, counts, overflow


def combine_exchange(
    processed: Any,
    ex: ExchangeResult,
    axis_name: str,
    *,
    fill=0,
) -> Any:
    """Inverse exchange: return processed (P, C, ...) slabs to their senders and
    restore original element order. Dropped (overflowed) elements get ``fill``.

    The MoE return trip — expert outputs ride back through the self-transpose
    ``all_to_all`` and land in the exact slots their tokens left from.

    >>> import jax, jax.numpy as jnp, repro
    >>> from jax.sharding import PartitionSpec as P
    >>> mesh = jax.make_mesh((jax.device_count(),), ("x",))
    >>> keys = jnp.arange(16, dtype=jnp.int32) % jax.device_count()
    >>> vals = jnp.arange(16.0)
    >>> def roundtrip(k, v):
    ...     ex = partition_exchange(k, v, k, "x", capacity=16)
    ...     return combine_exchange(ex.recv_values, ex, "x")
    >>> out = jax.jit(jax.shard_map(roundtrip, mesh=mesh,
    ...     in_specs=(P("x"), P("x")), out_specs=P("x")))(keys, vals)
    >>> [int(v) for v in out] == list(range(16))   # exact round-trip
    True
    """
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=0, concat_axis=0, tiled=False
    )
    with jax.named_scope("repro.all_to_all"):
        returned = jax.tree.map(a2a, processed)  # (P, C, ...) back in sender layout

    m = ex.send_slot.shape[0]

    def gather(v):
        flat = v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:])
        safe = jnp.clip(ex.send_slot, 0, flat.shape[0] - 1)
        out = flat[safe]
        mask = (ex.send_slot >= 0).reshape((m,) + (1,) * (out.ndim - 1))
        return jnp.where(mask, out, jnp.asarray(fill, out.dtype))

    return jax.tree.map(gather, returned)
