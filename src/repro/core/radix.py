"""One-step MSD-Radix bucketing (paper §3.4) + beyond-paper splitter selection.

The paper's master node inspects the most significant decimal digit and deals
data into 10 buckets, one (or more) per node; MSD (not LSD) preserves locality
so no inter-node merge is ever needed. Generalizations here:

* ``decimal`` mode — the paper's exact scheme: bucket = MSD of a ``digits``-digit
  decimal key; 10 buckets, nodes limited to 1..10 (kept for fidelity tests).
* ``range`` mode — binary generalization: bucket = top log2(B) bits of the key's
  offset in a static [lo, hi) range; any power-of-two bucket count.
* ``radix`` mode (beyond paper) — ``range`` without the static hints: the
  [lo, hi] endpoints are computed collectively per call
  (``repro.exchange.partition.radix_bucket_ids``), so the mode works on data
  whose range nobody declared and the autotuner can sweep it.
* ``splitters`` mode (beyond paper) — sample-based quantile splitters make the
  buckets balanced under arbitrary key skew (samplesort). The paper's static
  MSD map degrades when keys are non-uniform; DESIGN.md §2.
* ``sample`` mode (beyond paper) — ``splitters`` upgraded to composite
  ``(key, id)`` splitters (``sample_partition_ids``): bucket boundaries can
  land *inside* tie runs, so even all-equal / duplicate-heavy distributions
  balance. ``stable=True`` keeps the kv paths' stable-sort guarantee.

The splitter/radix machinery itself lives in ``repro.exchange.partition``
(the exchange layer's partition policy); ``splitter_bucket`` and
``choose_splitters`` are re-exported here for back-compat. All functions are
shard_map-friendly (pure jnp on local shards; the sampling helpers use
collectives given an axis name).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.exchange.partition import (  # noqa: F401  (re-exported back-compat)
    DEFAULT_OVERSAMPLE,
    choose_splitters,
    radix_bucket_ids,
    sample_partition_ids,
    splitter_bucket,
)

__all__ = [
    "decimal_msd_bucket",
    "range_bucket",
    "splitter_bucket",
    "choose_splitters",
    "make_partitioner",
]


def decimal_msd_bucket(keys: jax.Array, *, digits: int) -> jax.Array:
    """Paper mode: most significant digit of a ``digits``-digit decimal int."""
    scale = 10 ** (digits - 1)
    return jnp.clip(keys // scale, 0, 9).astype(jnp.int32)


def range_bucket(keys: jax.Array, *, n_buckets: int, lo, hi) -> jax.Array:
    """Binary MSD generalization: equal-width buckets over a static [lo, hi)."""
    kf = keys.astype(jnp.float32)
    b = (kf - lo) * (n_buckets / (hi - lo))
    return jnp.clip(b.astype(jnp.int32), 0, n_buckets - 1)


def make_partitioner(
    mode: str,
    *,
    n_buckets: int,
    digits: int = 3,
    lo=0,
    hi=1,
    axis_name: Optional[str] = None,
    oversample: int = 8,
    stable: bool = False,
) -> Callable[..., jax.Array]:
    """Return ``(keys, sorted_keys=None) -> bucket_ids`` for the chosen MSD mode.

    ``sorted_keys``, if given, is the same shard sorted ascending; the
    ``splitters`` mode takes its sample from it instead of sorting the shard
    itself, and the other modes ignore it.

    ``stable`` only affects ``sample`` mode: it selects arrival-order tie ids
    so a stable kv sort stays stable with bucket boundaries inside tie runs
    (keys-only sorts keep the default interleaved ids, which balance better).
    """
    if mode == "decimal":
        if n_buckets != 10:
            raise ValueError("decimal MSD implies exactly 10 buckets (paper §3.4)")
        return lambda k, sorted_keys=None: decimal_msd_bucket(k, digits=digits)
    if mode == "range":
        return lambda k, sorted_keys=None: range_bucket(
            k, n_buckets=n_buckets, lo=lo, hi=hi
        )
    if mode == "radix":
        if axis_name is None:
            raise ValueError("radix mode needs the mesh axis name")
        return lambda k, sorted_keys=None: radix_bucket_ids(k, n_buckets, axis_name)
    if mode == "splitters":
        if axis_name is None:
            raise ValueError("splitters mode needs the mesh axis name")

        def part(k, sorted_keys=None):
            spl = choose_splitters(
                k, n_buckets, axis_name, oversample=oversample, sorted_keys=sorted_keys
            )
            return splitter_bucket(k, spl)

        return part
    if mode == "sample":
        if axis_name is None:
            raise ValueError("sample mode needs the mesh axis name")
        # choose_splitters keeps its historic default; the composite sample
        # partition wants the larger DEFAULT_OVERSAMPLE unless overridden
        os_ = max(oversample, DEFAULT_OVERSAMPLE)
        return lambda k, sorted_keys=None: sample_partition_ids(
            k, n_buckets, axis_name, oversample=os_, stable=stable
        )
    raise ValueError(f"unknown partitioner mode {mode!r}")
