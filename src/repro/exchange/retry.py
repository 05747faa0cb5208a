"""Capacity-doubling retry driver shared by every exchange consumer.

``cluster_sort`` / ``cluster_sort_kv`` (model-D sort) and
``moe_apply_adaptive`` (MoE dispatch) all run their compiled exchange
through ``run_with_capacity_retries``: execute at the current capacity,
detect collective overflow, double and re-execute, and report the final
attempt's telemetry (peak per-(sender, bucket) count, overflow / retry /
recompile events) — the feedback ``repro.engine.adapt`` turns into learned
capacity factors so steady state never pays the retry again.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

__all__ = ["run_with_capacity_retries"]

# serializes the (miss-count snapshot, memoized construction) pairs inside
# run_with_capacity_retries so concurrent callers never attribute each
# other's cache misses to their own telemetry; construction is cheap (the
# jit wrapper — actual compilation happens at call time, outside the lock)
_RECOMPILE_COUNT_LOCK = threading.Lock()


def run_with_capacity_retries(
    make_fn: Callable[[int], Callable],
    run_fn: Callable[[Callable], tuple],
    *,
    m: int,
    part_buckets: int,
    cap: int,
    max_retries: int,
    telemetry: Optional[Callable[..., None]],
    lru,
    label: str,
    strict: bool = True,
    partition: Optional[str] = None,
    path: Optional[str] = None,
):
    """Shared capacity-doubling retry driver for exchange-based paths.

    ``make_fn(cap)`` returns the compiled executable for one capacity (an
    ``lru_cache``-memoized factory — ``lru`` is that factory, used to count
    retry-forced fresh compilations); ``run_fn(fn)`` executes it and returns
    ``(*outputs, counts, peak, overflow)``.  On success returns
    ``(outputs, counts)`` — sort callers turn ``counts`` into a validity
    mask with ``slab_valid``, MoE callers read per-expert token counts.
    On persistent overflow, ``strict=True`` (the sort contract: losing keys
    is corruption) raises ``RuntimeError``; ``strict=False`` (the MoE
    contract: GShard-style overflow-drop is well-defined) returns the last
    attempt's outputs with the overflow already reported.  Either way the
    final attempt's telemetry (peak per-(sender, bucket) count, overflow/
    retry/recompile events) is reported through ``telemetry`` — the feedback
    ``repro.engine.adapt`` turns into learned capacity factors — tagged with
    the caller's ``partition`` family and exchange ``path``
    (``"sorted_runs"`` or ``"scatter"``, ``ExchangeObservation.path``).

    >>> import jax.numpy as jnp
    >>> from functools import lru_cache
    >>> @lru_cache(maxsize=None)
    ... def make(cap):                     # "compile" for one capacity
    ...     return cap
    >>> def run(cap):                      # toy: overflows until cap >= 3
    ...     counts = jnp.array([3])
    ...     return jnp.zeros(4), counts, jnp.asarray(3), jnp.asarray(cap < 3)
    >>> outs, counts = run_with_capacity_retries(
    ...     make, run, m=8, part_buckets=1, cap=1, max_retries=4,
    ...     telemetry=None, lru=make, label="toy")
    >>> len(outs), int(counts[0])          # cap doubled 1 -> 2 -> 4, then fit
    (1, 3)
    """
    retries, peak, recompiles = 0, 0, 0

    def report(overflowed: bool) -> None:
        if telemetry is not None:
            telemetry(
                m=m,
                part_buckets=part_buckets,
                capacity=cap,
                peak=peak,
                overflowed=overflowed,
                retries=retries,
                recompiles=recompiles,
                partition=partition,
                path=path,
            )

    for attempt in range(max_retries + 1):
        if attempt:
            cap = min(m, cap * 2)
        with TraceAnnotation("repro.exchange.attempt"):
            with _RECOMPILE_COUNT_LOCK:
                misses0 = lru.cache_info().misses
                fn = make_fn(cap)
                fresh = lru.cache_info().misses - misses0
            if attempt:
                # only retry attempts count: a first-call warmup compile is the
                # normal cost of a new config, not an overflow-forced recompile
                recompiles += fresh
            *outs, counts, att_peak, overflow = run_fn(fn)
            # the host waits here for the device to finish the attempt
            with TraceAnnotation("repro.exchange.overflow_wait"):
                peak = max(peak, int(att_peak))
                overflowed = bool(overflow)
        retries = attempt
        if not overflowed:
            report(overflowed=attempt > 0)
            return outs, counts
        if cap >= m:
            break  # already loss-free capacity; more retries can't help
    report(overflowed=True)
    if strict:
        raise RuntimeError(f"{label}: capacity overflow persisted after retries")
    return outs, counts
