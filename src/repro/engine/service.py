"""Batched sort front door: ragged requests in, one vmapped sort per bucket.

``SortService.submit`` accepts a ragged batch of 1-D requests, groups them by
(length bucket, dtype), pads each group to a (pow2 batch, pow2 length) block
in numpy, and runs one ahead-of-time compiled executable per block shape from
the ``CompiledCache``.  All padding/slicing stays in numpy so the steady-state
hot path performs **zero** jax tracing/lowering — the property the engine
tests assert with jax's compilation counters.

The group/pad/execute core lives in ``_run_group`` so the sync ``submit``
path and the multi-tenant ``SortFrontend`` (``repro.engine.frontend``) share
one implementation — the frontend coalesces requests *across* callers into
the same per-(bucket, dtype, kind) groups this module executes.

Plans come from the ``Planner``: the per-bucket local sort recipe is the
tuned shared-memory plan for that (bucket, dtype) cell (a serving front door
is a single-host component; cluster plans apply to the mesh path in kv.py).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.shared_sort import shared_memory_sort
from .cache import CompiledCache, size_bucket
from .kv import _order_keys, _sort_records
from .planner import Planner, SortPlan, default_planner

__all__ = ["SortService", "ServiceStats"]

_KINDS = ("sort", "argsort", "sort_kv")


@dataclass
class ServiceStats:
    """Rolling counters for one ``SortService`` (requests, padding, compiles)
    and for the ``SortFrontend`` that queues in front of it.

    ``elapsed_s`` is *busy* wall time: the union of the per-batch execution
    spans, with overlaps between concurrent submitters merged — so
    ``throughput_keys_per_s`` stays meaningful (and ``elapsed_s`` never
    exceeds real wall time) no matter how many threads submit at once.

    ``overflow_retries`` / ``recompiles`` count model-D slab overflows (and
    the fresh executables those overflows forced) observed by this service's
    *planner* on the exchange path — previously this telemetry silently
    vanished; now it rides the same ledger ``serve.py --stats`` prints.
    They mirror planner-wide telemetry: every service sharing a planner (the
    process-wide default, usually) sees the same counts, so read them as
    "what the planner saw", not a per-service sum.  ``peak_mean_ratio`` is
    the largest peak/mean bucket-load ratio any observed exchange reported —
    the skew signal radix->sample promotion decisions read; ~1.0 means
    balanced partitions, values past the learner's ``promote_ratio`` mean
    promotion is (or soon will be) in play.

    The frontend's fields: ``fill_ratios`` / ``batch_sizes`` /
    ``queue_latency_s`` are rolling windows (bounded deques), so a
    long-lived service reports recent steady state rather than lifetime
    averages; ``shed`` attributes every load-shed to the tenant that
    suffered it and the reason it fired, and ``tenant_served`` tallies each
    tenant's served requests — overload debugging starts from "who was
    shed, and why", not from a global counter.

    >>> ServiceStats(keys_in=100, elapsed_s=2.0).throughput_keys_per_s()
    50.0
    >>> s = ServiceStats()
    >>> s.observe_batch(n_requests=6, capacity=8, latencies=[0.002] * 6)
    >>> round(s.fill_ratio(), 2)
    0.75
    >>> s.latency_percentiles()[50]
    0.002
    """

    requests: int = 0
    batches: int = 0
    keys_in: int = 0
    padded_keys: int = 0
    elapsed_s: float = 0.0
    compiles: int = 0
    cache_hits: int = 0
    overflow_retries: int = 0
    recompiles: int = 0
    peak_mean_ratio: float = 0.0
    enqueued: int = 0
    coalesced_batches: int = 0
    coalesced_requests: int = 0
    fill_ratios: deque = field(default_factory=lambda: deque(maxlen=1024), repr=False)
    batch_sizes: deque = field(default_factory=lambda: deque(maxlen=1024), repr=False)
    queue_latency_s: deque = field(
        default_factory=lambda: deque(maxlen=8192), repr=False
    )
    shed: Dict[str, Dict[str, int]] = field(default_factory=dict, repr=False)
    tenant_served: Dict[str, int] = field(default_factory=dict, repr=False)
    _busy_until: float = field(default=0.0, repr=False, compare=False)

    def throughput_keys_per_s(self) -> float:
        return self.keys_in / self.elapsed_s if self.elapsed_s else 0.0

    def account_span(self, t0: float, t1: float) -> None:
        """Merge one batch's [t0, t1] execution span into the busy time.

        Overlapping spans (concurrent submitters) only count once — the
        accounting is the union of intervals, not their sum.

        >>> s = ServiceStats()
        >>> s.account_span(0.0, 1.0); s.account_span(0.5, 1.5)  # overlap
        >>> s.elapsed_s
        1.5
        """
        self.elapsed_s += max(0.0, t1 - max(t0, self._busy_until))
        self._busy_until = max(self._busy_until, t1)

    def observe_shed(self, tenant: str, reason: str) -> None:
        """Attribute one load-shed to ``tenant`` with its ``reason``
        (``'tenant_backlog'`` / ``'global_backlog'`` / ``'deadline'``)."""
        per = self.shed.setdefault(tenant, {})
        per[reason] = per.get(reason, 0) + 1

    def shed_total(self, tenant: Optional[str] = None) -> int:
        """Total sheds — for one tenant, or across all tenants."""
        tenants = [tenant] if tenant is not None else list(self.shed)
        return sum(sum(self.shed.get(t, {}).values()) for t in tenants)

    def observe_batch(self, *, n_requests: int, capacity: int, latencies) -> None:
        """Record one dispatched batch (size, fill vs ``max_batch``, and
        each member request's submit-to-done latency)."""
        self.coalesced_batches += 1
        self.coalesced_requests += n_requests
        self.batch_sizes.append(n_requests)
        self.fill_ratios.append(n_requests / capacity if capacity else 0.0)
        self.queue_latency_s.extend(latencies)

    def fill_ratio(self) -> float:
        """Mean batch-fill ratio (requests per batch / max_batch) over the
        rolling window; 0.0 before any batch has run."""
        if not self.fill_ratios:
            return 0.0
        return sum(self.fill_ratios) / len(self.fill_ratios)

    def latency_percentiles(self, ps=(50, 90, 99)) -> Dict[int, float]:
        """{percentile: seconds} over the rolling latency window."""
        lat = sorted(self.queue_latency_s)
        if not lat:
            return {p: 0.0 for p in ps}
        return {
            p: lat[min(len(lat) - 1, round(p / 100 * (len(lat) - 1)))] for p in ps
        }


def _np_sentinel(dtype: np.dtype, *, largest: bool):
    if np.issubdtype(dtype, np.floating):
        return np.inf if largest else -np.inf
    info = np.iinfo(dtype)
    return info.max if largest else info.min


class SortService:
    """Shape-bucketed, plan-driven batch sorter with recompile accounting.

    >>> import numpy as np
    >>> svc = SortService()
    >>> [out] = svc.submit([np.array([3, 1, 2], np.int32)])
    >>> [int(v) for v in out]
    [1, 2, 3]
    >>> svc.stats.requests
    1
    """

    def __init__(
        self,
        *,
        planner: Optional[Planner] = None,
        min_bucket: int = 8,
    ):
        self.planner = planner or default_planner()
        self.min_bucket = min_bucket
        self.cache = CompiledCache()
        self.stats = ServiceStats()
        # guards cache lookups/compiles and stats counters; the executable
        # call itself runs outside it so concurrent batches still overlap
        self._lock = threading.Lock()
        # overflow retries/recompiles the planner observes on the exchange
        # path land in this service's stats instead of vanishing
        self.planner.add_stats_sink(self)

    def _note_exchange(self, obs) -> None:
        """Planner stats-sink hook: fold one exchange observation's retry and
        recompile cost — and its peak/mean bucket ratio — into this
        service's ledger."""
        with self._lock:
            self.stats.overflow_retries += obs.retries
            self.stats.recompiles += obs.recompiles
            self.stats.peak_mean_ratio = max(
                self.stats.peak_mean_ratio, obs.peak_mean_ratio()
            )

    # ------------------------------------------------------------ builders ---
    @staticmethod
    def _plan_fields(kind: str, plan: SortPlan):
        """The (impl, block_n, n_threads) that actually shape ``kind``'s
        program — the executable-cache key uses exactly these, so plans that
        differ only in fields this kind ignores share one executable."""
        impl = plan.local_impl
        if kind != "sort" and impl != "pallas":
            impl = "xla"  # argsort kinds only have the xla/pallas engines
        block_n = plan.block_n if impl == "pallas" else None
        n_threads = plan.n_threads if kind == "sort" else 0
        return impl, block_n, n_threads

    def _builder(self, kind: str, plan: SortPlan, ascending: bool):
        impl, block_n, n_threads = self._plan_fields(kind, plan)
        if kind == "sort":
            def build():
                return lambda xb: shared_memory_sort(
                    xb,
                    n_threads=n_threads,
                    local_impl=impl,
                    ascending=ascending,
                    block_n=block_n,
                )
        elif kind == "argsort":
            def build():
                return lambda xb: _order_keys(
                    xb, ascending=ascending, impl=impl, block_n=block_n
                )
        else:  # sort_kv
            def build():
                return partial(
                    _sort_records, ascending=ascending, impl=impl, block_n=block_n
                )
        return build

    # ---------------------------------------------------------- validation ---
    @staticmethod
    def _validate(
        kind: str,
        requests: Sequence[np.ndarray],
        values: Optional[Sequence[np.ndarray]],
    ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
        """Check one ragged batch; returns (reqs, vals) as numpy arrays."""
        if kind not in _KINDS:
            raise ValueError(f"kind must be one of {_KINDS}")
        if (values is not None) != (kind == "sort_kv"):
            raise ValueError("values= is required iff kind='sort_kv'")
        reqs = [np.asarray(r) for r in requests]
        vals = None
        for i, r in enumerate(reqs):
            if r.ndim != 1:
                raise ValueError("requests must be 1-D arrays")
            if np.issubdtype(r.dtype, np.floating) and np.isnan(r).any():
                # NaN sorts after the padding sentinel, which would leak
                # padding values (or out-of-range argsort indices) into results
                raise ValueError(f"request {i} contains NaN keys (unsupported)")
        if kind == "sort_kv":
            vals = [np.asarray(v) for v in values]
            if len(vals) != len(reqs):
                raise ValueError("need exactly one values array per request")
            for i, (r, v) in enumerate(zip(reqs, vals)):
                if v.shape[:1] != r.shape:
                    raise ValueError(f"values[{i}] length must match request {i}")
        return reqs, vals

    def _group_key(self, req: np.ndarray, val: Optional[np.ndarray] = None) -> tuple:
        """(length bucket, dtype[, value signature]) — requests sharing this
        key pad into one batch and run one executable."""
        gk = (size_bucket(len(req), min_bucket=self.min_bucket), req.dtype.name)
        if val is not None:
            gk += (val.shape[1:], val.dtype.name)
        return gk

    def _signature(self, kind: str, gk: tuple, bb: int, ascending: bool):
        """The full executable identity of one (group key, batch bucket) cell:
        (plan, cache key, ShapeDtypeStruct args).  ``_run_group`` and
        ``warm_cell`` both derive their compilations from this one function,
        which is what makes AOT warmup airtight — a warmed cell *is* the
        serving cell, not a lookalike."""
        bucket, dtype_name = gk[0], gk[1]
        plan = self.planner.plan_for(bucket, np.dtype(dtype_name))
        if plan.strategy != "shared":  # front door is single-host
            plan = SortPlan("shared")
        # the executable identity is exactly the plan fields this kind
        # consumes (block_n changes the traced program for pallas plans)
        impl, block_n, n_threads = self._plan_fields(kind, plan)
        key = (kind, bucket, bb, dtype_name, ascending,
               impl, n_threads, block_n)
        args = [jax.ShapeDtypeStruct((bb, bucket), jnp.dtype(dtype_name))]
        if kind == "sort_kv":
            vshape, vdtype = gk[2], np.dtype(gk[3])
            key = key + (vshape, vdtype.name)
            args.append(
                jax.ShapeDtypeStruct((bb, bucket) + vshape, jnp.dtype(vdtype))
            )
        return plan, key, args

    def warm_cell(
        self,
        kind: str,
        bucket: int,
        dtype,
        *,
        batch_bucket: int = 1,
        ascending: bool = True,
        values_spec: Optional[Tuple[tuple, Any]] = None,
    ) -> bool:
        """AOT-compile one executable cell before traffic arrives.

        The cell is identified exactly the way serving identifies it —
        (kind, length bucket, batch bucket, dtype, direction, plan fields) —
        so any later request that lands in a warmed cell is a pure cache hit:
        zero jax tracing, first-request latency == steady-state latency.
        Returns True when this call compiled a fresh executable, False when
        the cell was already warm.

        ``values_spec`` (trailing value shape, value dtype) is required
        semantics for ``kind='sort_kv'`` and defaults to scalar int32 values.

        >>> svc = SortService()
        >>> svc.warm_cell("sort", 1024, "int32")
        True
        >>> svc.warm_cell("sort", 1024, "int32")   # already warm
        False
        """
        gk: tuple = (int(bucket), np.dtype(dtype).name)
        if kind == "sort_kv":
            vshape, vdtype = values_spec if values_spec else ((), np.int32)
            gk += (tuple(vshape), np.dtype(vdtype).name)
        elif values_spec is not None:
            raise ValueError("values_spec= only applies to kind='sort_kv'")
        if kind not in _KINDS:
            raise ValueError(f"kind must be one of {_KINDS}")
        plan, key, args = self._signature(kind, gk, int(batch_bucket), ascending)
        with self._lock:
            before = self.cache.misses
            self.cache.get_or_build(key, self._builder(kind, plan, ascending), args)
            fresh = self.cache.misses - before
            self.stats.compiles += fresh
            self.stats.cache_hits += int(fresh == 0)
        return bool(fresh)

    # ----------------------------------------------------------- execution ---
    def _run_group(
        self,
        kind: str,
        gk: tuple,
        reqs: List[np.ndarray],
        vals: Optional[List[np.ndarray]] = None,
        *,
        ascending: bool = True,
    ) -> List[Any]:
        """Pad one group (all ``reqs`` share ``gk``) and run its executable.

        This is the whole hot path — numpy pad, one AOT executable call,
        numpy slice-out — shared verbatim by ``submit`` and ``SortFrontend``.
        Returns one result per request, in the given order.
        """
        t0 = time.perf_counter()
        bucket, dtype_name = gk[0], gk[1]
        dtype = np.dtype(dtype_name)
        bb = size_bucket(len(reqs), min_bucket=1)  # pow2 batch bucket
        with TraceAnnotation("repro.service.pad"):
            sent = _np_sentinel(dtype, largest=ascending)
            batch = np.full((bb, bucket), sent, dtype)
            for row, r in enumerate(reqs):
                batch[row, : len(r)] = r
            if kind == "sort_kv":
                vshape, vdtype = gk[2], np.dtype(gk[3])
                vbatch = np.zeros((bb, bucket) + vshape, vdtype)
                for row, v in enumerate(vals):
                    vbatch[row, : len(v)] = v

        with TraceAnnotation("repro.service.execute"):
            plan, key, args = self._signature(kind, gk, bb, ascending)
            with self._lock:
                before = self.cache.misses
                exe = self.cache.get_or_build(key, self._builder(kind, plan, ascending), args)
                self.stats.compiles += self.cache.misses - before
                self.stats.cache_hits += int(self.cache.misses == before)
                self.stats.batches += 1
                self.stats.padded_keys += bb * bucket - sum(len(r) for r in reqs)
            res = jax.block_until_ready(exe(batch, vbatch) if kind == "sort_kv" else exe(batch))

        out: List[Any] = [None] * len(reqs)
        with TraceAnnotation("repro.service.copy_back"):
            if kind == "sort_kv":
                ks, vres = np.asarray(res[0]), np.asarray(res[1])
                for row, r in enumerate(reqs):
                    n = len(r)
                    out[row] = (ks[row, :n], vres[row, :n])
            else:
                res = np.asarray(res)
                for row, r in enumerate(reqs):
                    # sentinel padding sorts last either direction, so the
                    # leading n entries (indices < n for argsort) are the answer
                    out[row] = res[row, : len(r)]

        t1 = time.perf_counter()
        with self._lock:
            self.stats.requests += len(reqs)
            self.stats.keys_in += sum(len(r) for r in reqs)
            self.stats.account_span(t0, t1)
        return out

    # -------------------------------------------------------------- submit ---
    def submit(
        self,
        requests: Sequence[np.ndarray],
        *,
        kind: str = "sort",
        values: Optional[Sequence[np.ndarray]] = None,
        ascending: bool = True,
    ) -> List[Any]:
        """Sort a ragged batch. Returns per-request numpy results, in order.

        kind='sort'    -> sorted keys
        kind='argsort' -> stable argsort indices
        kind='sort_kv' -> (sorted keys, aligned values); ``values[i]`` must
                          share ``requests[i]``'s length (extra trailing dims ok)
        """
        reqs, vals = self._validate(kind, requests, values)

        # group request indices by (length bucket, dtype) — plus the value
        # signature for sort_kv, so unrelated payload shapes never collide
        groups: Dict[tuple, List[int]] = {}
        for i, r in enumerate(reqs):
            gk = self._group_key(r, vals[i] if vals is not None else None)
            groups.setdefault(gk, []).append(i)

        out: List[Any] = [None] * len(reqs)
        for gk, idxs in sorted(groups.items(), key=lambda kv: repr(kv[0])):
            results = self._run_group(
                kind,
                gk,
                [reqs[i] for i in idxs],
                [vals[i] for i in idxs] if vals is not None else None,
                ascending=ascending,
            )
            for i, res in zip(idxs, results):
                out[i] = res
        return out
