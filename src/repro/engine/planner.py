"""Autotuned sort planning — measure the paper's crossover instead of guessing.

The paper's empirical core: which hybrid wins is workload-dependent ("Hybrid
Quicksort and Merge sort outperformed [the cluster model] ... when sorting
small size data, but with larger data the speedup of [the cluster model]
becomes bigger").  A ``SortPlan`` pins one concrete execution recipe
(strategy, local sort impl, thread count, capacity factor, partitioner mode);
``autotune`` microbenchmarks every candidate for a (size-bucket, dtype, mesh
fingerprint) cell and persists the winner to a JSON plan cache so serving
processes start with tuned choices.

Plan-cache file format (versioned, human-editable)::

    {"version": 3,
     "plans": {"<size_bucket>|<dtype>|<mesh_fp>": {"strategy": "shared",
                                                   "partition": null, ...}},
     "learned": {"<size_bucket>|<dtype>|<mesh_fp>": {"capacity_factor": 3.75,
                                                     "peak_factor": 3.0,
                                                     "observations": 7,
                                                     "partition": null,
                                                     "skew_strikes": 0}}}

The ``learned`` section (schema v2) is the capacity-learning feedback loop's
persistent state: per-cell capacity factors distilled from observed exchange
telemetry (repro.engine.adapt), so a restarted serving process sizes model-D
slabs right on its first compile.  Schema v3 adds the partition policy:
``SortPlan.partition`` pins a plan's partition family, and the learned
entries carry the skew-promotion latch (``partition``/``skew_strikes``) the
``CapacityLearner`` flips when a radix-partitioned cell's peak/mean bucket
ratio stays high — plus the probation counters (``calm_streak``/
``demotions``, additive within v3) that let a promoted cell demote back to
radix after a long calm stretch — see docs/plan-cache.md.  Version-1 and -2
files load fine — they simply carry no learned state / no partition policy.
Cells are keyed by any string the reporting path binds: sort cells use
``<size_bucket>|<dtype>|<mesh_fp>`` (``plan_key``), MoE dispatch cells use
``moe/E<experts>k<top_k>|<token_bucket>|<dtype>|<mesh_fp>``
(``models.moe.moe_plan_key``) — one learned table serves every
``repro.exchange`` consumer.

Under multi-process ``jax.distributed``, ``Planner.autotune`` runs a
**rank-coordinated** sweep: barriers align every rank on each candidate,
per-rank median-of-reps timings reduce by max over ranks, rank 0's winner is
broadcast so every rank proceeds bit-identically, and rank 0 alone writes
the plan file (single-writer election) through the fcntl-locked
merge-on-save path.  Those cells carry the ``/procs<P>x<D>`` fingerprint
suffix, so a later single-process server warm-starts from them only via an
explicit ``fingerprint=`` lookup, never by accident.
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings
import weakref
from contextlib import contextmanager
from dataclasses import asdict, dataclass, replace
from typing import Dict, Optional

try:  # advisory plan-file locking is POSIX-only; elsewhere merge-on-save
    import fcntl  # still unions concurrent writers, just without mutual
except ImportError:  # exclusion of the read-merge-write itself
    fcntl = None  # type: ignore[assignment]

import jax
import jax.numpy as jnp

from repro.core.bitonic import next_pow2
from repro.core.cluster_sort import cluster_sort
from repro.core.distributed_sort import distributed_merge_sort
from repro.core.seqsort import LOCAL_SORTS
from repro.core.shared_sort import shared_memory_sort
from repro.exchange import PARTITION_MODES, partition_of

from .adapt import CapacityLearner, ExchangeObservation, ExchangeTelemetry, LearnedCapacity

__all__ = [
    "SortPlan",
    "Planner",
    "default_planner",
    "mesh_fingerprint",
    "plan_key",
    "parse_plan_key",
    "plan_from_strategy",
    "run_plan",
    "autotune",
    "LEARNED_SCOPES",
    "PALLAS_BLOCK_SWEEP",
    "PALLAS_INTERPRET_MAX",
]

# how learned capacity factors are keyed across a multi-process deployment:
# 'global' shares one entry per cell (every rank reads/merges the same key —
# the most conservative rank wins), 'per_host' suffixes keys with
# '@h<process_index>' so hosts with host-local skew learn independently
LEARNED_SCOPES = ("global", "per_host")


@contextmanager
def _plan_file_lock(path: str):
    """Advisory ``fcntl`` lock serializing read-merge-write on one plan file.

    Taken on a ``<path>.lock`` sidecar (never the plan file itself: the
    writer atomically ``os.replace``s the plan file, which would drop any
    lock held on the replaced inode).  Cooperating writers — other ranks of
    a ``jax.distributed`` job, other processes sharing ``$REPRO_SORT_PLANS``
    — block here until the current read-merge-write completes.
    """
    if fcntl is None:
        yield
        return
    with open(f"{path}.lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lockf, fcntl.LOCK_UN)

_PLAN_VERSION = 3
# v1 = plans only, no learned section; v2 = learned capacity factors but no
# partition policy (plans/entries load with partition=None, strikes=0)
_LOADABLE_VERSIONS = (1, 2, _PLAN_VERSION)

# the learner floor handed to *promoted* (sample-partition) cells: the
# balanced partition needs almost no headroom, so the capacity factor a
# skewed radix history inflated decays back toward ~1 instead of toward the
# radix-era default
SAMPLE_DEFAULT_FACTOR = 1.25

# strategy names: 'shared' covers paper models A/B (A = local_impl='merge',
# B = local_impl='xla'/'bitonic'); C and D keep their api.py names.
_PLAN_STRATEGIES = ("shared", "distributed_merge", "cluster")


@dataclass(frozen=True)
class SortPlan:
    """One executable sort recipe; ``us_per_call`` records the tuned timing.

    ``block_n`` is the Pallas kernel's VMEM tile width; it is only meaningful
    for ``local_impl='pallas'`` and rides through the JSON plan cache so a
    plan tuned on a TPU ships with its winning tile size.

    ``partition`` (schema v3) pins the cluster partition *family* —
    ``"radix"`` (digit/range bucketing: fast, skew-fragile) or ``"sample"``
    (splitter bucketing: balanced under any distribution).  ``None`` means
    "whatever family ``mode`` itself belongs to"; a non-None value that
    disagrees with ``mode`` overrides it (that is how skew promotion flips a
    radix plan to sample mode without forgetting the tuned mode).

    >>> plan = SortPlan("shared", local_impl="pallas", block_n=512)
    >>> SortPlan.from_dict(plan.to_dict()) == plan
    True
    >>> SortPlan("cluster", mode="range").effective_partition()
    'radix'
    >>> SortPlan("cluster", mode="range", partition="sample").partitioner_mode()
    'sample'
    """

    strategy: str = "shared"
    local_impl: str = "xla"
    n_threads: int = 8
    capacity_factor: float = 2.0
    mode: str = "splitters"
    block_n: Optional[int] = None
    us_per_call: float = -1.0
    partition: Optional[str] = None

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "SortPlan":
        known = {k: d[k] for k in cls.__dataclass_fields__ if k in d}
        return cls(**known)

    def effective_partition(self) -> str:
        """The partition family this plan runs: the explicit ``partition``
        override if set, else ``mode``'s own family."""
        return self.partition or partition_of(self.mode)

    def partitioner_mode(self) -> str:
        """The concrete partitioner mode ``run_plan`` should execute.

        ``mode`` itself when it already belongs to ``effective_partition``'s
        family; otherwise the family's canonical mode (``"sample"`` /
        ``"radix"``) — a promoted radix plan runs sample splitters.
        """
        if self.partition is None or partition_of(self.mode) == self.partition:
            return self.mode
        return "sample" if self.partition == "sample" else "radix"


def mesh_fingerprint(mesh=None) -> str:
    """Stable id for the hardware layout a plan was tuned on.

    Single-process fingerprints are ``local/<platform>`` (no mesh) or
    ``<platform>/<axis>=<size>,...`` (mesh plans).  Under multi-process
    ``jax.distributed`` the same device count can describe very different
    hardware — 4 devices might be one host or four — so the fingerprint
    appends ``/procs<process_count>x<devices_per_process>``: a plan tuned on
    a 2-process x 2-device topology never masquerades as a single-host
    4-device plan (the collectives it was timed over cross real process
    boundaries).  Single-process fingerprints are unchanged, so existing
    plan-cache files stay valid.

    >>> mesh_fingerprint().split("/")[0]   # no mesh: 'local/<platform>'
    'local'
    """
    procs = jax.process_count()
    topo = f"/procs{procs}x{jax.local_device_count()}" if procs > 1 else ""
    if mesh is None:
        dev = jax.devices()[0]
        return f"local/{dev.platform}{topo}"
    axes = ",".join(f"{name}={size}" for name, size in mesh.shape.items())
    return f"{mesh.devices.flat[0].platform}/{axes}{topo}"


def plan_key(n: int, dtype, mesh=None, *, fingerprint: Optional[str] = None) -> str:
    """(size-bucket, dtype, mesh fingerprint) -> plan-cache key.

    ``fingerprint=`` substitutes a precomputed mesh fingerprint — how
    tooling builds keys for a topology the current process is not part of
    (e.g. a coordinator inspecting a multi-host plan file).

    >>> plan_key(3000, jnp.int32) == plan_key(4096, jnp.int32)  # same bucket
    True
    >>> plan_key(4096, jnp.int32) == plan_key(4097, jnp.int32)  # next bucket
    False
    >>> plan_key(100, jnp.int32, fingerprint="cpu/x=4/procs2x2")
    '128|int32|cpu/x=4/procs2x2'
    """
    fp = mesh_fingerprint(mesh) if fingerprint is None else fingerprint
    return f"{next_pow2(n)}|{jnp.dtype(dtype).name}|{fp}"


def parse_plan_key(key: str):
    """Inverse of ``plan_key``: ``(size_bucket, dtype_name, fingerprint)``.

    Round-trips every sort-cell key, including multi-process fingerprints
    (property-tested in tests/test_plan_cache_concurrency.py).  Non-sort
    cells (the MoE ``moe/E<e>k<k>|...`` keys) raise ``ValueError`` — they
    carry extra fields and are parsed by their own consumer.

    >>> parse_plan_key(plan_key(3000, jnp.int32, fingerprint="cpu/x=8"))
    (4096, 'int32', 'cpu/x=8')
    """
    parts = key.split("|")
    if len(parts) != 3 or not parts[0].isdigit():
        raise ValueError(f"not a sort plan-cache key: {key!r}")
    bucket, dtype_name, fp = parts
    return int(bucket), dtype_name, fp


def plan_from_strategy(strategy: str, *, n_threads: int = 8) -> SortPlan:
    """Map the public api.py strategy names onto plans (back-compat).

    >>> plan_from_strategy("shared_merge").local_impl
    'merge'
    >>> plan_from_strategy("shared").strategy
    'shared'
    """
    table = {
        "shared": SortPlan("shared", local_impl="xla", n_threads=n_threads),
        "shared_merge": SortPlan("shared", local_impl="merge", n_threads=n_threads),
        "shared_hybrid": SortPlan("shared", local_impl="xla", n_threads=n_threads),
        "distributed_merge": SortPlan("distributed_merge"),
        "cluster": SortPlan("cluster"),
    }
    if strategy not in table:
        raise ValueError(f"strategy must be one of {tuple(table)}")
    return table[strategy]


def default_plan(mesh=None) -> SortPlan:
    """The pre-autotune rule (what api.sort hard-coded before the engine)."""
    return SortPlan("cluster") if mesh is not None else SortPlan("shared")


def run_plan(
    plan: SortPlan,
    x: jax.Array,
    *,
    mesh=None,
    axis: Optional[str] = None,
    ascending: bool = True,
    **kwargs,
):
    """Execute a plan. Cluster plans return (slab, valid) like cluster_sort.

    >>> [int(v) for v in run_plan(SortPlan("shared"), jnp.array([3, 1, 2]))]
    [1, 2, 3]
    """
    if not ascending and plan.strategy == "cluster":
        raise ValueError(
            "the cluster strategy sorts ascending only; for descending "
            "distributed sorts use repro.engine.sort_kv(ascending=False)"
        )
    if plan.strategy == "shared":
        return shared_memory_sort(
            x,
            n_threads=plan.n_threads,
            local_impl=plan.local_impl,
            ascending=ascending,
            block_n=plan.block_n,
        )
    if mesh is None or axis is None:
        raise ValueError(f"plan strategy {plan.strategy!r} requires mesh= and axis=")
    if plan.strategy == "distributed_merge":
        kwargs.setdefault("local_impl", plan.local_impl)
        kwargs.setdefault("block_n", plan.block_n)
        out = distributed_merge_sort(x, mesh, axis, **kwargs)
        return out if ascending else jnp.flip(out, -1)
    if plan.strategy == "cluster":
        kwargs.setdefault("local_impl", plan.local_impl)
        kwargs.setdefault("block_n", plan.block_n)
        # partitioner_mode folds the plan's partition override in: a plan
        # promoted to the sample partition executes sample splitters even
        # though its tuned mode is still the radix one it was swept at
        kwargs.setdefault("mode", plan.partitioner_mode())
        kwargs.setdefault("capacity_factor", plan.capacity_factor)
        return cluster_sort(x, mesh, axis, **kwargs)
    raise ValueError(f"unknown plan strategy {plan.strategy!r}")


def _time_plan_reps(plan, x, mesh, axis, *, reps: int, **kwargs) -> list:
    """Per-rep wall-clock timings (microseconds) after one warmup call.

    Each rep blocks individually so the list supports order statistics —
    the distributed sweep wants the *median* rep (robust to one gloo
    hiccup), while the single-process sweep keeps the historical mean.
    """
    out = run_plan(plan, x, mesh=mesh, axis=axis, **kwargs)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run_plan(plan, x, mesh=mesh, axis=axis, **kwargs)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    return times


def _time_plan(plan, x, mesh, axis, *, reps: int, **kwargs) -> float:
    times = _time_plan_reps(plan, x, mesh, axis, reps=reps, **kwargs)
    return sum(times) / len(times)


def _median(xs) -> float:
    s = sorted(xs)
    k = len(s) // 2
    return s[k] if len(s) % 2 else 0.5 * (s[k - 1] + s[k])


# ------------------------------------------------ distributed coordination ---
# A rank-coordinated sweep needs three collectives the single-process planner
# never had: a barrier so every rank times the same candidate over the same
# quiet wire, a max-over-ranks reduction so every rank scores a candidate by
# its *slowest* participant (the number that actually bounds a distributed
# sort), and a broadcast so the winner every rank proceeds with is rank 0's
# pick by construction, not N locally-identical argmins trusted to agree.

def _dist_barrier(tag: str) -> None:
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)


def _max_over_ranks(value: float) -> float:
    """Reduce one per-rank scalar to its max across all processes.

    Every rank must call this (it is a collective).
    """
    import numpy as np
    from jax.experimental import multihost_utils

    got = np.asarray(
        multihost_utils.process_allgather(np.asarray(value, np.float64))
    )
    return float(np.max(got))


# the fixed wire size for the winning-plan broadcast: collectives need every
# rank to contribute identical shapes, so rank 0's JSON is padded to this
_PLAN_WIRE_BYTES = 4096


def _broadcast_plan(plan: Optional["SortPlan"]) -> "SortPlan":
    """Broadcast rank 0's winning plan to every rank (collective).

    Serialized as zero-padded JSON in a fixed-size uint8 buffer (JSON never
    contains NUL, so stripping the padding is unambiguous).  Non-zero ranks'
    ``plan`` argument is ignored — the return value is authoritative.
    """
    import numpy as np
    from jax.experimental import multihost_utils

    buf = np.zeros(_PLAN_WIRE_BYTES, np.uint8)
    if jax.process_index() == 0:
        if plan is None:
            raise RuntimeError("rank 0 has no winning plan to broadcast")
        payload = json.dumps(plan.to_dict()).encode()
        if len(payload) > _PLAN_WIRE_BYTES:
            raise ValueError(f"plan JSON exceeds {_PLAN_WIRE_BYTES} bytes")
        buf[: len(payload)] = np.frombuffer(payload, np.uint8)
    # allgather rather than broadcast_one_to_all: the gather keeps each
    # rank's buffer byte-exact as its own row, and every rank decodes the
    # same authoritative row 0 — still one agreement collective.
    rows = np.asarray(multihost_utils.process_allgather(buf))
    out = rows[0] if rows.ndim == 2 else rows
    return SortPlan.from_dict(json.loads(bytes(out).rstrip(b"\x00").decode()))


PALLAS_BLOCK_SWEEP = (256, 512, 1024)

# Off-TPU the Pallas kernels run in interpret mode, which is a correctness
# path, not a perf path — timing it on multi-million-key buckets would stall
# an autotune sweep for minutes to learn nothing. Cells above this size only
# sweep pallas candidates on a real TPU backend.
PALLAS_INTERPRET_MAX = 1 << 16


def candidate_plans(mesh=None, *, quick: bool = False):
    """The tuning grid: strategies x local_impl (x capacity for model D).

    ``local_impl='pallas'`` enters the sweep with one candidate per tile
    width in ``PALLAS_BLOCK_SWEEP`` (quick mode: just the smallest), so the
    tuned plan pins the ``block_n`` that measured fastest for its cell.
    """
    impls = ("xla", "merge") if quick else tuple(i for i in LOCAL_SORTS if i != "pallas")
    cands = [SortPlan("shared", local_impl=i) for i in impls]
    blocks = PALLAS_BLOCK_SWEEP[:1] if quick else PALLAS_BLOCK_SWEEP
    cands += [SortPlan("shared", local_impl="pallas", block_n=b) for b in blocks]
    if mesh is not None:
        cands += [SortPlan("distributed_merge", local_impl="xla")]
        cfs = (2.0,) if quick else (1.5, 2.0)
        # sweep the partition policy too: the composite-splitter sample mode
        # and (full sweeps only) the auto-ranged radix mode compete with the
        # historic plain-splitters mode on the measured workload
        modes = ("splitters", "sample") if quick else ("splitters", "sample", "radix")
        cands += [
            SortPlan("cluster", local_impl="xla", capacity_factor=cf, mode=md)
            for cf in cfs
            for md in modes
        ]
    return cands


class Planner:
    """Plan table: lookup tuned plans, autotune missing cells, persist JSON.

    Beyond the tuned-plan table, the planner closes the capacity-learning
    loop (repro.engine.adapt): ``recorder`` hands ``cluster_sort`` /
    ``cluster_sort_kv`` a telemetry callback bound to a plan-cache key,
    ``observe_exchange`` folds each observation into a learned per-key
    ``capacity_factor``, and ``plan_for`` serves cluster plans with the
    learned factor applied — persisted through the JSON plan cache so the
    lesson survives restarts.

    >>> Planner().plan_for(1000, jnp.int32).strategy   # untuned: default rule
    'shared'
    """

    def __init__(
        self, path: Optional[str] = None, *, learned_scope: Optional[str] = None
    ):
        scope = learned_scope or os.environ.get("REPRO_LEARNED_SCOPE", "global")
        if scope not in LEARNED_SCOPES:
            raise ValueError(f"learned_scope must be one of {LEARNED_SCOPES}")
        self.path = path
        self.learned_scope = scope
        self.plans: Dict[str, SortPlan] = {}
        self.telemetry = ExchangeTelemetry()
        self.learner = CapacityLearner()
        self.learned: Dict[str, LearnedCapacity] = {}
        # services register their stats here so overflow retries/recompiles
        # observed on the exchange path surface in serving telemetry
        self._stats_sinks: list = []
        self._lock = threading.Lock()
        if path and os.path.exists(path):
            self.load(path)

    # ------------------------------------------------------------ storage ---
    @staticmethod
    def _parse_doc(doc) -> tuple:
        """Validate one plan-cache JSON document -> (plans, learned).

        Raises on anything malformed; graceful-degradation policy lives in
        the callers (``load`` warns and keeps state, ``save`` merges from
        nothing).
        """
        if doc.get("version") not in _LOADABLE_VERSIONS:
            raise ValueError(f"plan cache version {doc.get('version')!r} unsupported")
        raw = doc["plans"]
        if not isinstance(raw, dict):
            raise ValueError("'plans' must be an object")
        plans = {}
        for k, v in raw.items():
            if not isinstance(v, dict):
                raise ValueError(f"plan entry {k!r} is not an object")
            plan = SortPlan.from_dict(v)  # unknown fields: forward-compat
            if plan.strategy not in _PLAN_STRATEGIES:
                raise ValueError(
                    f"plan entry {k!r} has unknown strategy {plan.strategy!r}"
                )
            if plan.partition is not None and plan.partition not in PARTITION_MODES:
                raise ValueError(
                    f"plan entry {k!r} has unknown partition {plan.partition!r}"
                )
            plans[k] = plan
        raw_learned = doc.get("learned", {})  # absent in v1 files
        if not isinstance(raw_learned, dict):
            raise ValueError("'learned' must be an object")
        learned = {}
        for k, v in raw_learned.items():
            if not isinstance(v, dict) or "capacity_factor" not in v:
                raise ValueError(f"learned entry {k!r} is malformed")
            learned[k] = LearnedCapacity.from_dict(v)
        return plans, learned

    @staticmethod
    def _merge_learned(
        mine: Dict[str, LearnedCapacity], theirs: Dict[str, LearnedCapacity]
    ) -> Dict[str, LearnedCapacity]:
        """Union two learned tables; shared keys merge via
        ``LearnedCapacity.merge`` (more-informed lineage wins — commutative
        and idempotent, so any interleaving of concurrent writers converges
        to the same table)."""
        out = dict(theirs)
        for k, entry in mine.items():
            other = out.get(k)
            out[k] = entry.merge(other) if other is not None else entry
        return out

    def load(self, path: str, *, strict: bool = False) -> "Planner":
        """Load a plan-cache file; a serving process must never die because a
        tuned-plans file rotted on disk.  Corrupt/truncated JSON, an unknown
        version, or malformed plan entries warn and keep the **current**
        table — empty at construction (every lookup then uses
        ``default_plan``), or the last-known-good plans when a live process
        re-loads a file that rotted mid-write.  Pass ``strict=True`` to
        re-raise instead (tooling that writes the file).

        The ``learned`` section **merges** into in-memory state instead of
        replacing it (field-wise max per shared key): a live rank re-reading
        a shared ``$REPRO_SORT_PLANS`` file picks up what other ranks
        learned without discarding its own observations.  The ``plans``
        table keeps replace semantics — the file is the tuning authority.
        """
        try:
            with open(path) as f:
                doc = json.load(f)
            plans, learned = self._parse_doc(doc)
        except Exception as e:
            if strict:
                raise
            warnings.warn(
                f"ignoring unreadable plan cache {path!r} ({e}); "
                f"keeping the {len(self.plans)} previously loaded plan(s)",
                RuntimeWarning,
                stacklevel=2,
            )
            return self
        with self._lock:
            self.plans = plans
            self.learned = self._merge_learned(self.learned, learned)
        return self

    def save(self, path: Optional[str] = None) -> str:
        """Persist plans + learned state with concurrent-writer safety.

        The write is a **read-merge-write** under an advisory ``fcntl`` lock
        (``<path>.lock``): re-read the file, union plan keys this planner
        does not carry, merge the on-disk ``learned`` section per key
        (``LearnedCapacity.merge``), then atomically ``os.replace`` the
        result into place.  Two ranks of a ``jax.distributed`` job learning
        capacity factors into one ``$REPRO_SORT_PLANS`` file therefore never
        clobber each other — the surviving file carries both ranks' entries
        no matter how the saves interleave (tests/test_plan_cache_concurrency
        in-process, tests/multihost/ across real processes).
        """
        path = path or self.path
        if path is None:
            raise ValueError("no path given and Planner has no default path")
        # the whole write happens under the thread lock: concurrent
        # telemetry-driven saves in this process serialize here, and the
        # fcntl lock extends the same exclusion across processes
        with self._lock:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with _plan_file_lock(path):
                disk_plans: Dict[str, SortPlan] = {}
                disk_learned: Dict[str, LearnedCapacity] = {}
                if os.path.exists(path):
                    try:
                        with open(path) as f:
                            disk_plans, disk_learned = self._parse_doc(json.load(f))
                    except Exception:
                        # a rotted file must not block persisting fresh state;
                        # there is nothing trustworthy in it to preserve
                        disk_plans, disk_learned = {}, {}
                plans = {**disk_plans, **self.plans}  # ours win shared keys
                learned = self._merge_learned(self.learned, disk_learned)
                doc = {
                    "version": _PLAN_VERSION,
                    "plans": {k: p.to_dict() for k, p in sorted(plans.items())},
                    "learned": {
                        k: c.to_dict() for k, c in sorted(learned.items())
                    },
                }
                # per-pid tmp name: a crashed writer's leftover can never be
                # overwritten mid-rename by another rank on the same host
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(doc, f, indent=1)
                os.replace(tmp, path)
            self.path = self.path or path
        return path

    # ------------------------------------------------------------- lookup ---
    def lookup(self, n: int, dtype, mesh=None) -> Optional[SortPlan]:
        return self.plans.get(plan_key(n, dtype, mesh))

    def warmup_cells(self, mesh=None):
        """The (size_bucket, dtype name) cells this plan table names for the
        given hardware fingerprint — the enumeration AOT warmup compiles
        ahead of traffic (``repro.engine.frontend.warmup``).

        Both the tuned ``plans`` table and the ``learned`` capacity section
        contribute: a cell with learned state but no tuned plan still proves
        real traffic landed there, and warming it is exactly as valuable.
        Non-sort keys (the MoE dispatch cells, ``moe/E<e>k<k>|...``) are not
        executable-cache cells and are skipped.

        >>> p = Planner()
        >>> p.plans["4096|int32|" + mesh_fingerprint()] = SortPlan("shared")
        >>> p.plans["moe/E8k2|256|float32|" + mesh_fingerprint()] = SortPlan()
        >>> p.warmup_cells()
        [(4096, 'int32')]
        """
        fp = mesh_fingerprint(mesh)
        my_suffix = f"@h{jax.process_index()}"  # per_host-scoped learned keys
        cells = set()
        for key in list(self.plans) + list(self.learned):
            if key.endswith(my_suffix):
                key = key[: -len(my_suffix)]  # this host's cells warm here;
            parts = key.split("|")  # other hosts' fail the fp match below
            if len(parts) != 3 or not parts[0].isdigit():
                continue  # MoE dispatch cells and future non-sort keys
            bucket, dtype_name, key_fp = parts
            if key_fp == fp:
                cells.add((int(bucket), dtype_name))
        return sorted(cells)

    def plan_for(self, n: int, dtype, mesh=None) -> SortPlan:
        """Tuned plan if one exists, else the pre-engine default rule — with
        the learned capacity factor folded into cluster plans (so
        steady-state callers size model-D slabs right on their first
        compile) and the skew-promotion latch applied: a radix-family plan
        whose cell the learner promoted comes back with
        ``partition="sample"``."""
        plan = self.lookup(n, dtype, mesh) or default_plan(mesh)
        if plan.strategy == "cluster":
            key = plan_key(n, dtype, mesh)
            promoted, _ = self.promotion_state(key)
            if promoted == "sample" and plan.effective_partition() == "radix":
                plan = replace(plan, partition="sample")
            cf = self.capacity_factor_for(key, default=plan.capacity_factor)
            if cf != plan.capacity_factor:
                plan = replace(plan, capacity_factor=cf)
        return plan

    # -------------------------------------------------- capacity learning ---
    def scoped_key(self, key: str) -> str:
        """Apply the learned-factor scope policy to a plan-cache key.

        ``global`` scope (default) returns the key unchanged: every rank of
        a multi-process job reads and merges one shared entry, so the most
        conservative rank's factor wins — right when skew follows the
        *data*, which any rank may receive.  ``per_host`` scope suffixes
        ``@h<process_index>``: each host learns its own factor — right when
        skew follows the *host* (a shard pinned to hot keys), where one hot
        host must not inflate every host's slab memory.  Both read and
        write paths (``capacity_factor_for`` / ``observe_exchange``) apply
        the same scoping, so a planner always reads what it wrote.
        """
        if self.learned_scope == "per_host":
            return f"{key}@h{jax.process_index()}"
        return key

    def capacity_factor_for(self, key: str, default: float = 2.0) -> float:
        """The learned capacity factor for a plan-cache key (``default``
        until telemetry for that key has taught us otherwise)."""
        key = self.scoped_key(key)
        with self._lock:
            entry = self.learned.get(key)
        return entry.capacity_factor if entry is not None else default

    def promotion_state(self, key: str) -> tuple:
        """``(partition, skew_strikes)`` of a key's learned entry — the
        skew-promotion latch, observable without touching private state.
        ``(None, 0)`` until the key has radix-skew history; ``("sample", _)``
        once promotion latched (the scope policy is applied, so a caller
        always reads the entry its own observations feed)."""
        key = self.scoped_key(key)
        with self._lock:
            entry = self.learned.get(key)
        if entry is None:
            return (None, 0)
        return (entry.partition, entry.skew_strikes)

    # persistence debounce: a learned-factor move below this fraction of the
    # default stays in memory only — skew that fluctuates call-to-call must
    # not turn the sort hot path into a full-file rewrite per call
    _SAVE_REL_DELTA = 0.05

    def observe_exchange(
        self, key: str, obs: ExchangeObservation, *, default: float = 2.0
    ) -> LearnedCapacity:
        """Fold one exchange observation into the learned table (and the
        telemetry ledger).  Persists when the planner has a backing file and
        the learned factor moved *materially* (>= ``_SAVE_REL_DELTA`` of the
        default, or landed exactly back on it) — steady state costs zero
        writes, and jittery skew costs only in-memory updates."""
        key = self.scoped_key(key)
        self.telemetry.record(key, obs)
        with self._lock:
            prev = self.learned.get(key)
            prev_cf = prev.capacity_factor if prev else default
            cf = self.learner.update(prev_cf, obs, default=default)
            prev_part = prev.partition if prev else None
            strikes = self.learner.promotion_strikes(
                prev.skew_strikes if prev else 0, obs
            )
            part = prev_part
            calm = prev.calm_streak if prev else 0
            demotions = prev.demotions if prev else 0
            if part != "sample" and self.learner.should_promote(strikes):
                part = "sample"  # the latch: merge keeps it within this
                calm = 0  # generation — only the probation below can undo it
            elif part == "sample":
                # promoted cell on probation: long calm stretches demote it
                # back to the radix family, one generation up so concurrent
                # writers holding the stale promotion can't flap it back
                calm = self.learner.calm_streak(calm, obs)
                if self.learner.should_demote(calm, demotions):
                    part, strikes, calm = None, 0, 0
                    demotions += 1
            entry = LearnedCapacity(
                capacity_factor=cf,
                peak_factor=max(
                    prev.peak_factor if prev else 0.0, obs.required_factor()
                ),
                observations=(prev.observations if prev else 0) + 1,
                partition=part,
                skew_strikes=strikes,
                calm_streak=calm,
                demotions=demotions,
            )
            self.learned[key] = entry
            changed = part != prev_part or (
                cf != prev_cf
                and (
                    abs(cf - prev_cf) >= self._SAVE_REL_DELTA * default
                    or cf == default  # the decay's landing point: worth a write
                )
            )
            self._stats_sinks = [r for r in self._stats_sinks if r() is not None]
            sinks = list(self._stats_sinks)
        for ref in sinks:
            svc = ref()
            if svc is not None:
                svc._note_exchange(obs)
        if changed and self.path:
            self.save()
        return entry

    def exchange_recorder(self, key: str, *, default: float = 2.0):
        """A telemetry callback bound to this planner and an arbitrary
        plan-cache key.  Sort cells use ``(n, dtype, mesh)`` keys via
        ``recorder``; the MoE dispatch path binds its own
        ``moe/E<experts>k<top_k>|...`` keys (``models.moe.moe_plan_key``) —
        one learned table, many exchange consumers."""

        def record(**kwargs) -> None:
            self.observe_exchange(key, ExchangeObservation(**kwargs), default=default)

        return record

    def recorder(self, n: int, dtype, mesh=None, *, default: float = 2.0):
        """A telemetry callback for ``cluster_sort(telemetry=...)`` bound to
        this planner and the (n, dtype, mesh) plan-cache key — the glue that
        closes the capacity-learning loop."""
        return self.exchange_recorder(plan_key(n, dtype, mesh), default=default)

    def cluster_kwargs(
        self,
        n: int,
        dtype,
        mesh=None,
        *,
        default: Optional[float] = None,
        mode: Optional[str] = None,
    ) -> dict:
        """The ``capacity_factor=`` / ``telemetry=`` kwargs that close the
        capacity-learning loop for one cluster call — the one policy both
        ``repro.sort`` and ``engine.sort_kv`` apply (only when the caller
        passed neither kwarg: an explicit value opts the call out of the
        whole loop, reading and writing).  ``default`` is the learner's
        floor; when omitted, a tuned cluster plan's own factor (if any) is
        used so a cell that won at a lean factor is never re-inflated.

        ``mode`` is a *hint*, not a request: pass the partitioner mode the
        caller will run (or None if the caller uses the default).  When the
        caller has no explicit mode and this cell's learned entry carries
        the skew-promotion latch, the returned dict additionally includes
        ``"mode": "sample"`` — and the learner floor drops to
        ``SAMPLE_DEFAULT_FACTOR`` so the capacity factor the radix era
        inflated decays back toward ~1.  A caller-chosen mode is always
        respected (no key collision, no silent override)."""
        if default is None:
            base = self.lookup(n, dtype, mesh)
            default = (
                base.capacity_factor
                if base is not None and base.strategy == "cluster"
                else SortPlan.capacity_factor
            )
        key = plan_key(n, dtype, mesh)
        out = {}
        if mode is None:
            promoted, _ = self.promotion_state(key)
            if promoted == "sample":
                out["mode"] = "sample"
                default = min(default, SAMPLE_DEFAULT_FACTOR)
        out["capacity_factor"] = self.capacity_factor_for(key, default=default)
        out["telemetry"] = self.recorder(n, dtype, mesh, default=default)
        return out

    def add_stats_sink(self, service) -> None:
        """Register a service whose stats should see exchange retry/recompile
        counts (held weakly; dead services are dropped on the next observe)."""
        with self._lock:
            self._stats_sinks.append(weakref.ref(service))

    # ----------------------------------------------------------- autotune ---
    # observability for the single-writer election: True iff the *last*
    # autotune call on this planner persisted the plan file from this
    # process (rank 0 in a distributed sweep; any rank single-process)
    last_autotune_wrote: bool = False

    def autotune(
        self,
        n: int,
        dtype=jnp.int32,
        *,
        mesh=None,
        axis: Optional[str] = None,
        reps: int = 3,
        quick: bool = False,
        seed: int = 0,
        save: bool = True,
        distributed: Optional[bool] = None,
        candidates=None,
        on_candidate=None,
        **kwargs,
    ) -> SortPlan:
        """Microbenchmark every candidate on synthetic keys; persist winner.

        Timed at the size bucket (next pow2 of ``n``) so every n in the bucket
        shares the plan — the same bucketing the compiled-executable cache
        uses, keeping plan granularity == compilation granularity.

        **Distributed sweeps.**  Under multi-process ``jax.distributed``
        (``distributed=None`` auto-detects ``jax.process_count() > 1``;
        pass ``False`` to opt a rank-divergent caller out) the sweep is
        rank-coordinated: a barrier precedes each candidate so every rank
        times it over a quiet wire, each rank scores the candidate by its
        **median** rep (robust to one slow rep), the per-rank scores reduce
        by **max over ranks** (a distributed sort is as slow as its slowest
        participant — and the reduced table is bit-identical everywhere, so
        every rank computes the same argmin), rank 0's winner is broadcast
        to all ranks as an explicit agreement step, and **rank 0 alone**
        writes the plan file through the fcntl-locked merge-on-save path —
        a final barrier holds the other ranks until the file is on disk.
        The cell lands under the ``/procs<P>x<D>`` fingerprint, so it never
        masquerades as a single-host plan.  ``last_autotune_wrote`` records
        which process performed the save.

        ``candidates=`` substitutes an explicit plan list for the default
        grid (how tests and smoke jobs keep a sweep tiny); ``on_candidate``
        is called as ``on_candidate(i, plan)`` before each candidate is
        timed — the multihost fault-injection battery hooks rank crashes
        and hangs there.
        """
        import numpy as np

        if distributed is None:
            distributed = jax.process_count() > 1
        nb = next_pow2(n)
        x = jnp.asarray(
            np.random.default_rng(seed).integers(100, 1000, size=nb).astype("int64"),
            jnp.dtype(dtype),
        )
        x_mesh = x
        if mesh is not None:
            P_ = mesh.shape[axis]
            if nb % P_:
                raise ValueError(
                    f"axis size {P_} must divide the size bucket {nb}"
                )
            if distributed:
                # multi-process meshes need committed global arrays; the
                # single-process forced mesh auto-shards host-local ones
                from jax.sharding import NamedSharding, PartitionSpec

                x_mesh = jax.device_put(
                    x, NamedSharding(mesh, PartitionSpec(axis))
                )
        key = plan_key(nb, dtype, mesh)
        cands = (
            candidate_plans(mesh, quick=quick)
            if candidates is None
            else list(candidates)
        )
        interpret_backend = jax.default_backend() != "tpu"
        best = None
        for i, cand in enumerate(cands):
            if (
                interpret_backend
                and cand.local_impl == "pallas"
                and nb > PALLAS_INTERPRET_MAX
            ):
                continue  # interpret-mode kernels: correctness path, not timeable
            if on_candidate is not None:
                on_candidate(i, cand)
            if distributed:
                _dist_barrier(f"autotune:{key}:{i}")
            arr = x if cand.strategy == "shared" else x_mesh
            times = _time_plan_reps(cand, arr, mesh, axis, reps=reps, **kwargs)
            us = _median(times) if distributed else sum(times) / len(times)
            if distributed:
                us = _max_over_ranks(us)
            cand = replace(cand, us_per_call=round(us, 2))
            if best is None or cand.us_per_call < best.us_per_call:
                best = cand
        if best is None:
            raise RuntimeError(f"autotune: no timeable candidate for {key}")
        if distributed:
            # every rank already holds the same argmin (the reduced table is
            # identical), but agreement is asserted, not assumed: rank 0's
            # pick is what everyone proceeds with, bit for bit
            best = _broadcast_plan(best)
        self.plans[key] = best
        self.last_autotune_wrote = False
        if save and self.path:
            if not distributed or jax.process_index() == 0:
                self.save()
                self.last_autotune_wrote = True
            if distributed:
                # hold every rank until the winner is on disk: a rank that
                # re-loads the shared file right after autotune must see it
                _dist_barrier(f"autotune:{key}:saved")
        return best


_DEFAULT: Optional[Planner] = None


def default_planner() -> Planner:
    """Process-wide planner; honours $REPRO_SORT_PLANS as its backing file.

    >>> default_planner() is default_planner()   # one table per process
    True
    """
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Planner(os.environ.get("REPRO_SORT_PLANS"))
    return _DEFAULT


def autotune(n: int, dtype=jnp.int32, **kwargs) -> SortPlan:
    """Module-level convenience: autotune into the default planner.

    >>> autotune(64, reps=1, quick=True, save=False).strategy
    'shared'
    """
    return default_planner().autotune(n, dtype, **kwargs)
