"""repro.engine — autotuned sort-plan engine (serving-grade front end).

planner  : SortPlan + autotuner + persistent JSON plan cache; candidate
           sweep covers local_impl='pallas' with a tuned block_n grid;
           folds learned capacity factors into cluster plans
adapt    : closed-loop tuning — ExchangeTelemetry + CapacityLearner turn
           observed model-D overflow into learned capacity factors;
           ManualClock, the deterministic clock tests inject
cache    : compiled-executable cache with pow2 shape bucketing
kv       : sort_kv / argsort / sort_pairs / topk — records, not just keys
           (impl='pallas' runs the kernels' stable (key, rank) network)
service  : SortService — ragged batches in, zero-recompile sorts out
frontend : SortFrontend, the one queued front door over SortService —
           cross-caller micro-batching, AOT warmup of the whole plan-cache
           executable ladder, per-tenant weighted admission with EDF
           dispatch and reject-with-reason load shed, and a reproducible
           open-loop load simulation (docs/serving.md)

See docs/architecture.md for the layer map and request lifecycle.
"""
from .adapt import (
    CapacityLearner,
    ExchangeObservation,
    ExchangeTelemetry,
    LearnedCapacity,
    ManualClock,
)
from .cache import CompiledCache, size_bucket
from .kv import argsort, cluster_sort_kv, sort_kv, sort_pairs, topk
from .planner import (
    Planner,
    SortPlan,
    autotune,
    default_planner,
    mesh_fingerprint,
    parse_plan_key,
    plan_from_strategy,
    plan_key,
    run_plan,
)
from .frontend import (
    LoadReport,
    ShedError,
    SortFrontend,
    Tenant,
    Ticket,
    WarmupReport,
    make_trace,
    run_load,
    warmup,
)
from .service import ServiceStats, SortService

__all__ = [
    "CapacityLearner",
    "ExchangeObservation",
    "ExchangeTelemetry",
    "LearnedCapacity",
    "ManualClock",
    "CompiledCache",
    "size_bucket",
    "argsort",
    "cluster_sort_kv",
    "sort_kv",
    "sort_pairs",
    "topk",
    "Planner",
    "SortPlan",
    "autotune",
    "default_planner",
    "mesh_fingerprint",
    "parse_plan_key",
    "plan_from_strategy",
    "plan_key",
    "run_plan",
    "ServiceStats",
    "SortService",
    "LoadReport",
    "ShedError",
    "SortFrontend",
    "Tenant",
    "Ticket",
    "WarmupReport",
    "make_trace",
    "run_load",
    "warmup",
]
