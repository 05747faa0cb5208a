"""repro.exchange — the unified adaptive exchange layer.

One implementation of "bucket, cap, all-to-all, retry-on-overflow, learn"
for every consumer in the codebase.  The paper's model D (one-step MSD-Radix
data distribution, ``core/cluster_sort.py``) and GShard/Switch-style MoE
expert dispatch (``models/moe.py``) are the same primitive wearing different
keys: an element (sort key / token) is assigned a bucket (radix digit /
expert id), shipped to the shard owning that bucket through a single
fixed-capacity ``all_to_all``, processed there (local sort / expert FFN),
and — for MoE — shipped back.  Both pay the same failure mode (a skewed
bucket distribution overflows the fixed slabs) and both feed the same
remedy (observed peak counts reported through ``ExchangeTelemetry`` become
learned capacity factors in the plan cache; see ``repro.engine.adapt``).

Modules:

slabs      : slab/capacity math — ``sentinel_for``, ``slab_capacity``,
             ``slab_geometry`` (model D), ``expert_capacity`` (MoE),
             ``slab_valid`` and ``compact_slabs`` (slab -> dense result)
collective : the wire — ``partition_exchange`` / ``combine_exchange`` /
             ``ExchangeResult`` (single all_to_all each way, optional int8
             compression); ``sorted_runs_exchange`` + ``bucket_counts``, the
             keys-only sort's slicing twin
retry      : ``run_with_capacity_retries`` — the capacity-doubling retry
             driver with per-attempt recompile accounting
telemetry  : ``ExchangeObservation`` / ``ExchangeTelemetry`` — the ledger
             the learning loop feeds on
partition  : the bucket-assignment policy — ``radix_bucket_ids`` (auto-ranged
             equal-width) vs ``sample_partition_ids`` (composite-splitter
             samplesort, balanced under any skew), ``partition_of``
             classifying every partitioner mode into the two families the
             planner persists and the learner promotes between

See docs/exchange.md for the layer's design and the model-D-sort vs
MoE-dispatch comparison.
"""
from .collective import (
    ExchangeResult,
    bucket_counts,
    combine_exchange,
    partition_exchange,
    sorted_runs_exchange,
)
from .partition import (
    DEFAULT_OVERSAMPLE,
    PARTITION_MODES,
    choose_splitters,
    partition_of,
    radix_bucket_ids,
    sample_partition_ids,
    splitter_bucket,
    splitters_from_sample,
)
from .retry import run_with_capacity_retries
from .slabs import (
    compact_slabs,
    expert_capacity,
    sentinel_for,
    slab_capacity,
    slab_geometry,
    slab_valid,
)
from .telemetry import ExchangeObservation, ExchangeTelemetry

# PARTITION_MODES / DEFAULT_OVERSAMPLE are importable constants but stay out
# of __all__: the docs gate doctests every __all__ export's docstring, and
# plain constants carry their type's docstring
__all__ = [
    "ExchangeObservation",
    "ExchangeResult",
    "ExchangeTelemetry",
    "bucket_counts",
    "choose_splitters",
    "combine_exchange",
    "compact_slabs",
    "expert_capacity",
    "partition_exchange",
    "partition_of",
    "radix_bucket_ids",
    "run_with_capacity_retries",
    "sample_partition_ids",
    "sentinel_for",
    "slab_capacity",
    "slab_geometry",
    "slab_valid",
    "sorted_runs_exchange",
    "splitter_bucket",
    "splitters_from_sample",
]
