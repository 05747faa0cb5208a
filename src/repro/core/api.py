"""Public sort API — a thin wrapper over the autotuned plan engine.

``sort(x)``                      -> planner-selected path (tuned plan if the
                                    engine has one for this size/dtype/mesh,
                                    else the paper's default rule: model B on
                                    one device, model D on a mesh)
``sort(x, mesh=..., axis=...)``  -> model D cluster sort (production path)
``strategy=`` overrides: 'shared' / 'shared_hybrid' (B), 'shared_merge' (A),
'distributed_merge' (C), 'cluster' (D) — these bypass the planner's plan
*selection*. Cluster runs on a mesh still close the capacity-learning loop
through the default planner (learned ``capacity_factor`` + telemetry) unless
``capacity_factor=`` / ``telemetry=`` — or a full ``plan=``, which pins its
own ``capacity_factor`` — are passed explicitly.
``local_impl=`` / ``block_n=`` further override the per-partition sequential
sort of whichever plan is selected (e.g. ``local_impl='pallas'`` routes every
local sort through the VMEM-tiled Pallas kernel).

Key-value sorting, argsort, and the batched serving front door live in
``repro.engine`` (kv.py / service.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

__all__ = ["sort"]


@partial(jax.profiler.annotate_function, name="repro.sort.dispatch")
def sort(
    x: jax.Array,
    *,
    mesh=None,
    axis: Optional[str] = None,
    strategy: Optional[str] = None,
    plan=None,
    local_impl: Optional[str] = None,
    block_n: Optional[int] = None,
    n_threads: int = 8,
    ascending: bool = True,
    **kwargs,
):
    """Sort the last axis of ``x`` using one of the paper's parallel models.

    Precedence: explicit ``strategy=`` > explicit ``plan=`` (a
    ``repro.engine.SortPlan``) > tuned plan from the default planner >
    the paper's hard-coded rule.  ``local_impl=`` / ``block_n=`` rewrite the
    selected plan's local-sort fields whichever way it was chosen.

    Cluster plans close the capacity-learning loop by default: the call
    reports its exchange telemetry to the default planner and runs at that
    planner's learned ``capacity_factor`` for this (size, dtype, mesh) cell,
    so a workload that overflowed once never pays the overflow-retry
    recompile again.  Passing ``capacity_factor=`` / ``telemetry=`` — or an
    explicit ``plan=``, which pins the whole recipe including its
    ``capacity_factor`` — opts the call out of the loop, reading and
    writing (see repro.engine.adapt).

    >>> import jax.numpy as jnp
    >>> [int(v) for v in sort(jnp.array([3, 1, 2]))]
    [1, 2, 3]
    >>> [int(v) for v in sort(jnp.array([3, 1, 2]), strategy="shared",
    ...                       local_impl="pallas", n_threads=2)]
    [1, 2, 3]
    """
    from dataclasses import replace

    from repro.engine.planner import default_planner, plan_from_strategy, run_plan

    # an explicit plan= pins the full recipe — including capacity_factor —
    # so it must neither read nor mutate the learned table below (strategy=
    # only names a model family and keeps the loop on)
    pinned_plan = plan is not None and strategy is None
    if strategy is not None:
        plan = plan_from_strategy(strategy, n_threads=n_threads)
    elif plan is None:
        plan = default_planner().lookup(x.shape[-1], x.dtype, mesh)
        # with mesh= the documented return contract is cluster_sort's
        # (slab, valid) — only an explicit strategy=/plan= may change it, so
        # tuned non-cluster plans don't apply here
        if mesh is not None and (plan is None or plan.strategy != "cluster"):
            plan = plan_from_strategy("cluster")
        elif plan is None:  # pre-engine rule, honouring the n_threads argument
            plan = plan_from_strategy("shared_hybrid", n_threads=n_threads)
    if local_impl is not None:
        plan = replace(plan, local_impl=local_impl)
    if block_n is not None:
        plan = replace(plan, block_n=block_n)
    if (
        plan.strategy == "cluster"
        and mesh is not None
        and not pinned_plan
        and "capacity_factor" not in kwargs
        and "telemetry" not in kwargs
    ):
        # close the feedback loop: run at the learned capacity factor and
        # report this call's exchange telemetry back to the planner.  An
        # explicit capacity_factor=, telemetry=, or plan= opts out of the
        # WHOLE loop — a pinned experiment must neither read nor mutate the
        # process-wide learned state
        # mode=kwargs.get("mode") is the hint that keeps an explicit caller
        # mode authoritative; with no explicit mode, a skew-promoted cell
        # comes back with "mode": "sample" injected alongside the kwargs
        kwargs.update(
            default_planner().cluster_kwargs(
                x.shape[-1],
                x.dtype,
                mesh,
                default=plan.capacity_factor,
                mode=kwargs.get("mode"),
            )
        )
    return run_plan(plan, x, mesh=mesh, axis=axis, ascending=ascending, **kwargs)
