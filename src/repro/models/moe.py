"""Mixture-of-Experts layer with sort-based dispatch — the paper's model D as
a first-class framework feature.

Token routing *is* the paper's cluster sort (DESIGN.md §3): the expert id is
the key's "most significant digit", expert-parallel shards are the cluster
nodes, and dispatch is one MSD-radix ``all_to_all`` each way with **zero**
inter-shard merging — the exact property the paper built model D for. The
stable grouping sort inside ``partition_exchange`` preserves arrival order per
expert (the paper's stability argument, doing real work here).

Everything slab-shaped comes from ``repro.exchange`` (the unified adaptive
exchange layer): ``partition_exchange``/``combine_exchange`` are the wire,
``expert_capacity`` is the one capacity formula (shared rounding with the
sort path's ``slab_geometry``), and ``moe_apply_adaptive`` closes the same
capacity-learning loop model-D sort has — per-(n_experts, top_k, token
bucket) expert capacity factors learned from observed telemetry and
persisted in the plan cache, so a skewed routing distribution pays its
overflow/drop penalty once per deployment, zero after restart.

Layout: experts are sharded over the ``model`` mesh axis; tokens entering the
layer are sharded over ``(pod, data, model)`` (the reshard is a free view
change for XLA). Fixed per-(sender, expert) capacity with overflow-drop
follows GShard/Switch semantics; ``capacity_factor`` controls it, the train
loop monitors the overflow signal (fault_tolerance.py treats routing collapse
as an anomaly), and the aux load-balancing loss keeps the router near-uniform.
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.exchange import (
    combine_exchange,
    expert_capacity,
    partition_exchange,
    run_with_capacity_retries,
)
from .layers import Params, linear_init

DEFAULT_CAPACITY_FACTOR = 2.0


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden dim
    n_experts: int
    top_k: int
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR
    mlp_gated: bool = True
    compress_dispatch: bool = False   # int8 a2a payloads (beyond paper)


def moe_init(key, cfg: MoEConfig, dtype, *, ep_shards: int) -> Params:
    """Expert weights stacked (E_pad, ...); E padded to a multiple of ep_shards
    with dummy experts the router can never select (logits masked)."""
    e_pad = math.ceil(cfg.n_experts / ep_shards) * ep_shards
    ks = jax.random.split(key, 4)
    s_in = cfg.d_model ** -0.5
    s_out = cfg.d_ff ** -0.5
    p = {
        "router": linear_init(ks[0], cfg.d_model, e_pad, jnp.float32),
        "w_in": (jax.random.normal(ks[1], (e_pad, cfg.d_model, cfg.d_ff)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ks[2], (e_pad, cfg.d_ff, cfg.d_model)) * s_out).astype(dtype),
    }
    if cfg.mlp_gated:
        p["w_gate"] = (
            jax.random.normal(ks[3], (e_pad, cfg.d_model, cfg.d_ff)) * s_in
        ).astype(dtype)
    return p


def router_probs(p: Params, cfg: MoEConfig, x: jax.Array):
    """x (T, D) -> (probs (T, E_pad), top_idx (T, k), top_gate (T, k), aux_loss)."""
    e_pad = p["router"]["w"].shape[-1]
    logits = (x.astype(jnp.float32) @ p["router"]["w"]).astype(jnp.float32)
    if e_pad != cfg.n_experts:  # mask dummy padding experts
        pad_mask = jnp.arange(e_pad) >= cfg.n_experts
        logits = jnp.where(pad_mask, -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_gate, top_idx = jax.lax.top_k(probs, cfg.top_k)
    top_gate = top_gate / jnp.maximum(top_gate.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * P_e  (f = token fraction, P = prob mass)
    f = jnp.zeros((e_pad,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    # sum/max(T,1), not mean: the mean of an empty axis is NaN, which would
    # poison the aux loss (and every grad) for a drained shard/microbatch
    P_mass = probs.sum(axis=0) / max(x.shape[0], 1)
    aux = cfg.n_experts * jnp.sum(f * P_mass)
    return probs, top_idx, top_gate, aux


def collapse_router(p: Params, logit_scale: float = 10.0) -> Params:
    """A copy of ``p`` whose router concentrates routing on a few low-index
    experts — the worst-case skew demos and tests use to exercise the
    capacity-learning loop.

    The single nonzero router column gives expert 0 logit
    ``logit_scale * sum(x)`` while every other real expert sits at exactly
    0: tokens with positive ``sum(x)`` route to expert 0, the rest tie at 0
    and drain to the lowest-index remaining experts (``top_k`` ties break
    low), so a handful of experts absorb the whole batch regardless of the
    token distribution.
    """
    w = p["router"]["w"]
    # index the expert axis from the end: the router weight is (D, E_pad)
    # standalone but (n_groups, D, E_pad) inside stacked train params
    return {**p, "router": {"w": jnp.zeros_like(w).at[..., 0].set(logit_scale)}}


def moe_apply_local(
    p: Params,
    cfg: MoEConfig,
    x: jax.Array,
    axis_name: str,
    all_axes: tuple = (),
    *,
    capacity: Optional[int] = None,
    with_stats: bool = False,
):
    """shard_map body. x: (T_loc, D) local token slice; expert weights already
    sliced to (E_loc, ...) by shard_map in_specs. Returns (y (T_loc, D), aux,
    overflow) with aux/overflow replicated over ``all_axes``.

    ``capacity`` overrides the per-(sender, expert) token capacity (default:
    ``expert_capacity`` from ``cfg.capacity_factor`` — the shared exchange-
    layer formula).  ``with_stats=True`` returns
    ``(y, aux, dropped, counts, peak, overflow)`` instead: ``counts`` are
    EP-group-global per-expert token counts, ``peak`` the max per-(sender,
    expert) count, ``dropped`` the EP-group total of overflow-dropped tokens
    — the exchange-telemetry signal ``moe_apply_adaptive`` reports into the
    capacity-learning loop.
    """
    T, D = x.shape
    ep = jax.lax.axis_size(axis_name)
    e_loc = p["w_in"].shape[0]          # local experts (already sharded)
    e_pad = e_loc * ep

    # --- routing (router weights replicated) ---
    probs, top_idx, top_gate, aux = router_probs(p, cfg, x)

    # --- dispatch = paper model D: one-step MSD-radix all_to_all ---
    keys = top_idx.reshape(-1).astype(jnp.int32)            # (T*k,) expert ids
    vals = jnp.repeat(x, cfg.top_k, axis=0)                 # (T*k, D)
    cap = capacity if capacity is not None else expert_capacity(
        T, cfg.top_k, cfg.n_experts, cfg.capacity_factor
    )
    ex = partition_exchange(
        keys, vals, keys, axis_name, capacity=cap, n_buckets=e_pad,
        compress=cfg.compress_dispatch,
    )
    # recv: (ep, e_loc*cap, D) -> (e_loc, ep*cap, D) grouped per local expert
    recv = ex.recv_values.reshape(ep, e_loc, cap, D).transpose(1, 0, 2, 3)
    recv = recv.reshape(e_loc, ep * cap, D)
    rmask = (ex.recv_src_slot.reshape(ep, e_loc, cap) >= 0).transpose(1, 0, 2)
    rmask = rmask.reshape(e_loc, ep * cap)

    # --- local expert FFN (the per-node OpenMP work of Fig 4) ---
    h = jnp.einsum("etd,edf->etf", recv, p["w_in"].astype(recv.dtype))
    if "w_gate" in p:
        g = jnp.einsum("etd,edf->etf", recv, p["w_gate"].astype(recv.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("etf,efd->etd", h, p["w_out"].astype(recv.dtype))
    y = jnp.where(rmask[..., None], y, 0.0)

    # --- combine = inverse exchange, then gate-weighted sum over k replicas ---
    y = y.reshape(e_loc, ep, cap, D).transpose(1, 0, 2, 3).reshape(ep, e_loc * cap, D)
    back = combine_exchange(y, ex, axis_name)               # (T*k, D)
    back = back.reshape(T, cfg.top_k, D)
    out = jnp.einsum("tkd,tk->td", back.astype(jnp.float32), top_gate)
    overflow = ex.overflow
    if all_axes:
        aux = jax.lax.pmean(aux, all_axes)
        rest = tuple(a for a in all_axes if a != axis_name)
        if rest:  # overflow is already pmax'd over the EP axis
            overflow = jax.lax.pmax(overflow, rest)
    out = out.astype(x.dtype)
    if with_stats:
        counts = jax.lax.psum(ex.counts, axis_name)         # (e_pad,) global
        dropped = jax.lax.psum(
            jnp.sum(jnp.maximum(ex.counts - cap, 0)), axis_name
        )
        peak = jax.lax.pmax(jnp.max(ex.counts), axis_name)
        return out, aux, dropped, counts, peak, overflow
    return out, aux, overflow


def moe_apply_ep_replicated(
    p: Params,
    cfg: MoEConfig,
    x: jax.Array,
    ep_axis: Optional[str] = None,
    all_axes: tuple = (),
    *,
    capacity: Optional[int] = None,
    with_stats: bool = False,
):
    """MoE forward with tokens *replicated* over the EP axis (decode path, and
    the single-device fallback when ``ep_axis is None``).

    Each EP shard routes the same tokens but computes only its local experts,
    then contributions are psum'd over the EP axis. No all_to_all: for tiny
    decode batches the duplicate routing FLOPs are cheaper than the collective
    latency (hypothesis H-serve in EXPERIMENTS.md §Perf).

    ``capacity`` / ``with_stats`` follow ``moe_apply_local``'s contract:
    ``with_stats=True`` returns ``(y, aux, dropped, counts, peak, overflow)``
    with per-expert token ``counts``, the max per-expert ``peak``, and the
    ``dropped`` token total — what ``moe_apply_adaptive`` feeds the shared
    exchange telemetry.
    """
    T, D = x.shape
    ep = 1 if ep_axis is None else jax.lax.axis_size(ep_axis)
    my = 0 if ep_axis is None else jax.lax.axis_index(ep_axis)
    e_loc = p["w_in"].shape[0]

    probs, top_idx, top_gate, aux = router_probs(p, cfg, x)

    keys = top_idx.reshape(-1).astype(jnp.int32)             # (T*k,) global ids
    local = keys - my * e_loc
    mine = (local >= 0) & (local < e_loc)
    bucket = jnp.where(mine, local, e_loc)                   # trash bucket e_loc
    cap = capacity if capacity is not None else expert_capacity(
        T, cfg.top_k, cfg.n_experts, cfg.capacity_factor
    )

    order = jnp.argsort(bucket, stable=True)
    sorted_b = bucket[order]
    counts = jnp.bincount(bucket, length=e_loc + 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(keys.shape[0], dtype=jnp.int32) - offsets[sorted_b]
    valid = (pos < cap) & (sorted_b < e_loc)
    slot_sorted = jnp.where(valid, sorted_b * cap + pos, e_loc * cap)

    vals = jnp.repeat(x, cfg.top_k, axis=0)                  # (T*k, D)
    slab = jnp.zeros((e_loc * cap, D), x.dtype).at[slot_sorted].set(
        vals[order], mode="drop"
    )
    smask = jnp.zeros((e_loc * cap,), bool).at[slot_sorted].set(True, mode="drop")
    send_slot = (
        jnp.full((keys.shape[0],), -1, jnp.int32)
        .at[order]
        .set(jnp.where(valid, slot_sorted, -1).astype(jnp.int32))
    )

    recv = slab.reshape(e_loc, cap, D)
    h = jnp.einsum("etd,edf->etf", recv, p["w_in"].astype(recv.dtype))
    if "w_gate" in p:
        g = jnp.einsum("etd,edf->etf", recv, p["w_gate"].astype(recv.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("etf,efd->etd", h, p["w_out"].astype(recv.dtype))
    y = jnp.where(smask.reshape(e_loc, cap)[..., None], y, 0.0)

    flat = y.reshape(e_loc * cap, D)
    safe = jnp.clip(send_slot, 0, flat.shape[0] - 1)
    back = jnp.where((send_slot >= 0)[:, None], flat[safe], 0.0)
    back = back.reshape(T, cfg.top_k, D)
    out = jnp.einsum("tkd,tk->td", back.astype(jnp.float32), top_gate)
    counts_real = counts[:e_loc]
    overflow = jnp.max(counts_real) > cap
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
        overflow = jax.lax.pmax(overflow, ep_axis)
    if all_axes:
        aux = jax.lax.pmean(aux, all_axes)
        rest = tuple(a for a in all_axes if a != ep_axis)
        if rest:
            overflow = jax.lax.pmax(overflow, rest)
    out = out.astype(x.dtype)
    if with_stats:
        # inside the branch so the plain (decode) forward never issues the
        # extra collectives, jit or eager
        dropped = jnp.sum(jnp.maximum(counts_real - cap, 0))
        peak = jnp.max(counts_real)
        if ep_axis is not None:
            counts_real = jax.lax.all_gather(counts_real, ep_axis).reshape(-1)
            dropped = jax.lax.psum(dropped, ep_axis)
            peak = jax.lax.pmax(peak, ep_axis)
        return out, aux, dropped, counts_real, peak, overflow
    return out, aux, overflow


# ------------------------------------------------------- adaptive dispatch ---
def moe_plan_key(tokens: int, cfg: MoEConfig, dtype=jnp.float32, mesh=None) -> str:
    """Plan-cache cell for MoE expert-capacity learning.

    Keyed per (n_experts, top_k, pow2 token bucket, dtype, mesh fingerprint)
    — the quantities ``expert_capacity`` depends on — so skew learned for one
    routing shape never bleeds into another.  Lives in the same ``learned``
    table as the sort cells (docs/plan-cache.md).
    """
    from repro.core.bitonic import next_pow2
    from repro.engine.planner import mesh_fingerprint

    return (
        f"moe/E{cfg.n_experts}k{cfg.top_k}|{next_pow2(tokens)}"
        f"|{jnp.dtype(dtype).name}|{mesh_fingerprint(mesh)}"
    )


@lru_cache(maxsize=256)
def _compiled_moe_replicated(cfg: MoEConfig, capacity: int):
    """One jitted single-host forward per (config, capacity) — the factory
    ``run_with_capacity_retries`` counts retry-forced fresh compiles on."""

    def f(p, x):
        return moe_apply_ep_replicated(p, cfg, x, capacity=capacity, with_stats=True)

    return jax.jit(f)


def _drop_report(telemetry, attempt_drops: list):
    """Wrap a telemetry callback with served/averted drop accounting.

    The retry driver reports once, after the final attempt; routing (and so
    per-attempt drops) is identical across attempts, only the capacity
    moves — the final attempt's drops reached the served output iff it
    still overflowed (peak > its capacity), every earlier attempt's were
    recomputed away by the retry.  Shared by both adaptive MoE paths
    (replicated and shard_map expert-parallel) so the telemetry schema
    can't drift between them.
    """
    if telemetry is None:
        return None

    def report(**kwargs):
        served = (
            attempt_drops[-1]
            if attempt_drops and kwargs["peak"] > kwargs["capacity"]
            else 0
        )
        # later attempts re-drop a subset of the first attempt's tokens,
        # so distinct at-risk tokens = the first (largest) attempt's
        # count, not the sum across attempts
        averted = max(attempt_drops, default=0) - served
        telemetry(dropped=served, dropped_averted=averted, **kwargs)

    return report


def moe_apply_adaptive(
    p: Params,
    cfg: MoEConfig,
    x: jax.Array,
    *,
    planner=None,
    capacity_factor: Optional[float] = None,
    telemetry=None,
    max_retries: int = 4,
):
    """Adaptive single-host MoE forward: learned capacity, retry over drop.

    The MoE twin of the adaptive ``cluster_sort`` path.  Runs
    ``moe_apply_ep_replicated`` at the learned expert capacity factor for
    this (n_experts, top_k, token bucket) cell, retries with doubled
    capacity when the router's skew overflows it (``capacity == T * top_k``
    is the loss-free bound, so retries always converge), and reports the
    call's exchange telemetry — peak per-expert token count, overflow/
    retry/recompile events, and drop counts (``dropped`` = tokens the served
    output actually lost, ``dropped_averted`` = tokens retried attempts
    would have lost) — through the planner, which folds it into a persisted
    capacity factor:
    a skewed routing distribution pays its overflow penalty once per
    deployment, zero after restart.  When retries are exhausted the last
    attempt's output is returned with its drops intact (GShard semantics)
    rather than raising — serving must degrade, not die.

    By default the loop runs through ``planner`` (the process-wide default
    planner when None); passing an explicit ``capacity_factor=`` or
    ``telemetry=`` opts the call out of the whole loop, reading and
    writing, exactly like the sort paths.

    Returns ``(y, aux, counts)`` with per-expert token ``counts`` — the
    final attempt never overflowed unless retries were exhausted, so unlike
    the fixed path there is no overflow flag to thread through.
    """
    T, _ = x.shape
    m = T * cfg.top_k
    if capacity_factor is None and telemetry is None:
        from repro.engine.planner import default_planner

        planner = planner or default_planner()
        key = moe_plan_key(T, cfg, x.dtype)
        capacity_factor = planner.capacity_factor_for(
            key, default=cfg.capacity_factor
        )
        telemetry = planner.exchange_recorder(key, default=cfg.capacity_factor)
    elif capacity_factor is None:
        capacity_factor = cfg.capacity_factor
    cap = expert_capacity(T, cfg.top_k, cfg.n_experts, capacity_factor)
    # cfg.capacity_factor is dead inside the compiled forward (capacity is
    # explicit), so normalize it out of the compile-cache key: two defaults
    # over the same architecture share one executable per capacity
    ccfg = cfg._replace(capacity_factor=0.0)

    attempt_drops = []

    def run_fn(fn):
        out, aux, dropped, counts, peak, overflow = fn(p, x)
        attempt_drops.append(int(dropped))
        return out, aux, counts, peak, overflow

    report = _drop_report(telemetry, attempt_drops)

    (y, aux), counts = run_with_capacity_retries(
        lambda c: _compiled_moe_replicated(ccfg, c),
        run_fn,
        m=m,
        part_buckets=max(cfg.n_experts, 1),
        cap=cap,
        max_retries=max_retries,
        telemetry=report,
        lru=_compiled_moe_replicated,
        label="moe_apply_adaptive",
        strict=False,
        path="scatter",
    )
    return y, aux, counts


@lru_cache(maxsize=256)
def _compiled_moe_local(cfg: MoEConfig, capacity: int, mesh, axes: tuple, ep_axis: str):
    """One jitted shard_map expert-parallel forward per (config, capacity,
    mesh, axes) — the factory ``run_with_capacity_retries`` counts
    retry-forced fresh compiles on.  ``jax.Mesh`` hashes by (devices,
    axis names), so two calls over the same topology share one executable
    per capacity, exactly like the replicated twin.

    ``dropped``/``counts``/``peak`` come out *mesh*-global (the
    ``moe_apply_local`` stats are EP-group-global; the extra psum/pmax here
    folds in the non-EP axes), so the host-side capacity loop reads one
    scalar per step regardless of topology.
    """

    def body(mp, xt):
        out, aux, dropped, counts, peak, overflow = moe_apply_local(
            mp, cfg, xt, ep_axis, axes, capacity=capacity, with_stats=True
        )
        rest = tuple(a for a in axes if a != ep_axis)
        if rest:
            dropped = jax.lax.psum(dropped, rest)
            counts = jax.lax.psum(counts, rest)
            peak = jax.lax.pmax(peak, rest)
        return out, aux, dropped, counts, peak, overflow

    def f(p, x):
        (p_spec, x_spec), out_specs = moe_shard_specs(
            p, mesh_axes=axes, ep_axis=ep_axis, with_stats=True
        )
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(p_spec, x_spec),
            out_specs=out_specs,
            check_vma=False,
        )(p, x)

    return jax.jit(f)


def moe_apply_local_adaptive(
    p: Params,
    cfg: MoEConfig,
    x: jax.Array,
    mesh,
    *,
    axes: tuple = ("data", "model"),
    ep_axis: str = "model",
    planner=None,
    capacity_factor: Optional[float] = None,
    telemetry=None,
    max_retries: int = 4,
):
    """Adaptive *expert-parallel* MoE forward: the shard_map all_to_all
    dispatch (``moe_apply_local``) under the shared capacity-retry driver.

    The mesh twin of ``moe_apply_adaptive``: runs the paper's model-D
    dispatch at the learned expert capacity factor for this (n_experts,
    top_k, token bucket, *mesh*) cell, retries with doubled capacity when
    the router's skew overflows it, and reports the call's exchange
    telemetry through the planner so the factor persists in the plan cache
    — training and serving processes that share a topology (and a
    ``$REPRO_SORT_PLANS`` file) warm each other.  Capacity is a static
    compile-cache key, so a learned bump recompiles exactly once; when
    retries are exhausted the last attempt's output is returned with its
    drops intact (GShard semantics).

    ``x`` is the *global* (T, D) token batch; T must divide the mesh (the
    shard_map in_specs split it over every axis in ``axes``).  Passing an
    explicit ``capacity_factor=`` or ``telemetry=`` opts out of the
    planner loop, exactly like the replicated path.

    Returns ``(y, aux, counts)`` with mesh-global per-expert ``counts``.
    """
    T, _ = x.shape
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    if T % n_dev:
        raise ValueError(f"tokens {T} must divide the {n_dev}-device mesh")
    t_loc = T // n_dev                     # per-sender token slice
    m = t_loc * cfg.top_k                  # per-sender assignments
    if capacity_factor is None and telemetry is None:
        from repro.engine.planner import default_planner

        planner = planner or default_planner()
        key = moe_plan_key(T, cfg, x.dtype, mesh)
        capacity_factor = planner.capacity_factor_for(
            key, default=cfg.capacity_factor
        )
        telemetry = planner.exchange_recorder(key, default=cfg.capacity_factor)
    elif capacity_factor is None:
        capacity_factor = cfg.capacity_factor
    cap = expert_capacity(t_loc, cfg.top_k, cfg.n_experts, capacity_factor)
    ccfg = cfg._replace(capacity_factor=0.0)

    attempt_drops = []

    def run_fn(fn):
        out, aux, dropped, counts, peak, overflow = fn(p, x)
        attempt_drops.append(int(dropped))
        return out, aux, counts, peak, overflow

    report = _drop_report(telemetry, attempt_drops)

    (y, aux), counts = run_with_capacity_retries(
        lambda c: _compiled_moe_local(ccfg, c, mesh, tuple(axes), ep_axis),
        run_fn,
        m=m,
        part_buckets=max(cfg.n_experts, 1),
        cap=cap,
        max_retries=max_retries,
        telemetry=report,
        lru=_compiled_moe_local,
        label="moe_apply_local_adaptive",
        strict=False,
        path="scatter",
    )
    return y, aux, counts


def moe_shard_specs(
    params: Params,
    mesh_axes=("pod", "data", "model"),
    ep_axis="model",
    *,
    with_stats: bool = False,
):
    """PartitionSpecs for calling moe_apply_local under shard_map.

    Tokens shard over every mesh axis; experts over the EP axis; router
    replicated. Returns (in_specs for (params, x), out_specs) — the
    out_specs match ``moe_apply_local``'s 3-tuple, or its 6-tuple stats
    contract when ``with_stats`` (aux/dropped/counts/peak/overflow all
    replicated).
    """
    from jax.sharding import PartitionSpec as P

    def leaf_spec(path):
        return P() if path[0] == "router" else P(ep_axis)

    p_spec = jax.tree_util.tree_map_with_path(
        lambda kp, _: leaf_spec(tuple(k.key for k in kp)), params
    )
    x_spec = P(tuple(mesh_axes))
    n_out = 6 if with_stats else 3
    out_specs = (P(tuple(mesh_axes)),) + (P(),) * (n_out - 1)
    return (p_spec, x_spec), out_specs
