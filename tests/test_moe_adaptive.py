"""MoE capacity learning through the unified exchange layer.

The acceptance regression: a skewed router pays its overflow/drop retry
exactly once per process and zero after a simulated restart (asserted with
jax's lowering counters, mirroring tests/test_adapt.py), plus property
tests that learned expert capacity factors stay within learner bounds and
that the hoisted capacity formula drives both MoE forwards.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare container — requirements-dev.txt installs the real one
    from _hypothesis_shim import given, settings, strategies as st

from repro.engine import CapacityLearner, ExchangeObservation, Planner
from repro.exchange import expert_capacity
from repro.models.moe import (
    MoEConfig,
    collapse_router,
    moe_apply_adaptive,
    moe_apply_ep_replicated,
    moe_init,
    moe_plan_key,
)

settings.register_profile("repro-ci", max_examples=10, deadline=None,
                          derandomize=True)
settings.load_profile("repro-ci")

DEFAULT_CF = 2.0


def _collapsed_moe(key, *, n_experts=8, top_k=1, capacity_factor=DEFAULT_CF):
    """An MoE layer with worst-case routing skew (collapse_router) — what
    the capacity loop exists for."""
    cfg = MoEConfig(d_model=16, d_ff=8, n_experts=n_experts, top_k=top_k,
                    capacity_factor=capacity_factor)
    return cfg, collapse_router(moe_init(key, cfg, jnp.float32, ep_shards=1))


# ----------------------------------------------- acceptance regression ------
def test_skewed_router_pays_retry_once_and_zero_after_restart(key):
    """ISSUE acceptance: first adaptive call overflows, retries to the
    loss-free bound, and teaches the planner; the same cell then serves with
    zero retries and — via jax's lowering counters — zero fresh traces; a
    fresh planner over the same JSON (simulated restart) starts at the
    learned factor so its first call pays nothing either."""
    from jax._src import test_util as jtu

    cfg, p = _collapsed_moe(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    path = os.path.join(tempfile.mkdtemp(), "plans.json")
    planner = Planner(path)
    cell = moe_plan_key(64, cfg, x.dtype)

    # call 1: the default factor under-provisions the hot expert -> retries
    y1, aux1, counts = moe_apply_adaptive(p, cfg, x, planner=planner)
    obs1 = planner.telemetry.last(cell)
    assert obs1 is not None and obs1.overflowed and obs1.retries >= 1
    # the retry recomputed the overflowed attempts: nothing reached the
    # served output, everything shows up as averted
    assert obs1.recompiles >= 1
    assert obs1.dropped == 0 and obs1.dropped_averted > 0
    cf = planner.capacity_factor_for(cell, default=cfg.capacity_factor)
    assert cf > cfg.capacity_factor
    assert cf >= obs1.required_factor()
    # the hot expert really did absorb the skew
    assert int(np.asarray(counts).max()) == obs1.peak

    # the final attempt ran loss-free: output == an over-provisioned forward
    y_ref, _, ovf = moe_apply_ep_replicated(
        p, cfg._replace(capacity_factor=float(cfg.n_experts * cfg.top_k)), x)
    assert not bool(ovf)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_ref), atol=1e-5)

    # call 2: learned factor -> zero retries, zero drops
    y2, _, _ = moe_apply_adaptive(p, cfg, x, planner=planner)
    obs2 = planner.telemetry.last(cell)
    assert not obs2.overflowed and obs2.retries == 0 and obs2.dropped == 0
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref), atol=1e-5)

    # steady state: same cell, zero retries AND zero fresh lowerings
    with jtu.count_jit_and_pmap_lowerings() as count:
        moe_apply_adaptive(p, cfg, x, planner=planner)
    assert count() == 0, "steady-state MoE dispatch must not re-trace"
    assert planner.telemetry.last(cell).retries == 0

    # restart: a fresh planner over the same JSON starts provisioned
    restarted = Planner(path)
    assert restarted.capacity_factor_for(cell, default=cfg.capacity_factor) == cf
    with jtu.count_jit_and_pmap_lowerings() as count:
        y3, _, _ = moe_apply_adaptive(p, cfg, x, planner=restarted)
    assert count() == 0, "post-restart first call must reuse the executable"
    assert restarted.telemetry.last(cell).retries == 0
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y_ref), atol=1e-5)


def test_fixed_capacity_path_reports_real_drops(key):
    """max_retries=0 is the GShard fixed path: overflow drops tokens instead
    of raising (strict=False in the shared driver), and the drop count lands
    in the telemetry ledger — the previously-silent signal serve.py --stats
    now prints."""
    cfg, p = _collapsed_moe(key)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    planner = Planner()
    cell = moe_plan_key(64, cfg, x.dtype)

    y_drop, _, _ = moe_apply_adaptive(p, cfg, x, planner=planner, max_retries=0)
    obs = planner.telemetry.last(cell)
    assert obs.overflowed and obs.retries == 0 and obs.dropped > 0
    assert obs.dropped_averted == 0, "no retry ran, so nothing was averted"
    assert planner.telemetry.total_dropped == obs.dropped

    y_ref, _, _ = moe_apply_ep_replicated(
        p, cfg._replace(capacity_factor=float(cfg.n_experts * cfg.top_k)), x)
    assert not np.allclose(np.asarray(y_drop), np.asarray(y_ref)), \
        "dropped tokens must actually be missing from the output"


def test_explicit_sort_plan_pin_opts_out_of_the_loop(debug_mesh):
    """api.sort with an explicit plan= pins the whole recipe: it must not
    read a learned factor over the pin, nor inflate the shared learned
    table with the pin as the learner floor."""
    import jax

    from repro.core import sort
    from repro.engine import SortPlan
    from repro.engine.planner import default_planner, plan_key

    planner = default_planner()
    n = 64
    x = jax.random.randint(jax.random.PRNGKey(8), (n,), 0, 1000, jnp.int32)
    cell = plan_key(n, jnp.int32, debug_mesh)
    calls_before = planner.telemetry.calls
    learned_before = dict(planner.learned)
    slab, valid = sort(x, mesh=debug_mesh, axis="x",
                       plan=SortPlan("cluster", capacity_factor=8.0))
    assert (np.asarray(slab)[np.asarray(valid)] == np.sort(np.asarray(x))).all()
    assert planner.telemetry.calls == calls_before, "pinned call reported"
    assert planner.learned.get(cell) == learned_before.get(cell), \
        "pinned call mutated the shared learned table"


def test_explicit_capacity_factor_opts_out_of_the_loop(key):
    """Like the sort paths: an explicit capacity_factor= neither reads nor
    writes the planner's learned table."""
    cfg, p = _collapsed_moe(key)
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 16))
    planner = Planner()
    y, _, _ = moe_apply_adaptive(
        p, cfg, x, planner=planner, capacity_factor=float(cfg.n_experts))
    assert planner.telemetry.calls == 0
    assert planner.learned == {}


# ------------------------------------------------------ shared capacity -----
def test_moe_forwards_use_the_hoisted_capacity_formula(key):
    """capacity= overrides must reproduce the cfg-derived default exactly —
    i.e. both forwards consume expert_capacity, not a re-derived copy."""
    cfg = MoEConfig(d_model=16, d_ff=8, n_experts=4, top_k=2,
                    capacity_factor=1.3)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 16))
    cap = expert_capacity(24, cfg.top_k, cfg.n_experts, cfg.capacity_factor)
    y_default, _, ovf_d = moe_apply_ep_replicated(p, cfg, x)
    y_explicit, _, ovf_e = moe_apply_ep_replicated(p, cfg, x, capacity=cap)
    np.testing.assert_array_equal(np.asarray(y_default), np.asarray(y_explicit))
    assert bool(ovf_d) == bool(ovf_e)


def test_with_stats_is_consistent_with_plain_forward(key):
    """with_stats=True must not perturb the computation, and its counts/peak
    must describe the routing exactly."""
    cfg = MoEConfig(d_model=16, d_ff=8, n_experts=4, top_k=2,
                    capacity_factor=8.0)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    y, aux, ovf = moe_apply_ep_replicated(p, cfg, x)
    ys, auxs, dropped, counts, peak, ovfs = moe_apply_ep_replicated(
        p, cfg, x, with_stats=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ys))
    assert float(aux) == float(auxs)
    assert int(dropped) == 0 and not bool(ovfs)
    assert int(np.asarray(counts).sum()) == 32 * cfg.top_k
    assert int(peak) == int(np.asarray(counts).max())


# -------------------------------------------- local (all_to_all) dispatch ---
def test_moe_apply_local_matches_replicated_on_one_shard(key):
    """moe_apply_local (the all_to_all dispatch) on a 1-device EP mesh must
    equal the replicated fallback exactly — the two forwards are the same
    exchange consumed two ways, and this runs the wire path in-process."""
    from jax.sharding import PartitionSpec as PS

    from repro.models.moe import moe_apply_local, moe_shard_specs

    cfg = MoEConfig(d_model=16, d_ff=8, n_experts=4, top_k=2,
                    capacity_factor=8.0)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    x = jax.random.normal(jax.random.PRNGKey(6), (32, 16))
    mesh = jax.make_mesh((1,), ("model",))
    (p_spec, x_spec), out_specs = moe_shard_specs(p, mesh_axes=("model",))

    y_local, aux_l, ovf_l = jax.shard_map(
        lambda mp, xt: moe_apply_local(mp, cfg, xt, "model", ("model",)),
        mesh=mesh, in_specs=(p_spec, x_spec), out_specs=out_specs,
        check_vma=False)(p, x)
    y_rep, aux_r, ovf_r = moe_apply_ep_replicated(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_rep),
                               atol=1e-5)
    assert float(aux_l) == pytest.approx(float(aux_r))
    assert not bool(ovf_l) and not bool(ovf_r)

    # with_stats twin: same output, counts describe the routing exactly
    stats_specs = (out_specs[0], PS(), PS(), PS(), PS(), PS())
    ys, _, dropped, counts, peak, _ = jax.shard_map(
        lambda mp, xt: moe_apply_local(mp, cfg, xt, "model", ("model",),
                                       with_stats=True),
        mesh=mesh, in_specs=(p_spec, x_spec), out_specs=stats_specs,
        check_vma=False)(p, x)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y_rep), atol=1e-5)
    assert int(dropped) == 0
    assert int(np.asarray(counts).sum()) == 32 * cfg.top_k
    assert int(peak) == int(np.asarray(counts).max())

    # the replicated forward's EP-axis branch (decode path) agrees too
    y_ep, _, ovf_ep = jax.shard_map(
        lambda mp, xt: moe_apply_ep_replicated(mp, cfg, xt, "model",
                                               ("model",)),
        mesh=mesh, in_specs=(p_spec, PS()), out_specs=(PS(), PS(), PS()),
        check_vma=False)(p, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_rep), atol=1e-5)
    assert not bool(ovf_ep)


def test_moe_apply_local_compressed_dispatch_close_to_exact(key):
    """compress_dispatch=True rides the exchange layer's int8 wire; outputs
    stay within quantization tolerance of the exact forward."""
    from repro.models.moe import moe_apply_local, moe_shard_specs

    cfg = MoEConfig(d_model=16, d_ff=8, n_experts=4, top_k=2,
                    capacity_factor=8.0, compress_dispatch=True)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 16))
    mesh = jax.make_mesh((1,), ("model",))
    (p_spec, x_spec), out_specs = moe_shard_specs(p, mesh_axes=("model",))
    y_c, _, _ = jax.shard_map(
        lambda mp, xt: moe_apply_local(mp, cfg, xt, "model", ("model",)),
        mesh=mesh, in_specs=(p_spec, x_spec), out_specs=out_specs,
        check_vma=False)(p, x)
    y_exact, _, _ = moe_apply_ep_replicated(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_exact),
                               atol=0.15)


# ------------------------------------------------------- learner bounds -----
cfs = st.floats(0.05, 8.0)
Ts = st.sampled_from((16, 64, 256))
Es = st.sampled_from((2, 4, 8, 16))
ks = st.integers(1, 4)
seeds = st.integers(0, 2**20)


def _moe_observation(rng, T, E, k):
    m = T * k
    peak = int(rng.integers(0, m + 1))
    cap = expert_capacity(T, k, E, DEFAULT_CF)
    overflowed = peak > cap
    return ExchangeObservation(
        m=m, part_buckets=E, capacity=max(cap, peak if overflowed else cap),
        peak=peak, overflowed=overflowed, retries=int(overflowed),
        dropped=max(0, peak - cap) if overflowed else 0)


@given(st.integers(1, 40), Ts, Es, ks, seeds)
def test_learned_expert_factors_stay_within_learner_bounds(n_obs, T, E, k, seed):
    """For ANY sequence of MoE-shaped observations the planner's learned
    expert capacity factor stays within [default, max_factor] — routing
    chaos cannot run capacity (or expert-buffer memory) away."""
    rng = np.random.default_rng(seed)
    planner = Planner()
    learner = CapacityLearner()
    cell = f"moe/E{E}k{k}|{T}|float32|local/cpu"
    for _ in range(n_obs):
        planner.observe_exchange(
            cell, _moe_observation(rng, T, E, k), default=DEFAULT_CF)
        cf = planner.capacity_factor_for(cell, default=DEFAULT_CF)
        assert DEFAULT_CF <= cf <= learner.max_factor
        # the factor is always realizable as a concrete expert capacity
        assert 1 <= expert_capacity(T, k, E, cf) <= T * k


@given(Ts, Es, ks, cfs)
def test_learned_factor_roundtrips_to_a_fitting_capacity(T, E, k, cf):
    """required_factor -> expert_capacity closes: learning from a peak and
    re-deriving the capacity always fits that peak (margin >= 1)."""
    rng = np.random.default_rng(0)
    peak = int(rng.integers(1, T * k + 1))
    obs = ExchangeObservation(m=T * k, part_buckets=E, capacity=peak,
                              peak=peak, overflowed=True, retries=1)
    learner = CapacityLearner()
    learned = learner.update(DEFAULT_CF, obs, default=DEFAULT_CF)
    if learner.target(obs, default=DEFAULT_CF) < learner.max_factor:
        assert expert_capacity(T, k, E, learned) >= min(peak, T * k)
