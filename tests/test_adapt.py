"""Closed-loop adaptive tuning: capacity learning from exchange telemetry.

Property-based invariants for ``slab_geometry`` and the ``CapacityLearner``
(hypothesis when installed, the seeded shim otherwise — both deterministic),
the plan-cache v2 round-trip of learned state, and the acceptance regression:
a skewed range-mode workload that overflows at ``capacity_factor=2.0`` pays
exactly one retry on the first call and — after the telemetry round-trip —
zero retries and zero recompiles at the same plan-cache key.
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare container — requirements-dev.txt installs the real one
    from _hypothesis_shim import given, settings, strategies as st

from conftest import run_with_devices
from repro.core.cluster_sort import slab_geometry
from repro.engine import (
    CapacityLearner,
    ExchangeObservation,
    ExchangeTelemetry,
    LearnedCapacity,
    Planner,
)
from repro.engine.planner import plan_key

settings.register_profile("repro-ci", max_examples=10, deadline=None,
                          derandomize=True)
settings.load_profile("repro-ci")

modes = st.sampled_from(("decimal", "splitters", "range"))
ms = st.integers(1, 1 << 14)
Ps = st.integers(1, 64)
cfs = st.floats(0.05, 64.0)
seeds = st.integers(0, 2**20)

DEFAULT_CF = 2.0


# ----------------------------------------------------- slab_geometry (D) ---
@given(modes, ms, Ps, cfs)
def test_slab_geometry_invariants(mode, m, P, cf):
    """For arbitrary (mode, m, P, capacity_factor): capacity stays within
    [1, m], the bucket grid is a multiple of P that covers every partitioner
    bucket, and a factor >= 1 provisions at least m slots across buckets."""
    part, n_buckets, cap = slab_geometry(mode, m, P, cf)
    assert part == (10 if mode == "decimal" else P)
    assert 1 <= cap <= m
    assert n_buckets % P == 0, "partition_exchange's B % P == 0 contract"
    assert n_buckets >= part, "slabs must cover all partitioner buckets"
    assert n_buckets - part < P, "bucket grid rounds up minimally"
    if cf >= 1.0:
        # enough total slots for every key on a uniform sender
        assert cap * part >= m
    # capacity is monotone in the factor (a bigger margin never shrinks slabs)
    _, _, cap2 = slab_geometry(mode, m, P, cf * 2)
    assert cap2 >= cap


# ----------------------------------------------------- capacity learner ----
def _random_observation(rng) -> ExchangeObservation:
    m = int(rng.integers(1, 1 << 12))
    part_buckets = int(rng.choice((8, 10, 16)))
    peak = int(rng.integers(0, m + 1))
    retries = int(rng.integers(0, 4))
    return ExchangeObservation(
        m=m,
        part_buckets=part_buckets,
        capacity=max(1, peak),
        peak=peak,
        overflowed=retries > 0,
        retries=retries,
        recompiles=int(rng.integers(0, retries + 1)),
    )


@given(st.integers(1, 60), seeds)
def test_capacity_learner_bounded_and_never_oscillates_past_peak(n_obs, seed):
    """For ANY observation sequence the learned factor stays within
    [default, max_factor] and never exceeds the largest observed
    peak-x-margin target — i.e. learning cannot run away or oscillate past
    what the telemetry justified."""
    rng = np.random.default_rng(seed)
    learner = CapacityLearner()
    learned = DEFAULT_CF
    max_target = DEFAULT_CF
    for _ in range(n_obs):
        obs = _random_observation(rng)
        target = learner.target(obs, default=DEFAULT_CF)
        max_target = max(max_target, target)
        prev = learned
        learned = learner.update(learned, obs, default=DEFAULT_CF)
        assert DEFAULT_CF <= learned <= learner.max_factor
        assert learned <= max_target + 1e-12, "overshot observed peak x margin"
        if target >= prev:
            assert learned == target, "pressure must be adopted immediately"
        else:
            assert learned <= prev, "calm traffic must never grow the factor"
            assert learned >= target, "decay must not undershoot the target"


@given(st.integers(1, 30), seeds)
def test_capacity_learner_decays_toward_default_when_calm(n_calm, seed):
    """After a burst of skew, a stream of calm observations walks the factor
    geometrically back toward the default (but never below it)."""
    learner = CapacityLearner()
    hot = ExchangeObservation(m=256, part_buckets=8, capacity=64, peak=256,
                              overflowed=True, retries=2)
    learned = learner.update(DEFAULT_CF, hot, default=DEFAULT_CF)
    assert learned == learner.target(hot, default=DEFAULT_CF) > DEFAULT_CF
    calm = ExchangeObservation(m=256, part_buckets=8, capacity=64, peak=0,
                               overflowed=False, retries=0)
    prev = learned
    for _ in range(n_calm):
        learned = learner.update(learned, calm, default=DEFAULT_CF)
        assert DEFAULT_CF <= learned <= prev
        prev = learned
    # decay is geometric: 30 calm steps from <= 64 land within a hair of 2.0
    if n_calm >= 30:
        assert learned == pytest.approx(DEFAULT_CF, rel=1e-6)


@given(st.integers(1, 20), seeds)
def test_learned_factors_roundtrip_through_plan_cache_json(n_obs, seed):
    """Any telemetry-fed learned table survives save -> load exactly (the
    plan-cache v2 'learned' section).  (tempfile, not the tmp_path fixture:
    function-scoped fixtures don't mix with @given.)"""
    import tempfile

    rng = np.random.default_rng(seed)
    path = os.path.join(tempfile.mkdtemp(), "plans.json")
    planner = Planner(path)
    keys = [plan_key(1 << k, jnp.int32) for k in (10, 12, 14)]
    for _ in range(n_obs):
        planner.observe_exchange(
            keys[int(rng.integers(0, len(keys)))], _random_observation(rng)
        )
    planner.save()
    reloaded = Planner(path)
    assert reloaded.learned == planner.learned
    for k in keys:
        assert reloaded.capacity_factor_for(k) == planner.capacity_factor_for(k)


# --------------------------------------------------- ledger + persistence ---
def test_exchange_telemetry_ledger_counts_and_windows():
    led = ExchangeTelemetry(window=4)
    key = plan_key(1024, jnp.int32)
    assert led.last(key) is None and led.peak_factor(key) == 0.0
    for peak in (10, 20, 120, 5, 8):
        led.record(key, ExchangeObservation(
            m=128, part_buckets=8, capacity=32, peak=peak,
            overflowed=peak > 32, retries=int(peak > 32)))
    assert led.calls == 5 and led.overflow_events == 1 and led.total_retries == 1
    assert led.last(key).peak == 8
    # the window dropped the first observation; peak_factor sees the rest
    assert led.peak_factor(key) == pytest.approx(120 * 8 / 128)
    assert led.keys() == [key]


def test_planner_v1_files_still_load_and_v2_learned_is_graceful(tmp_path):
    """Schema bump reuses the graceful-load path: v1 files (no 'learned')
    load cleanly, malformed learned sections warn + keep prior state, and
    unknown versions still warn."""
    import json
    import warnings

    v1 = tmp_path / "v1.json"
    v1.write_text(json.dumps({
        "version": 1,
        "plans": {plan_key(4096, jnp.int32): {
            "strategy": "shared", "local_impl": "xla"}},
    }))
    p = Planner(str(v1))
    assert p.lookup(4096, jnp.int32).local_impl == "xla"
    assert p.learned == {}

    # a v2 file with a rotted learned section is a rotted file: warn, keep
    bad = tmp_path / "bad_learned.json"
    bad.write_text(json.dumps({
        "version": 2, "plans": {},
        "learned": {"k": {"not_capacity": 1}},
    }))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        p.load(str(bad))
    assert any("plan cache" in str(x.message) for x in w)
    assert p.lookup(4096, jnp.int32) is not None, "prior table survives"

    with pytest.raises(Exception):
        Planner().load(str(bad), strict=True)

    # a good v2 file round-trips both sections
    key = plan_key(8192, jnp.int32)
    p.learned[key] = LearnedCapacity(3.5, 2.8, 4)
    p.save(str(tmp_path / "v2.json"))
    p2 = Planner(str(tmp_path / "v2.json"))
    assert p2.learned[key].capacity_factor == 3.5
    assert p2.lookup(4096, jnp.int32).local_impl == "xla"


def test_plan_for_folds_learned_capacity_into_cluster_plans():
    planner = Planner()
    key = plan_key(1024, jnp.int32, None)
    # single-host default is a shared plan: learning must not touch it
    planner.learned[key] = LearnedCapacity(5.0, 4.0, 1)
    assert planner.plan_for(1024, jnp.int32).strategy == "shared"
    # a cluster plan for the same cell picks the learned factor up
    from repro.engine import SortPlan

    planner.plans[key] = SortPlan("cluster", capacity_factor=2.0)
    assert planner.plan_for(1024, jnp.int32).capacity_factor == 5.0


def test_service_stats_sink_sees_overflow_retries_and_recompiles():
    """The silent-telemetry-gap fix: exchange retries/recompiles observed by
    a service's planner land in ServiceStats instead of vanishing."""
    from repro.engine import SortService

    planner = Planner()
    svc = SortService(planner=planner)
    assert svc.stats.overflow_retries == 0 and svc.stats.recompiles == 0
    rec = planner.recorder(4096, jnp.int32)
    rec(m=512, part_buckets=8, capacity=128, peak=300, overflowed=True,
        retries=2, recompiles=2)
    rec(m=512, part_buckets=8, capacity=512, peak=300, overflowed=False,
        retries=0, recompiles=1)
    assert svc.stats.overflow_retries == 2
    assert svc.stats.recompiles == 3
    # the ledger kept the raw observations too
    assert planner.telemetry.total_retries == 2
    assert planner.telemetry.overflow_events == 1


# ----------------------------------------------- acceptance regression ------
def test_skewed_overflow_learns_capacity_and_stops_recompiling():
    """ISSUE acceptance: a duplicate-heavy range-mode workload overflowing at
    capacity_factor=2.0 pays exactly one retry on the first call; after the
    telemetry round-trip the same plan-cache key serves with zero retries and
    zero recompiles (asserted via jax's lowering counters) — and the learned
    factor survives a planner save/load (simulated process restart)."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp, tempfile, os
        from jax._src import test_util as jtu
        from repro.core.cluster_sort import cluster_sort, slab_geometry
        from repro.engine import Planner, cluster_sort_kv
        from repro.engine.planner import plan_key

        mesh = jax.make_mesh((8,), ("x",))
        n, P = 1024, 8
        m = n // P
        rng = np.random.default_rng(0)
        # keys concentrate in the low 3 of 8 range buckets over [0, 8000):
        # per-(sender, bucket) peak ~ m/3, above cap(2.0) but below one
        # doubling -> exactly one retry at the default factor
        x = rng.integers(0, 3000, n).astype(np.int32)
        kw = dict(mode="range", lo=0, hi=8000)
        _, _, cap0 = slab_geometry("range", m, P, 2.0)
        assert cap0 < m

        path = os.path.join(tempfile.mkdtemp(), "plans.json")
        planner = Planner(path)
        key = plan_key(n, jnp.int32, mesh)
        rec = planner.recorder(n, jnp.int32, mesh)

        # call 1: default factor overflows once, retries, learns
        slab, valid = cluster_sort(
            jnp.asarray(x), mesh, "x",
            capacity_factor=planner.capacity_factor_for(key),
            telemetry=rec, **kw)
        assert (np.asarray(slab)[np.asarray(valid)] == np.sort(x)).all()
        obs1 = planner.telemetry.last(key)
        assert obs1.overflowed and obs1.retries == 1, obs1
        assert obs1.recompiles >= 1
        cf = planner.capacity_factor_for(key)
        assert cf > 2.0 and cf >= obs1.required_factor()

        # call 2: learned factor -> zero retries (first compile at that cap)
        slab, valid = cluster_sort(jnp.asarray(x), mesh, "x",
                                   capacity_factor=cf, telemetry=rec, **kw)
        assert (np.asarray(slab)[np.asarray(valid)] == np.sort(x)).all()
        obs2 = planner.telemetry.last(key)
        assert not obs2.overflowed and obs2.retries == 0, obs2

        # steady state: same key, zero retries AND zero recompiles
        cf3 = planner.capacity_factor_for(key)
        with jtu.count_jit_and_pmap_lowerings() as count:
            slab, valid = cluster_sort(jnp.asarray(x), mesh, "x",
                                       capacity_factor=cf3, telemetry=rec, **kw)
        assert count() == 0, "steady-state cluster path must not re-trace"
        assert planner.telemetry.last(key).retries == 0
        assert (np.asarray(slab)[np.asarray(valid)] == np.sort(x)).all()

        # the lesson is on disk: a fresh planner (process restart) starts at
        # the learned factor, so its FIRST call already avoids the retry
        restarted = Planner(path)
        assert restarted.capacity_factor_for(key) == cf3
        rec2 = restarted.recorder(n, jnp.int32, mesh)
        slab, valid = cluster_sort(
            jnp.asarray(x), mesh, "x",
            capacity_factor=restarted.capacity_factor_for(key),
            telemetry=rec2, **kw)
        assert restarted.telemetry.last(key).retries == 0
        assert (np.asarray(slab)[np.asarray(valid)] == np.sort(x)).all()

        # the kv twin feeds the same loop
        v = np.arange(n, dtype=np.int32)
        ref = np.argsort(x, kind="stable")
        sk, sv, valid = cluster_sort_kv(
            jnp.asarray(x), jnp.asarray(v), mesh, "x",
            capacity_factor=restarted.capacity_factor_for(key),
            telemetry=rec2, **kw)
        assert restarted.telemetry.last(key).retries == 0
        sk = np.asarray(sk)[np.asarray(valid)]
        sv = np.asarray(sv)[np.asarray(valid)]
        assert (sk == x[ref]).all() and (sv == ref).all()
        print("capacity learning regression ok")
    """)


def test_api_sort_and_sort_kv_close_the_loop_by_default():
    """api.sort / engine.sort_kv on a mesh wire telemetry + learned capacity
    through the default planner automatically — the second skewed call pays
    no retry without the caller doing anything."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import sort
        from repro.engine import sort_kv
        from repro.engine.planner import default_planner, plan_key

        mesh = jax.make_mesh((8,), ("x",))
        n = 1024
        rng = np.random.default_rng(0)
        x = rng.integers(0, 3000, n).astype(np.int32)
        kw = dict(mode="range", lo=0, hi=8000)

        planner = default_planner()
        key = plan_key(n, jnp.int32, mesh)
        slab, valid = sort(jnp.asarray(x), mesh=mesh, axis="x", **kw)
        assert (np.asarray(slab)[np.asarray(valid)] == np.sort(x)).all()
        obs = planner.telemetry.last(key)
        assert obs is not None and obs.retries == 1, obs

        slab, valid = sort(jnp.asarray(x), mesh=mesh, axis="x", **kw)
        assert planner.telemetry.last(key).retries == 0
        assert (np.asarray(slab)[np.asarray(valid)] == np.sort(x)).all()

        # sort_kv rides the same default-planner loop (splitters mode here:
        # uniform buckets, no overflow — but telemetry must still record)
        calls_before = planner.telemetry.calls
        k2 = rng.integers(100, 1000, n).astype(np.int32)
        v2 = np.arange(n, dtype=np.int32)
        sk, sv = sort_kv(jnp.asarray(k2), jnp.asarray(v2), mesh=mesh, axis="x")
        ref = np.argsort(k2, kind="stable")
        assert (np.asarray(sk) == k2[ref]).all()
        assert planner.telemetry.calls == calls_before + 1
        assert planner.telemetry.last(key).retries == 0
        print("default-planner closed loop ok")
    """)
