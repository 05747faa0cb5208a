"""repro.engine: autotuned plans, compiled-plan cache, key-value sorting."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import run_with_devices
from repro.engine import (
    Planner,
    SortPlan,
    SortService,
    argsort,
    plan_key,
    size_bucket,
    sort_kv,
    sort_pairs,
    topk,
)

RNG = np.random.default_rng(0)


def _key_cases(n, rng):
    base = rng.integers(100, 1000, n).astype(np.int32)
    return {
        "random": base,
        "sorted": np.sort(base),
        "reverse": np.sort(base)[::-1].copy(),
        "duplicate_heavy": rng.integers(0, 7, n).astype(np.int32),
    }


# ----------------------------------------------------------------- planner ---
def test_autotune_selects_and_persists_plan(tmp_path):
    path = str(tmp_path / "plans.json")
    planner = Planner(path)
    plan = planner.autotune(3000, jnp.int32, quick=True, reps=1)
    assert plan.strategy == "shared"
    assert plan.us_per_call > 0
    # persisted: a fresh planner reloads the same plan, bucketed by pow2 size
    reloaded = Planner(path)
    assert reloaded.lookup(3000, jnp.int32) == plan
    assert reloaded.lookup(4096, jnp.int32) == plan  # same 4096 bucket
    assert reloaded.lookup(5000, jnp.int32) is None  # 8192 bucket untuned
    assert reloaded.plan_for(5000, jnp.int32).strategy == "shared"  # default rule


def test_plan_key_separates_dtype_and_bucket():
    assert plan_key(3000, jnp.int32) == plan_key(4096, jnp.int32)
    assert plan_key(3000, jnp.int32) != plan_key(3000, jnp.float32)
    assert plan_key(4096, jnp.int32) != plan_key(4097, jnp.int32)


def test_autotune_sweeps_pallas_and_roundtrips_block_n(tmp_path):
    """Acceptance: the full candidate sweep contains pallas plans with a
    block_n grid, and a tuned pallas plan survives the JSON round-trip."""
    from repro.engine.planner import PALLAS_BLOCK_SWEEP, candidate_plans

    cands = candidate_plans()
    pallas = [c for c in cands if c.local_impl == "pallas"]
    assert sorted(c.block_n for c in pallas) == sorted(PALLAS_BLOCK_SWEEP)
    assert [c for c in cands if c.local_impl == "xla"], "xla stays in the sweep"

    # an actual sweep on this container: small bucket keeps interpret mode cheap
    path = str(tmp_path / "plans.json")
    planner = Planner(path)
    plan = planner.autotune(200, jnp.int32, reps=1)
    assert plan.us_per_call > 0
    reloaded = Planner(path).lookup(200, jnp.int32)
    assert reloaded == plan
    assert reloaded.block_n == plan.block_n  # tuned block_n round-trips

    # a pallas winner (forced) round-trips its tile width exactly
    planner.plans[plan_key(8192, jnp.float32)] = SortPlan(
        "shared", local_impl="pallas", block_n=512
    )
    planner.save()
    got = Planner(path).lookup(8192, jnp.float32)
    assert got.local_impl == "pallas" and got.block_n == 512


def test_autotune_raises_when_a_pallas_candidate_fails_to_compile(tmp_path, monkeypatch):
    """A tile width the backend refuses fails the sweep loudly: it is never
    quietly dropped from the candidates."""
    from repro.kernels.bitonic_sort import ops

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(ops, "_pallas_sort_impl", refuse)
    planner = Planner(str(tmp_path / "plans.json"))
    cands = [
        SortPlan("shared", local_impl="xla"),
        SortPlan("shared", local_impl="pallas", block_n=16),
    ]
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        planner.autotune(48, jnp.int32, reps=1, candidates=cands)
    assert planner.lookup(48, jnp.int32) is None


def test_api_sort_pallas_local_impl_matches_numpy():
    """Acceptance: sort(x, strategy='shared', local_impl='pallas') == np.sort
    for non-pow2 and batched inputs (interpret mode on this container)."""
    from repro.core import sort

    rng = np.random.default_rng(11)
    x = rng.integers(-500, 500, 777).astype(np.int32)  # non-pow2
    got = sort(jnp.asarray(x), strategy="shared", local_impl="pallas", block_n=128)
    assert (np.asarray(got) == np.sort(x)).all()
    xb = rng.standard_normal((2, 3, 100)).astype(np.float32)  # batched
    got = sort(jnp.asarray(xb), strategy="shared", local_impl="pallas", block_n=64,
               n_threads=4)
    assert np.allclose(np.asarray(got), np.sort(xb, -1))
    got = sort(jnp.asarray(x), plan=SortPlan("shared", local_impl="pallas", block_n=128),
               ascending=False)
    assert (np.asarray(got) == np.sort(x)[::-1]).all()


def test_api_sort_honours_strategy_and_plan_overrides():
    from repro.core import sort

    x = jnp.asarray(RNG.integers(100, 1000, 2048).astype(np.int32))
    want = np.sort(np.asarray(x))
    for strategy in ("shared_merge", "shared_hybrid"):
        assert (np.asarray(sort(x, strategy=strategy)) == want).all()
    assert (np.asarray(sort(x, plan=SortPlan("shared", local_impl="bitonic"))) == want).all()
    assert (np.asarray(sort(x)) == want).all()  # planner default path
    with pytest.raises(ValueError):
        sort(x, strategy="nope")
    with pytest.raises(ValueError):
        sort(x, strategy="cluster")  # needs mesh= and axis=
    with pytest.raises(ValueError, match="ascending"):
        sort(x, strategy="cluster", ascending=False)  # cluster is ascending-only


# ------------------------------------------------------------------ kv API ---
def test_sort_kv_and_argsort_match_numpy_single_device():
    for name, k in _key_cases(2000, np.random.default_rng(1)).items():
        v = np.arange(len(k), dtype=np.int32)
        ref = np.argsort(k, kind="stable")
        sk, sv = sort_pairs(jnp.asarray(k), jnp.asarray(v))
        assert (np.asarray(sk) == k[ref]).all(), name
        assert (np.asarray(sv) == ref).all(), name
        assert (np.asarray(argsort(jnp.asarray(k))) == ref).all(), name
        # descending stable: ties keep original order
        refd = np.argsort(-k.astype(np.int64), kind="stable")
        assert (np.asarray(argsort(jnp.asarray(k), ascending=False)) == refd).all(), name


def test_sort_kv_pytree_and_batched():
    rng = np.random.default_rng(2)
    k = rng.standard_normal((3, 100)).astype(np.float32)
    v = {"a": rng.standard_normal((3, 100, 4)).astype(np.float32)}
    sk, sv = sort_kv(jnp.asarray(k), jax.tree.map(jnp.asarray, v))
    order = np.argsort(k, axis=-1, kind="stable")
    assert np.allclose(np.asarray(sk), np.take_along_axis(k, order, -1))
    assert np.allclose(
        np.asarray(sv["a"]),
        np.take_along_axis(v["a"], order[..., None], 1),
    )


def test_topk_matches_lax_top_k():
    x = RNG.standard_normal((5, 64)).astype(np.float32)
    x[:, 10] = x[:, 20]  # force ties
    vals, idx = topk(jnp.asarray(x), 8)
    lv, li = jax.lax.top_k(jnp.asarray(x), 8)
    assert np.allclose(np.asarray(vals), np.asarray(lv))
    assert (np.asarray(idx) == np.asarray(li)).all()


def test_kv_pallas_impl_matches_numpy_stable():
    """sort_kv / argsort / topk on the kernel path: exact np.argsort(stable)
    equivalence, non-pow2 and batched, both directions."""
    rng = np.random.default_rng(12)
    k = rng.integers(0, 7, 300).astype(np.int32)  # duplicate-heavy
    ref = np.argsort(k, kind="stable")
    assert (np.asarray(argsort(jnp.asarray(k), impl="pallas", block_n=64)) == ref).all()
    refd = np.argsort(~k, kind="stable")
    got = argsort(jnp.asarray(k), impl="pallas", block_n=64, ascending=False)
    assert (np.asarray(got) == refd).all()

    kb = rng.standard_normal((3, 100)).astype(np.float32)  # batched kv round-trip
    v = {"a": rng.standard_normal((3, 100, 2)).astype(np.float32)}
    sk, sv = sort_kv(jnp.asarray(kb), jax.tree.map(jnp.asarray, v),
                     impl="pallas", block_n=64)
    order = np.argsort(kb, axis=-1, kind="stable")
    assert np.allclose(np.asarray(sk), np.take_along_axis(kb, order, -1))
    assert np.allclose(np.asarray(sv["a"]),
                       np.take_along_axis(v["a"], order[..., None], 1))

    x = rng.standard_normal((2, 64)).astype(np.float32)
    x[:, 3] = x[:, 9]  # ties: stable descending == lax.top_k
    vals, idx = topk(jnp.asarray(x), 5, impl="pallas", block_n=64)
    lv, li = jax.lax.top_k(jnp.asarray(x), 5)
    assert np.allclose(np.asarray(vals), np.asarray(lv))
    assert (np.asarray(idx) == np.asarray(li)).all()


def test_sort_kv_argsort_cluster_matches_numpy_reference():
    """Acceptance: engine kv ops == np.argsort references on a multi-device
    CPU mesh, for random / sorted / reverse / duplicate-heavy inputs."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.engine import sort_kv, sort_pairs, argsort
        mesh = jax.make_mesh((8,), ("x",))
        rng = np.random.default_rng(0)
        n = 4096
        base = rng.integers(100, 1000, n).astype(np.int32)
        cases = {
            "random": base,
            "sorted": np.sort(base),
            "reverse": np.sort(base)[::-1].copy(),
            "duplicate_heavy": rng.integers(0, 7, n).astype(np.int32),
        }
        for name, k in cases.items():
            v = rng.standard_normal((n, 3)).astype(np.float32)
            ref = np.argsort(k, kind="stable")
            sk, sv = sort_pairs(jnp.asarray(k), jnp.asarray(v), mesh=mesh, axis="x")
            assert (np.asarray(sk) == k[ref]).all(), name
            assert (np.asarray(sv) == v[ref]).all(), name
            idx = argsort(jnp.asarray(k), mesh=mesh, axis="x")
            assert (np.asarray(idx) == ref).all(), name
            # descending must also be stable (ties keep arrival order)
            refd = np.argsort(~k, kind="stable")
            idxd = argsort(jnp.asarray(k), mesh=mesh, axis="x", ascending=False)
            assert (np.asarray(idxd) == refd).all(), name
        # pytree payload + int8 wire compression: float leaves quantized
        # (close), integer leaves must travel uncompressed (exact)
        k = cases["random"]
        vals = {"f": rng.standard_normal((n, 4)).astype(np.float32) * 3,
                "i": np.arange(n, dtype=np.int32)}
        ref = np.argsort(k, kind="stable")
        sk, sv = sort_kv(jnp.asarray(k), jax.tree.map(jnp.asarray, vals),
                         mesh=mesh, axis="x", compress=True)
        assert (np.asarray(sk) == k[ref]).all()
        assert (np.asarray(sv["i"]) == ref).all(), "int payloads must be exact"
        rel = np.abs(np.asarray(sv["f"]) - vals["f"][ref]).max() / np.abs(vals["f"]).max()
        assert rel < 0.02, rel
        print("cluster kv ok")
    """)


# ----------------------------------------------------------------- service ---
def test_service_zero_recompiles_for_same_bucket_traffic():
    """Acceptance: a second submit with same-bucket shapes performs zero new
    compilations — asserted with jax's lowering counter, not just ours."""
    from jax._src import test_util as jtu

    rng = np.random.default_rng(3)
    svc = SortService()
    first = [rng.integers(0, 1000, n).astype(np.int32) for n in (1000, 800, 500)]
    out = svc.submit(first)
    for r, o in zip(first, out):
        assert (o == np.sort(r)).all()
    compiles_after_first = svc.cache.misses
    assert compiles_after_first == 2  # one executable per (1024, 512) bucket

    second = [rng.integers(0, 1000, n).astype(np.int32) for n in (900, 700, 400)]
    with jtu.count_jit_and_pmap_lowerings() as count:
        out2 = svc.submit(second)
    assert count() == 0, "serving hot path must not re-trace"
    assert svc.cache.misses == compiles_after_first
    for r, o in zip(second, out2):
        assert (o == np.sort(r)).all()
    assert svc.stats.requests == 6 and svc.stats.throughput_keys_per_s() > 0


def test_service_kinds_and_stats():
    rng = np.random.default_rng(4)
    svc = SortService()
    reqs = [rng.integers(0, 100, n).astype(np.int32) for n in (300, 200)]
    vals = [rng.standard_normal((len(r), 2)).astype(np.float32) for r in reqs]
    for r, o in zip(reqs, svc.submit(reqs, kind="argsort")):
        assert (o == np.argsort(r, kind="stable")).all()
    for r, o in zip(reqs, svc.submit(reqs, kind="sort", ascending=False)):
        assert (o == np.sort(r)[::-1]).all()
    for r, v, (sk, sv) in zip(reqs, vals, svc.submit(reqs, kind="sort_kv", values=vals)):
        ref = np.argsort(r, kind="stable")
        assert (sk == r[ref]).all() and (sv == v[ref]).all()
    assert svc.stats.batches >= 3
    with pytest.raises(ValueError):
        svc.submit(reqs, kind="sort_kv")  # missing values
    with pytest.raises(ValueError):
        svc.submit([np.zeros((2, 2), np.int32)])  # not 1-D
    with pytest.raises(ValueError, match="NaN"):
        svc.submit([np.array([1.0, np.nan], np.float32)])


def test_service_sort_kv_mixed_value_shapes_same_bucket():
    """Requests whose keys share a length bucket but carry different payload
    shapes must group separately, not error."""
    rng = np.random.default_rng(5)
    svc = SortService()
    reqs = [rng.integers(0, 100, n).astype(np.int32) for n in (900, 1000)]
    vals = [
        rng.standard_normal((900, 2)).astype(np.float32),
        rng.standard_normal((1000, 4)).astype(np.float32),
    ]
    for r, v, (sk, sv) in zip(reqs, vals, svc.submit(reqs, kind="sort_kv", values=vals)):
        ref = np.argsort(r, kind="stable")
        assert (sk == r[ref]).all() and (sv == v[ref]).all()


def test_service_runs_tuned_pallas_plan_and_keys_on_block_n():
    """A planner cell tuned to pallas drives the service's local sort; two
    plans differing only in block_n must compile distinct executables."""
    rng = np.random.default_rng(6)
    planner = Planner()
    planner.plans[plan_key(512, jnp.int32)] = SortPlan(
        "shared", local_impl="pallas", block_n=64
    )
    svc = SortService(planner=planner)
    reqs = [rng.integers(0, 1000, n).astype(np.int32) for n in (500, 400)]
    for r, o in zip(reqs, svc.submit(reqs)):
        assert (o == np.sort(r)).all()
    entries_before = len(svc.cache.executables)

    planner.plans[plan_key(512, jnp.int32)] = SortPlan(
        "shared", local_impl="pallas", block_n=128
    )
    for r, o in zip(reqs, svc.submit(reqs)):
        assert (o == np.sort(r)).all()
    assert len(svc.cache.executables) == entries_before + 1, (
        "block_n must be part of the executable cache key"
    )


def test_size_bucket_pow2():
    assert size_bucket(1000) == 1024
    assert size_bucket(1024) == 1024
    assert size_bucket(3, min_bucket=8) == 8


# ------------------------------------------------------ plan-cache robustness ---
def test_planner_load_graceful_on_corrupt_or_unknown_cache(tmp_path):
    """A serving process must never die because its tuned-plans file rotted:
    corrupt/truncated/unknown-schema caches warn and fall back to the
    default-plan rule instead of raising."""
    import json
    import warnings

    bad_files = {
        "corrupt.json": "{this is not json",
        "truncated.json": '{"version": 1, "plans": {"4096|int32|x": {"strat',
        "badversion.json": '{"version": 99, "plans": {}}',
        "notadict.json": '{"version": 1, "plans": {"k": ["not", "a", "dict"]}}',
        "badstrategy.json": '{"version": 1, "plans": {"k": {"strategy": "warp"}}}',
        "noplans.json": '{"version": 1}',
        "plansnotobj.json": '{"version": 1, "plans": 7}',
    }
    for name, content in bad_files.items():
        p = tmp_path / name
        p.write_text(content)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            planner = Planner(str(p))
        assert planner.plans == {}, name
        assert any("plan cache" in str(x.message) for x in w), name
        # lookups fall back to the default rule, not an exception
        assert planner.plan_for(1000, jnp.int32).strategy == "shared", name

    # unknown *extra fields* in an otherwise valid entry are forward-compat:
    # the known fields load, the unknown ones are ignored
    fwd = tmp_path / "forward.json"
    fwd.write_text(json.dumps({
        "version": 1,
        "plans": {plan_key(4096, jnp.int32): {
            "strategy": "shared", "local_impl": "xla", "from_the_future": 1,
        }},
    }))
    assert Planner(str(fwd)).lookup(4096, jnp.int32).local_impl == "xla"

    # a live re-load of a rotted file keeps the last-known-good plans
    # instead of wiping the table a serving process is already using
    survivor = Planner(str(fwd))
    assert survivor.plans
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        survivor.load(str(tmp_path / "corrupt.json"))
    assert survivor.lookup(4096, jnp.int32).local_impl == "xla"

    # tooling that *writes* plan caches wants the error, not the fallback
    with pytest.raises(Exception):
        Planner().load(str(tmp_path / "corrupt.json"), strict=True)
