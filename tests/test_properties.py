"""Property-based correctness harness for every sort path in the repo.

One oracle: numpy (``np.sort`` / ``np.argsort(kind='stable')``).  One input
generator: random lengths and dtypes crossed with an adversarial case matrix
(duplicate-heavy, pre-sorted, reverse-sorted, all-equal, ±inf floats / int
extremes).  Every path — ``api.sort`` across the paper's models and all
``local_impl`` engines, ``engine.kv`` (sort_kv / argsort / topk), and the
sync service and the queued ``SortFrontend`` over it — must reproduce the
oracle exactly.

Runs under real ``hypothesis`` when installed (CI) with a fixed,
derandomized profile so CI stays deterministic; falls back to the seeded
shim in bare containers.
"""
import numpy as np
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare container — requirements-dev.txt installs the real one
    from _hypothesis_shim import given, settings, strategies as st

from conftest import run_with_devices
from repro.core import sort
from repro.engine import SortFrontend, SortService, Tenant, argsort, sort_pairs, topk
from repro.exchange import splitter_bucket, splitters_from_sample

# fixed + derandomized: the same examples on every CI run
settings.register_profile("repro-ci", max_examples=10, deadline=None,
                          derandomize=True)
settings.load_profile("repro-ci")

CASES = ("random", "duplicate_heavy", "sorted", "reverse", "all_equal", "extremes")
DTYPES = ("int32", "float32")
LOCAL_IMPLS = ("xla", "bitonic", "merge", "pallas")

lengths = st.integers(1, 300)
cases = st.sampled_from(CASES)
dtypes = st.sampled_from(DTYPES)
seeds = st.integers(0, 2**20)


def make_keys(case: str, n: int, dtype: str, seed: int) -> np.ndarray:
    """One adversarial (or random) key array, NaN-free by construction."""
    dt = np.dtype(dtype)
    rng = np.random.default_rng(seed)
    if np.issubdtype(dt, np.floating):
        base = (rng.standard_normal(n) * 1e3).astype(dt)
    else:
        base = rng.integers(-10_000, 10_000, n).astype(dt)
    if case == "duplicate_heavy":
        pool = np.asarray([-3, 0, 7, 7, 42], dt)
        base = rng.choice(pool, n)
    elif case == "sorted":
        base = np.sort(base)
    elif case == "reverse":
        base = np.sort(base)[::-1].copy()
    elif case == "all_equal":
        base = np.full(n, base[0], dt)
    elif case == "extremes":
        # ±inf for floats / iinfo extremes for ints: ties against the
        # padding sentinels every padded path uses internally
        if np.issubdtype(dt, np.floating):
            lo, hi = -np.inf, np.inf
        else:
            lo, hi = np.iinfo(dt).min, np.iinfo(dt).max
        base[rng.random(n) < 0.2] = hi
        base[rng.random(n) < 0.2] = lo
    return base


def np_rev(k: np.ndarray) -> np.ndarray:
    """Order-reversing bijection matching engine.kv._rev_key (descending
    stable references: np.argsort(np_rev(k), kind='stable'))."""
    return ~k if np.issubdtype(k.dtype, np.integer) else -k


# one service per module: examples share the compiled-executable cache, so
# the harness exercises the steady state instead of recompiling per example
SERVICE = SortService()


# --------------------------------------------------------- api.sort (A/B) ---
@given(lengths, cases, dtypes, seeds)
def test_api_sort_shared_models_all_local_impls(n, case, dtype, seed):
    """Models A/B (shared memory) x every local_impl, both directions."""
    x = make_keys(case, n, dtype, seed)
    want = np.sort(x)
    for impl in LOCAL_IMPLS:
        if impl == "pallas" and n > 128:
            continue  # interpret-mode kernel: cap the per-example cost off-TPU
        kw = {"block_n": 64} if impl == "pallas" else {}
        got = sort(jnp.asarray(x), strategy="shared", local_impl=impl,
                   n_threads=4, **kw)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=impl)
        got = sort(jnp.asarray(x), strategy="shared", local_impl=impl,
                   n_threads=4, ascending=False, **kw)
        np.testing.assert_array_equal(np.asarray(got), want[::-1], err_msg=impl)
    # model A's paper schedule (merge-sort local stage) via its strategy name
    got = sort(jnp.asarray(x), strategy="shared_merge", n_threads=4)
    np.testing.assert_array_equal(np.asarray(got), want)


# ----------------------------------------------------------- engine.kv ------
@given(lengths, cases, dtypes, seeds)
def test_engine_kv_argsort_sortkv_topk(n, case, dtype, seed):
    """sort_kv / argsort / topk == numpy stable references, xla and pallas."""
    k = make_keys(case, n, dtype, seed)
    ref = np.argsort(k, kind="stable")
    refd = np.argsort(np_rev(k), kind="stable")
    v = np.arange(n, dtype=np.int32)
    kt = min(n, 5)
    for impl in ("xla", "pallas"):
        if impl == "pallas" and n > 128:
            continue  # interpret-mode kernel: cap the per-example cost off-TPU
        kw = {"impl": impl, "block_n": 64} if impl == "pallas" else {"impl": impl}
        got = np.asarray(argsort(jnp.asarray(k), **kw))
        np.testing.assert_array_equal(got, ref, err_msg=impl)
        got = np.asarray(argsort(jnp.asarray(k), ascending=False, **kw))
        np.testing.assert_array_equal(got, refd, err_msg=impl)
        sk, sv = sort_pairs(jnp.asarray(k), jnp.asarray(v), **kw)
        np.testing.assert_array_equal(np.asarray(sk), k[ref], err_msg=impl)
        np.testing.assert_array_equal(np.asarray(sv), ref, err_msg=impl)
        vals, idx = topk(jnp.asarray(k), kt, **kw)
        np.testing.assert_array_equal(np.asarray(idx), refd[:kt], err_msg=impl)
        np.testing.assert_array_equal(np.asarray(vals), k[refd[:kt]], err_msg=impl)


# ------------------------------------------- splitter derivation (sample) ---
@given(st.integers(8, 2048), st.integers(2, 32), cases, dtypes, seeds)
def test_splitter_derivation_properties(n, n_buckets, case, dtype, seed):
    """The sample partition's splitter math, against the same case matrix:
    splitters come back sorted and deduplicated, derivation is a pure
    function of the sample, and the induced bucket assignment is total and
    order-compatible with the key order."""
    sample = make_keys(case, n, dtype, seed)
    spl = np.asarray(splitters_from_sample(sample, n_buckets, unique=True))
    again = np.asarray(splitters_from_sample(sample, n_buckets, unique=True))
    np.testing.assert_array_equal(spl, again)      # deterministic
    assert 1 <= len(spl) <= n_buckets - 1
    if len(spl) > 1:
        assert np.all(np.diff(spl) > 0)            # sorted + deduplicated
    # the partition they induce: every key lands in exactly one bucket ...
    keys = make_keys(case, n, dtype, seed + 1)
    b = np.asarray(splitter_bucket(jnp.asarray(keys), jnp.asarray(spl)))
    assert b.shape == keys.shape
    assert b.min() >= 0 and b.max() <= len(spl)
    assert int(np.bincount(b, minlength=len(spl) + 1).sum()) == n
    # ... and the assignment is monotone in the key (order-compatible:
    # concatenating bucket-sorted buckets yields the globally sorted order)
    order = np.argsort(keys, kind="stable")
    assert np.all(np.diff(b[order]) >= 0)


# ------------------------------------------------------------- services -----
@given(st.lists(st.integers(1, 600), min_size=1, max_size=5), cases, dtypes, seeds)
def test_sort_service_ragged_batches(lens, case, dtype, seed):
    """SortService.submit on ragged adversarial batches, every kind."""
    reqs = [make_keys(case, n, dtype, seed + j) for j, n in enumerate(lens)]
    vals = [np.arange(len(r), dtype=np.int32) for r in reqs]
    for r, o in zip(reqs, SERVICE.submit(reqs)):
        np.testing.assert_array_equal(o, np.sort(r))
    for r, o in zip(reqs, SERVICE.submit(reqs, ascending=False)):
        np.testing.assert_array_equal(o, np.sort(r)[::-1])
    for r, o in zip(reqs, SERVICE.submit(reqs, kind="argsort")):
        np.testing.assert_array_equal(o, np.argsort(r, kind="stable"))
    for r, v, (sk, sv) in zip(reqs, vals,
                              SERVICE.submit(reqs, kind="sort_kv", values=vals)):
        ref = np.argsort(r, kind="stable")
        np.testing.assert_array_equal(sk, r[ref])
        np.testing.assert_array_equal(sv, ref)


@given(st.lists(st.integers(1, 600), min_size=1, max_size=5), cases, dtypes, seeds)
def test_frontend_ragged_batches(lens, case, dtype, seed):
    """SortFrontend tickets == the sync oracle, interleaved kinds."""
    fe = SortFrontend(SERVICE, tenants=[Tenant("t")], max_batch=8)
    reqs = [make_keys(case, n, dtype, seed + j) for j, n in enumerate(lens)]
    futs = [(r, "sort", fe.submit("t", r)) for r in reqs]
    futs += [(r, "argsort", fe.submit("t", r, kind="argsort")) for r in reqs]
    futs += [
        (r, "sort_kv",
         fe.submit("t", r, kind="sort_kv",
                   values=np.arange(len(r), dtype=np.int32)))
        for r in reqs
    ]
    fe.poll()
    for r, kind, f in futs:
        ref = np.argsort(r, kind="stable")
        if kind == "sort":
            np.testing.assert_array_equal(f.result(timeout=60), np.sort(r))
        elif kind == "argsort":
            np.testing.assert_array_equal(f.result(timeout=60), ref)
        else:
            sk, sv = f.result(timeout=60)
            np.testing.assert_array_equal(sk, r[ref])
            np.testing.assert_array_equal(sv, ref)


# --------------------------------------------- distributed models (C / D) ---
def test_api_sort_distributed_models_case_matrix():
    """The mesh leg of the harness: models C and D through api.sort on a
    forced 8-device mesh, across the same adversarial case matrix."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import sort

        mesh = jax.make_mesh((8,), ("x",))
        n = 1024
        def make(case, dtype, seed):
            rng = np.random.default_rng(seed)
            dt = np.dtype(dtype)
            if np.issubdtype(dt, np.floating):
                base = (rng.standard_normal(n) * 1e3).astype(dt)
            else:
                base = rng.integers(-10_000, 10_000, n).astype(dt)
            if case == "duplicate_heavy":
                base = rng.choice(np.asarray([-3, 0, 7, 7, 42], dt), n)
            elif case == "sorted":
                base = np.sort(base)
            elif case == "reverse":
                base = np.sort(base)[::-1].copy()
            elif case == "all_equal":
                base = np.full(n, base[0], dt)
            return base

        cases = ("random", "duplicate_heavy", "sorted", "reverse", "all_equal")
        for dtype in ("int32", "float32"):
            for ci, case in enumerate(cases):
                x = make(case, dtype, seed=100 + ci)
                want = np.sort(x)
                for impl in ("xla", "merge"):   # model C: ppermute merge tree
                    got = sort(jnp.asarray(x), strategy="distributed_merge",
                               mesh=mesh, axis="x", local_impl=impl)
                    assert (np.asarray(got) == want).all(), ("C", impl, case, dtype)
                for impl in ("xla", "bitonic", "pallas"):  # model D: cluster
                    kw = {"block_n": 64} if impl == "pallas" else {}
                    slab, valid = sort(jnp.asarray(x), strategy="cluster",
                                       mesh=mesh, axis="x", local_impl=impl, **kw)
                    got = np.asarray(slab)[np.asarray(valid)]
                    assert (got == want).all(), ("D", impl, case, dtype)
                # model D again across both partition families (PR 8): the
                # auto-ranged radix and the composite-splitter sample modes
                # must match the oracle on every adversarial case too
                # (explicit capacity_factor= keeps the fuzz out of the
                # process-wide capacity-learning loop)
                for mode in ("radix", "sample"):
                    slab, valid = sort(jnp.asarray(x), strategy="cluster",
                                       mesh=mesh, axis="x", mode=mode,
                                       capacity_factor=2.0)
                    got = np.asarray(slab)[np.asarray(valid)]
                    assert (got == want).all(), ("D", mode, case, dtype)
        print("C/D case matrix ok")
    """)
