"""Property + unit tests for the paper's sort models (single device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare container — requirements-dev.txt installs the real one
    from _hypothesis_shim import given, settings, strategies as st

from repro.core import (
    bitonic_merge_pair,
    bitonic_sort,
    bitonic_topk,
    merge_adjacent,
    merge_sorted_pair,
    nonrecursive_merge_sort,
    recursive_merge_sort_host,
    shared_memory_sort,
)

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

ints = st.lists(st.integers(-10_000, 10_000), min_size=1, max_size=300)


# ------------------------------------------------------------- properties ---
@given(ints)
def test_bitonic_sorts_and_permutes(xs):
    x = np.asarray(xs, np.int32)
    out = np.asarray(bitonic_sort(jnp.asarray(x)))
    assert (out == np.sort(x)).all()  # sortedness + permutation in one


@given(ints)
def test_bitonic_descending(xs):
    x = np.asarray(xs, np.int32)
    out = np.asarray(bitonic_sort(jnp.asarray(x), ascending=False))
    assert (out == -np.sort(-x)).all()


@given(st.lists(st.integers(0, 7), min_size=1, max_size=200))
def test_bitonic_stability(xs):
    """Stable sort: payload order within equal keys == original order."""
    x = np.asarray(xs, np.int32)
    idx = np.arange(len(x), dtype=np.int32)
    k, v = bitonic_sort(jnp.asarray(x), jnp.asarray(idx), stable=True)
    ref = np.argsort(x, kind="stable")
    assert (np.asarray(v) == ref).all()
    assert (np.asarray(k) == x[ref]).all()


@given(ints)
def test_nonrecursive_merge_sort_matches_paper_semantics(xs):
    x = np.asarray(xs, np.int32)
    assert (np.asarray(nonrecursive_merge_sort(jnp.asarray(x))) == np.sort(x)).all()


@given(st.integers(1, 4), ints)
def test_shared_memory_sort_all_impls(log_t, xs):
    x = np.asarray(xs, np.int32)
    t = 1 << log_t
    for impl in ("xla", "bitonic", "merge"):
        out = np.asarray(shared_memory_sort(jnp.asarray(x), n_threads=t, local_impl=impl))
        assert (out == np.sort(x)).all(), impl


@given(st.lists(st.floats(-1e6, 1e6, allow_nan=False, width=32), min_size=1, max_size=128),
       st.lists(st.floats(-1e6, 1e6, allow_nan=False, width=32), min_size=1, max_size=128))
def test_merge_sorted_pair_stable_merge(a, b):
    n = min(len(a), len(b))
    a = np.sort(np.asarray(a[:n], np.float32))
    b = np.sort(np.asarray(b[:n], np.float32))
    out = np.asarray(merge_sorted_pair(jnp.asarray(a), jnp.asarray(b)))
    assert np.allclose(out, np.sort(np.concatenate([a, b])))


# ------------------------------------------------------------------ units ---
def test_recursive_host_reference():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 1000, size=(4, 37)).astype(np.int64)
    assert (recursive_merge_sort_host(x) == np.sort(x, -1)).all()


def test_bitonic_merge_pair_pow2_only():
    with pytest.raises(ValueError):
        bitonic_merge_pair(jnp.zeros(3), jnp.zeros(3))


def test_merge_adjacent_round():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 100, size=(64,)).astype(np.int32)
    x4 = np.sort(x.reshape(-1, 16), axis=-1).reshape(-1)  # sorted runs of 16
    out = np.asarray(merge_adjacent(jnp.asarray(x4), 16))
    expect = np.sort(x.reshape(-1, 32), -1).reshape(-1)
    assert (out == expect).all()


@pytest.mark.parametrize("lead,pairs", [((), 1), ((2,), 1), ((), 4), ((2,), 4), ((), 16)])
def test_merge_adjacent_is_stable_with_values(lead, pairs):
    """Rows of the round's sort (``lead`` x ``pairs``: 1, 2, 4, 8, 16) all
    merge stably: keys as ``np.sort``, equal keys left run first, with their
    values."""
    rng = np.random.default_rng(len(lead) * 100 + pairs)
    w = 32
    keys = rng.integers(0, 6, size=(*lead, pairs, 2, w)).astype(np.int32)
    keys = np.sort(keys, axis=-1)  # two sorted runs a pair
    vals = {"pos": np.arange(keys.size, dtype=np.int32).reshape(keys.shape),
            "f": rng.standard_normal(keys.shape).astype(np.float32)}
    n = pairs * 2 * w
    out, mvals = merge_adjacent(
        jnp.asarray(keys.reshape(*lead, n)), w,
        {k: jnp.asarray(v.reshape(*lead, n)) for k, v in vals.items()},
    )
    rows = keys.reshape(-1, 2 * w)
    order = np.argsort(rows, axis=-1, kind="stable")
    np.testing.assert_array_equal(np.asarray(out).reshape(-1, 2 * w), np.sort(rows, axis=-1))
    for k, v in vals.items():
        want = np.take_along_axis(v.reshape(-1, 2 * w), order, axis=-1)
        np.testing.assert_array_equal(np.asarray(mvals[k]).reshape(-1, 2 * w), want)


def test_bitonic_topk_matches_lax():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((5, 64)).astype(np.float32)
    vals, idx = bitonic_topk(jnp.asarray(x), 8)
    lv, li = jax.lax.top_k(jnp.asarray(x), 8)
    assert np.allclose(np.asarray(vals), np.asarray(lv))
    assert np.allclose(np.take_along_axis(x, np.asarray(idx), -1), np.asarray(lv))


def test_batched_leading_dims():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 3, 50)).astype(np.float32)
    out = np.asarray(bitonic_sort(jnp.asarray(x)))
    assert np.allclose(out, np.sort(x, -1))


# -------------------------------------------------- pallas local_impl path ---
# interpret mode off-TPU: small sizes/block_n keep the per-shape compiles cheap
@pytest.mark.parametrize("n", [1, 100, 256, 700])  # non-pow2 included
@pytest.mark.parametrize("n_threads", [2, 8])
def test_shared_memory_sort_pallas_impl(n, n_threads):
    rng = np.random.default_rng(6)
    x = rng.integers(-10_000, 10_000, n).astype(np.int32)
    out = shared_memory_sort(
        jnp.asarray(x), n_threads=n_threads, local_impl="pallas", block_n=64
    )
    assert (np.asarray(out) == np.sort(x)).all()


def test_shared_memory_sort_pallas_batched_and_descending():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((2, 3, 100)).astype(np.float32)
    out = shared_memory_sort(jnp.asarray(x), n_threads=4, local_impl="pallas", block_n=64)
    assert np.allclose(np.asarray(out), np.sort(x, -1))
    out = shared_memory_sort(
        jnp.asarray(x), n_threads=4, local_impl="pallas", block_n=64, ascending=False
    )
    assert np.allclose(np.asarray(out), np.sort(x, -1)[..., ::-1])


def test_fast_local_sort_pallas_matches_xla():
    from repro.core import fast_local_sort

    rng = np.random.default_rng(8)
    x = rng.integers(0, 100, (4, 130)).astype(np.int32)  # batched, non-pow2
    got = fast_local_sort(jnp.asarray(x), impl="pallas", block_n=64)
    assert (np.asarray(got) == np.sort(x, -1)).all()
    got = fast_local_sort(jnp.asarray(x), impl="pallas", block_n=64, ascending=False)
    assert (np.asarray(got) == np.sort(x, -1)[..., ::-1]).all()
    with pytest.raises(ValueError):
        fast_local_sort(jnp.asarray(x), impl="nope")
