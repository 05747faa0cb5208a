"""The one-device record sort by a multi-word key (``repro.engine.sort_kv``
with a tuple of key words): Sort Benchmark-like records against numpy's
stable ``lexsort``, with ties forced in the leading words and in the whole
key; both directions; word-major and 1-D payload leaves; what it refuses;
the grouped carry of many leaves; and the programs each kind of key lowers
to."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import sort_kv
from repro.engine import kv
from repro.engine.kv import CARRY_SPAN, _CARRY_WORDS, _by_rank, _groups, _inverse, _word_order
from repro.launch.compile_cache import compile_count
from test_trace_scopes import host_events, host_spans, scopes_of

N = 1 << 11
WORDS = 6
KEY_HALF = 0xFFFF0000


def key_words(seed: int, ties: str, dtype=np.uint32, lead=()):
    """Three key words. ``ties`` picks where keys collide: ``w0`` (few
    leading words, the rest distinct), ``w0w1`` (few first two words) or
    ``full`` (few whole keys, so equal keys are common)."""
    rng = np.random.default_rng(seed)
    shape = lead + (N,)

    def word(values: int):
        w = rng.integers(0, 1 << 32, size=shape, dtype=np.uint64).astype(np.uint32)
        if values:
            w = np.unique(w)[:values][rng.integers(0, values, size=shape)]
        return w

    few = {"w0": (4, 0, 0), "w0w1": (3, 3, 0), "full": (2, 2, 3)}[ties]
    w0, w1, w2 = (word(v) for v in few)
    return tuple(w.astype(dtype) for w in (w0, w1, w2 & np.uint32(KEY_HALF)))


def payload(seed: int, lead=()):
    rng = np.random.default_rng(seed + 100)
    return rng.integers(0, 1 << 32, size=(WORDS,) + lead + (N,), dtype=np.uint64).astype(np.uint32)


def lexsorted(keys, table, ascending):
    """numpy's stable lexsort order (first word most significant) applied to
    the keys and to each payload word; descending by the keys' complements,
    so equal keys keep input order either way."""
    ks = keys if ascending else tuple(~k for k in keys)
    order = np.lexsort(ks[::-1], axis=-1)
    take = lambda a: np.take_along_axis(a, order, axis=-1)  # noqa: E731
    return tuple(take(k) for k in keys), np.stack([take(w) for w in table])


@pytest.mark.parametrize("layout", ["word_major", "1d_leaves"])
@pytest.mark.parametrize("ascending", [True, False], ids=["ascending", "descending"])
@pytest.mark.parametrize("ties", ["w0", "w0w1", "full"])
def test_multi_word_key_matches_numpy_lexsort(ties, ascending, layout):
    keys = key_words(3, ties)
    table = payload(3)
    if ties == "full":
        # equal whole keys are common, so the payload shows any tie reordered
        assert len({(a, b, c) for a, b, c in zip(*keys)}) <= 12
    values = (jnp.asarray(table) if layout == "word_major"
              else {f"w{i}": jnp.asarray(w) for i, w in enumerate(table)})
    got_k, got_v = sort_kv(tuple(jnp.asarray(k) for k in keys), values, ascending=ascending)
    want_k, want_t = lexsorted(keys, table, ascending)
    assert isinstance(got_k, tuple) and len(got_k) == 3
    for g, w in zip(got_k, want_k):
        np.testing.assert_array_equal(np.asarray(g), w)
    got_t = (np.asarray(got_v) if layout == "word_major"
             else np.stack([np.asarray(got_v[f"w{i}"]) for i in range(WORDS)]))
    np.testing.assert_array_equal(got_t, want_t)


@pytest.mark.parametrize("case", ["signed_words", "batched", "mixed_leaves"])
def test_multi_word_key_signed_batched_and_mixed_leaves(case):
    """int32 words compare signed; a leading batch dimension sorts each row;
    one call may mix a word-major leaf and a 1-D leaf."""
    lead = (3,) if case == "batched" else ()
    dtype = np.int32 if case == "signed_words" else np.uint32
    keys = key_words(5, "w0w1", dtype, lead)
    table = payload(5, lead)
    values = {"t": jnp.asarray(table[:4]), "x": jnp.asarray(table[4]), "y": jnp.asarray(table[5])}
    got_k, got_v = sort_kv(tuple(jnp.asarray(k) for k in keys), values)
    want_k, want_t = lexsorted(keys, table, True)
    for g, w in zip(got_k, want_k):
        np.testing.assert_array_equal(np.asarray(g), w)
    np.testing.assert_array_equal(np.asarray(got_v["t"]), want_t[:4])
    np.testing.assert_array_equal(np.asarray(got_v["x"]), want_t[4])
    np.testing.assert_array_equal(np.asarray(got_v["y"]), want_t[5])


@pytest.mark.parametrize("ascending", [True, False], ids=["ascending", "descending"])
def test_one_word_tuple_agrees_with_the_scalar_key(ascending):
    rng = np.random.default_rng(9)
    keys = jnp.asarray(rng.integers(0, 40, N).astype(np.int32) - 20)
    cols = {"c": jnp.asarray(rng.integers(0, 1 << 30, N).astype(np.int32))}
    (tk,), tv = sort_kv((keys,), cols, ascending=ascending)
    sk, sv = sort_kv(keys, cols, ascending=ascending)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(sk))
    np.testing.assert_array_equal(np.asarray(tv["c"]), np.asarray(sv["c"]))


def test_the_mesh_path_refuses_a_multi_word_key(debug_mesh):
    keys = tuple(jnp.zeros(8, jnp.uint32) for _ in range(3))
    with pytest.raises(NotImplementedError, match="composite splitters"):
        sort_kv(keys, {"v": jnp.zeros(8, jnp.uint32)}, mesh=debug_mesh, axis="x")


@pytest.mark.parametrize("keys,values,error", [
    ((), {}, ValueError),
    ((np.zeros(8, np.uint32), np.zeros(4, np.uint32)), {}, TypeError),
    ((np.zeros(8, np.uint32), np.zeros(8, np.float32)), {}, TypeError),
    ((np.zeros(8, np.uint32),), {"v": np.zeros((8, 25), np.uint32)}, ValueError),
], ids=["no_words", "unequal_shapes", "float_word", "record_major_leaf"])
def test_multi_word_key_refuses_what_it_cannot_sort(keys, values, error):
    keys = tuple(jnp.asarray(k) for k in keys)
    with pytest.raises(error):
        sort_kv(keys, {k: jnp.asarray(v) for k, v in values.items()})


def test_multi_word_key_refuses_the_pallas_order():
    keys = (jnp.zeros(8, jnp.uint32), jnp.zeros(8, jnp.uint32))
    with pytest.raises(ValueError, match="impl='xla'"):
        sort_kv(keys, {}, impl="pallas")


def test_multi_word_key_orders_then_permutes_by_sorts_and_no_gather():
    """One sort of the key words under ``repro.kv_order``; the inverse and
    each leaf's permutation one sort by the rank under ``repro.kv_permute``.
    The two kinds of sort differ in their first result (the uint32 key
    word, the int32 order or rank), which is all a trace that names
    operations by kind can tell them apart by."""
    keys = tuple(jnp.asarray(k) for k in key_words(1, "w0"))
    order_text = _word_order.lower(keys, ascending=True).compile().as_text()
    assert scopes_of(order_text, "sort") == ["repro.kv_order"]
    rank = jnp.arange(N, dtype=jnp.int32)
    texts = [_inverse.lower(rank).compile().as_text()] + [
        _by_rank.lower(rank, leaf).compile().as_text()
        for leaf in (jnp.asarray(payload(1)), jnp.asarray(payload(1)[0]))]
    for text in texts:
        assert scopes_of(text, "sort") == ["repro.kv_permute"]
        assert re.search(r"= \(s32\[", text) and " sort(" in text
    assert re.search(r"= \(u32\[\d+\]\S*, u32", order_text)
    for text in [_word_order.lower(keys, ascending=False).compile().as_text()] + texts:
        assert not scopes_of(text, "gather")


def test_scalar_key_still_runs_the_order_and_per_leaf_gathers(monkeypatch):
    """A single key array keeps the argsort and one gather program per leaf,
    and never reaches the multi-word body."""
    from repro.engine import kv

    called = []
    monkeypatch.setattr(kv, "_carry_records", lambda *a, **k: called.append(a))
    monkeypatch.setattr(kv, "_order_keys", _counting(kv._order_keys, called, "order"))
    monkeypatch.setattr(kv, "_gather_last", _counting(kv._gather_last, called, "gather"))
    keys = jnp.arange(N, dtype=jnp.int32)[::-1]
    sort_kv(keys, {"a": keys, "b": keys})
    assert called == ["order", "gather", "gather", "gather"]


def _counting(fn, log, name):
    def wrapped(*a, **k):
        log.append(name)
        return fn(*a, **k)

    return wrapped


def test_a_new_record_shape_compiles_three_programs_a_repeat_nothing_and_one_span():
    """The order, its inverse, and one permutation program that both groups
    of the six uint32 words share (near-equal groups: three and three); one
    ``repro.*`` span a call, the dispatch, and one carry span."""
    keys = tuple(jnp.asarray(k[: N - 5]) for k in key_words(2, "full"))
    table = {f"w{i}": jnp.asarray(w[: N - 5]) for i, w in enumerate(payload(2))}
    other = tuple(jnp.asarray(np.asarray(k)[::-1].copy()) for k in keys)
    before = compile_count()
    jax.block_until_ready(sort_kv(keys, table))
    assert compile_count() - before == 3
    before = compile_count()
    jax.block_until_ready(sort_kv(other, table))
    assert compile_count() == before
    assert _groups(WORDS) == [3, 3]
    assert host_spans(lambda: jax.block_until_ready(sort_kv(keys, table))) == {"repro.kv.dispatch": 1}
    assert host_spans(lambda: jax.block_until_ready(sort_kv(keys, table)), CARRY_SPAN) == {CARRY_SPAN: 1}


LEAF_DTYPES = (np.uint32, np.int32, np.float32)


def mixed_leaves(seed: int, count: int):
    """``count`` 1-D leaves cycling through uint32, int32 and float32."""
    rng = np.random.default_rng(seed + 200)
    leaves = []
    for i in range(count):
        dtype = LEAF_DTYPES[i % len(LEAF_DTYPES)]
        if dtype == np.float32:
            leaves.append(rng.standard_normal(N).astype(np.float32))
        else:
            leaves.append(rng.integers(0, 1 << 32, N, dtype=np.uint64).astype(np.uint32).view(dtype))
    return leaves


@pytest.mark.parametrize("ascending", [True, False], ids=["ascending", "descending"])
@pytest.mark.parametrize("count", [1, 4, 5, 7, 25, 26])
def test_grouped_carry_matches_lexsort_bit_for_bit(count, ascending):
    """However many leaves, and whatever their dtypes, each comes back in
    numpy's stable lexsort order, equal keys in input order, every bit kept."""
    keys = key_words(7, "full")
    assert len({(a, b, c) for a, b, c in zip(*keys)}) <= 12
    leaves = mixed_leaves(7, count)
    values = {f"v{i:02d}": jnp.asarray(v) for i, v in enumerate(leaves)}
    got_k, got_v = sort_kv(tuple(jnp.asarray(k) for k in keys), values, ascending=ascending)
    ks = keys if ascending else tuple(~k for k in keys)
    order = np.lexsort(ks[::-1])
    for g, w in zip(got_k, keys):
        np.testing.assert_array_equal(np.asarray(g), w[order])
    for i, v in enumerate(leaves):
        got = np.asarray(got_v[f"v{i:02d}"])
        assert got.dtype == v.dtype
        np.testing.assert_array_equal(got.view(np.uint32), v[order].view(np.uint32))


def test_near_equal_groups():
    assert _CARRY_WORDS == 5
    assert {n: _groups(n) for n in (0, 1, 4, 5, 6, 7, 25, 26)} == {
        0: [], 1: [1], 4: [4], 5: [5], 6: [3, 3], 7: [4, 3], 25: [5] * 5,
        26: [5, 5, 4, 4, 4, 4]}


def test_a_record_table_dispatches_a_rank_sort_a_group_and_no_gather(monkeypatch):
    """25 record words go through ``ceil(25 / _CARRY_WORDS)`` sorts by the
    rank, each led by the int32 rank under ``repro.kv_permute``, none with
    a gather."""
    calls = []

    def recording(rank, *vs):
        if not isinstance(rank, jax.core.Tracer):
            calls.append((rank, vs))
        return _by_rank(rank, *vs)

    monkeypatch.setattr(kv, "_by_rank", recording)
    keys = tuple(jnp.asarray(k) for k in key_words(4, "w0w1"))
    table = {f"w{i:02d}": jnp.asarray(w) for i, w in enumerate(mixed_leaves(4, 25))}
    sort_kv(keys, table)
    assert [len(vs) for _, vs in calls] == _groups(25)
    assert len(calls) == -(-25 // _CARRY_WORDS)
    for rank, vs in calls:
        text = _by_rank.lower(rank, *vs).compile().as_text()
        assert scopes_of(text, "sort") == ["repro.kv_permute"]
        assert re.search(r"= \(s32\[\d+\]\S*, ", text) and " sort(" in text
        assert not scopes_of(text, "gather")


@pytest.mark.parametrize("flat,word_major", [(25, 0), (7, 1), (0, 2)])
def test_the_carry_span_reports_its_sorts_and_leaves(flat, word_major):
    """The carry span: one rank sort a group of key-shaped leaves and one a
    word-major leaf, over every leaf carried."""
    keys = tuple(jnp.asarray(k) for k in key_words(6, "w0"))
    values = {f"w{i:02d}": jnp.asarray(v) for i, v in enumerate(mixed_leaves(6, flat))}
    values.update({f"t{i}": jnp.asarray(payload(6 + i)[:2]) for i in range(word_major)})
    run = lambda: jax.block_until_ready(sort_kv(keys, values))  # noqa: E731
    run()
    carry = [args for _, args in host_events(run, CARRY_SPAN)]
    assert carry == [{"sorts": len(_groups(flat)) + word_major, "leaves": flat + word_major}]
