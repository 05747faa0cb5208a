"""The one-device record sort (``repro.engine.sort_kv`` with ``mesh=None``):
heavy-tie keys carrying four int32 columns against numpy's stable argsort,
the named scopes of its programs and of the service's record and argsort
kinds, its host span, and what it compiles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import SortService, sort_kv
from repro.engine.kv import _gather_last, _order_keys
from repro.launch.compile_cache import compile_count
from test_trace_scopes import host_spans, scopes_of

N = 1 << 12


def records(seed: int, lead=()):
    """Zipf-like keys (a few values repeat very often) and four random int32
    columns."""
    rng = np.random.default_rng(seed)
    shape = lead + (N,)
    keys = (rng.zipf(1.3, size=shape) % 97 * 2654435761 % (1 << 31)).astype(np.int32)
    cols = {f"c{i}": rng.integers(-(1 << 31), 1 << 31, size=shape, dtype=np.int64).astype(np.int32)
            for i in range(4)}
    return keys, cols


def reference(keys, cols, ascending):
    order = np.argsort(keys if ascending else ~keys, axis=-1, kind="stable")
    take = lambda a: np.take_along_axis(a, order, axis=-1)  # noqa: E731
    return take(keys), {k: take(v) for k, v in cols.items()}


@pytest.mark.parametrize("lead", [(), (3,)], ids=["1d", "batched"])
@pytest.mark.parametrize("ascending", [True, False], ids=["ascending", "descending"])
def test_sort_kv_matches_numpy_stable_argsort(ascending, lead):
    keys, cols = records(7 + len(lead), lead)
    assert len(np.unique(keys)) < N // 8  # heavy ties: stability is tested
    got_k, got_c = sort_kv(jnp.asarray(keys), {k: jnp.asarray(v) for k, v in cols.items()},
                           ascending=ascending)
    want_k, want_c = reference(keys, cols, ascending)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    assert set(got_c) == set(want_c)
    for name in want_c:
        np.testing.assert_array_equal(np.asarray(got_c[name]), want_c[name])


def test_one_device_programs_carry_order_and_permute_scopes():
    keys, cols = records(1)
    text = _order_keys.lower(keys, ascending=True).compile().as_text()
    assert set(scopes_of(text, "sort")) == {"repro.kv_order"}
    assert "repro.kv_permute" not in text
    text = _gather_last.lower(cols["c0"], np.arange(N, dtype=np.int32)).compile().as_text()
    assert {s for op in ("gather", "fusion") for s in scopes_of(text, op)} == {"repro.kv_permute"}
    assert "repro.kv_order" not in text


@pytest.mark.parametrize("kind,scopes", [
    ("sort_kv", {"repro.kv_order", "repro.kv_permute"}),
    ("argsort", {"repro.kv_order"}),
])
def test_service_kinds_carry_the_same_scopes(kind, scopes):
    svc = SortService()
    gk = (256, "int32") + (((), "int32") if kind == "sort_kv" else ())
    plan, key, args = svc._signature(kind, gk, 2, True)
    exe = svc.cache.get_or_build(key, svc._builder(kind, plan, True), args)
    text = exe.as_text()
    assert set(scopes_of(text, "sort")) == {"repro.kv_order"}
    found = {s for op in ("sort", "gather", "fusion") for s in scopes_of(text, op)}
    assert scopes <= found


def test_a_new_shape_compiles_the_order_and_one_gather_and_a_repeat_nothing():
    keys, cols = records(2)
    k = jnp.asarray(keys[: N - 3])
    c = {n: jnp.asarray(v[: N - 3]) for n, v in cols.items()}
    before = compile_count()
    sort_kv(k, c)[0].block_until_ready()
    # the keys and the four columns share one gather program (same shape and dtype)
    assert compile_count() - before == 2
    before = compile_count()
    again = sort_kv(jnp.asarray(keys[: N - 3][::-1].copy()), c)
    again[0].block_until_ready()
    assert compile_count() == before
    assert host_spans(lambda: sort_kv(k, c)[0].block_until_ready()) == {"repro.kv.dispatch": 1}
