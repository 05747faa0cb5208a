"""``compact_slabs``: the slab -> dense compaction, against numpy.

Each shard's valid prefix is one run of dense positions, so the compaction
builds every shard's dense range from one slice of the gathered slab per
source shard.  Every case here compacts a pytree (an int32 vector, an int32
leaf with a trailing dim and a float32 leaf with two, NaNs included) on
meshes of 1, 2 and 4 forced host devices, and checks it bit for bit against
numpy's ``slab[valid]`` and against the per-element gather
(``all_gather(a)[src]``) it replaced.  ``C_total`` is never below ``m``: the
``n = P * m`` valid slots fit in ``P * C_total``.  All cases share one
subprocess with 4 forced host devices (device count is fixed at the first
jax import), whose results the parametrised cases read.
"""
import json

import pytest

from conftest import run_with_devices

MESH_SIZES = [1, 2, 4]
CASES = ["skewed", "empty_shards", "straddle", "full", "sparse", "all_on_shard_0",
         "random", "sort_kv_zipf"]

_CASES = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.engine import sort_kv
from repro.exchange import compact_slabs

MESH_SIZES = %(mesh_sizes)r
CASES = %(cases)r


def gather_compact(mesh, n):
    # the formulation compact_slabs had: one gather of the gathered slab
    P_ = mesh.shape["x"]
    m = n // P_

    def body(tree, valid):
        C_total = valid.shape[0]
        counts = jax.lax.all_gather(jnp.sum(valid, dtype=jnp.int32), "x")
        ends = jnp.cumsum(counts)
        g = jax.lax.axis_index("x") * m + jnp.arange(m, dtype=jnp.int32)
        owner = jnp.searchsorted(ends, g, side="right")
        src = owner * C_total + g - (ends[owner] - counts[owner])
        return jax.tree.map(lambda a: jax.lax.all_gather(a, "x", tiled=True)[src], tree)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P("x")),
                                 out_specs=P("x")))


def trim(counts, P_):
    # drop slots from the last shards until the total divides P_
    counts = np.array(counts, np.int64)
    extra = counts.sum() %% P_
    for p in range(P_ - 1, -1, -1):
        d = min(extra, counts[p])
        counts[p] -= d
        extra -= d
    return counts


def geometry(case, P_, rng):
    # (C_total, counts per shard) of a slab_valid slab; None: the
    # all-on-shard-0 mask distributed_merge_sort passes
    if case == "skewed":
        C = 48
        return C, trim([C] + list(rng.integers(0, 6, P_ - 1)), P_)
    if case == "empty_shards":
        C = 40
        return C, trim([0 if p %% 2 == 0 and P_ > 1 else 37 - p for p in range(P_)], P_)
    if case == "straddle":
        C = 100
        return C, trim(([3, C, 5, 7] * P_)[:P_], P_)
    if case == "full":
        C = 24
        return C, np.full(P_, C)
    if case == "sparse":
        C = 256
        return C, trim(rng.integers(1, 9, P_), P_)
    if case == "random":
        C = 33
        return C, trim(rng.integers(0, C + 1, P_), P_)
    assert case == "all_on_shard_0"
    return None


def tree_of(total, rng):
    f = rng.standard_normal((total, 2, 2)).astype(np.float32)
    f[rng.random((total, 2, 2)) < 0.1] = np.nan
    return (rng.integers(-2**31, 2**31 - 1, total, dtype=np.int64).astype(np.int32),
            {"pay": rng.integers(-50, 50, (total, 3)).astype(np.int32), "f": f})


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == np.float32:
        a, b = a.view(np.uint32), b.view(np.uint32)
    return bool(a.shape == b.shape and np.array_equal(a, b))


def compare(got, want):
    g, w = jax.tree.leaves(got), jax.tree.leaves(want)
    return len(g) == len(w) and all(same_bits(a, b) for a, b in zip(g, w))


def zipf_sort_kv(mesh, P_, rng):
    n = 512 * P_
    w = 1.0 / np.arange(1, 201) ** 0.99
    keys = rng.choice(200, n, p=w / w.sum()).astype(np.int32)
    pay = {"a": rng.integers(-2**31, 2**31 - 1, n, dtype=np.int64).astype(np.int32),
           "b": rng.integers(0, 9, (n, 2)).astype(np.int32)}
    k, v = sort_kv(jnp.asarray(keys), jax.tree.map(jnp.asarray, pay), mesh=mesh, axis="x",
                   capacity_factor=2.0)
    order = np.argsort(keys, kind="stable")
    return compare((k, v), (keys[order], {kk: vv[order] for kk, vv in pay.items()}))


out = {}
for P_ in MESH_SIZES:
    mesh = jax.make_mesh((P_,), ("x",), devices=jax.devices()[:P_])
    for i, case in enumerate(CASES):
        rng = np.random.default_rng(100 * P_ + i)
        if case == "sort_kv_zipf":
            out[f"{P_}/{case}"] = {"numpy": zipf_sort_kv(mesh, P_, rng)}
            continue
        geo = geometry(case, P_, rng)
        if geo is None:
            n = 16 * P_
            C, total = n, P_ * n
            valid = np.arange(total) < n
        else:
            C, counts = geo
            total, n = P_ * C, int(counts.sum())
            valid = (np.arange(total) %% C) < np.repeat(counts, C)
        tree = tree_of(total, rng)
        args = (jax.tree.map(jnp.asarray, tree), jnp.asarray(valid))
        got = compact_slabs(*args, n, mesh, "x")
        out[f"{P_}/{case}"] = {
            "numpy": compare(got, jax.tree.map(lambda a: a[valid], tree)),
            "gather": compare(got, gather_compact(mesh, n)(*args)),
            "sharded": all(l.sharding.is_equivalent_to(NamedSharding(mesh, P("x")), l.ndim)
                           for l in jax.tree.leaves(got)),
        }
print("RESULTS" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    text = run_with_devices(_CASES % {"mesh_sizes": MESH_SIZES, "cases": CASES}, n=4)
    line = [ln for ln in text.splitlines() if ln.startswith("RESULTS")][-1]
    return json.loads(line[len("RESULTS"):])


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("P_", MESH_SIZES)
def test_compact_slabs_matches_numpy_and_the_gather(results, P_, case):
    """Bit-identical to numpy's ``slab[valid]`` and to the gather it
    replaced, sharded on the axis; ``sort_kv(mesh=...)`` equals a stable
    argsort of the keys, payload leaves included."""
    got = results[f"{P_}/{case}"]
    assert got == {k: True for k in got}, got
