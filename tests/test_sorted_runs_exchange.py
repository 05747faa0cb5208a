"""The keys-only model-D exchange that slices sorted runs.

``cluster_sort`` sorts each shard once and ships every bucket as a slice of
the sorted shard (``sorted_runs_exchange``); key-value sorts and MoE keep
``partition_exchange``'s argsort and scatter.  Every case here is checked
against ``partition_exchange`` on the same keys and bucket ids, and against
``np.sort``.  The mesh cases share one subprocess with 4 forced host devices
(device count is fixed at the first jax import), whose results the
parametrised cases read.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.exchange import bucket_counts

MODES = ["decimal", "range", "radix", "splitters", "sample"]
INPUTS = ["uniform", "zipf", "all_equal", "heavy_duplicate", "tiny_capacity",
          "float_specials", "float_no_nan"]

_CASES = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.cluster_sort import cluster_sort
from repro.core.radix import make_partitioner
from repro.core.seqsort import fast_local_sort
from repro.exchange import (bucket_counts, partition_exchange, slab_geometry,
                            sorted_runs_exchange)

P_, n = 4, 4096
m = n // P_
mesh = jax.make_mesh((P_,), ("x",))
MODES = %(modes)r
INPUTS = %(inputs)r
KW = {"decimal": dict(digits=3), "range": dict(lo=0, hi=1000)}


def keys_for(name, seed):
    rng = np.random.default_rng(seed)
    if name in ("uniform", "tiny_capacity"):
        return rng.integers(0, 1000, n).astype(np.int32)
    if name == "zipf":
        w = 1.0 / np.arange(1, 1001) ** 0.99
        return rng.choice(1000, n, p=w / w.sum()).astype(np.int32)
    if name == "all_equal":
        return np.full(n, 500, np.int32)
    if name == "heavy_duplicate":
        return rng.choice([3, 500, 997], n, p=[0.1, 0.8, 0.1]).astype(np.int32)
    x = rng.uniform(0, 1000, n).astype(np.float32)
    specials = np.array([0.0, -0.0, np.inf, -np.inf, np.nan], np.float32)
    if name == "float_no_nan":
        specials = specials[:4]
    at = rng.choice(n, 200, replace=False)
    x[at] = specials[np.arange(200) %% len(specials)]
    return x


def matches(got, want):
    return bool(got.shape == want.shape and np.array_equal(got, want, equal_nan=True))


out = {}
for mode in MODES:
    kw = KW.get(mode, {})
    for name in INPUTS:
        x = keys_for(name, 10 * MODES.index(mode) + INPUTS.index(name))
        cf = 0.3 if name == "tiny_capacity" else 2.0
        part_b, B, cap = slab_geometry(mode, m, P_, cf)
        part = make_partitioner(mode, n_buckets=part_b, axis_name="x",
                                digits=kw.get("digits", 3), lo=kw.get("lo", 0),
                                hi=kw.get("hi", 1))

        def body(local, C):
            bucket = part(local).astype(jnp.int32)
            old = partition_exchange(local, None, bucket, "x", capacity=C, n_buckets=B)
            srt = jnp.sort(local)
            counts = bucket_counts(part(local, sorted_keys=srt).astype(jnp.int32), B)
            recv, counts, ovf = sorted_runs_exchange(srt, counts, "x", capacity=C)
            # what this shard received, by (sender, local bucket, slot)
            shape = (1, P_, B // P_, C)
            return (old.recv_keys.reshape(shape), old.counts[None], old.overflow,
                    recv.reshape(shape), counts[None], ovf)

        def run(C):
            f = jax.jit(jax.shard_map(lambda l: body(l, C), mesh=mesh, in_specs=P("x"),
                                      out_specs=(P("x"), P("x"), P(), P("x"), P("x"), P())))
            return [np.asarray(a) for a in f(jnp.asarray(x))]

        _, old_c, old_o, _, new_c, new_o = run(cap)
        old_r, full_c, _, new_r, _, _ = run(m)
        segments = True
        for s in range(P_):
            for b in range(B):
                r, lb, k = b * P_ // B, b %% (B // P_), int(full_c[s, b])
                a = np.sort(old_r[r, s, lb, :k])
                z = np.sort(new_r[r, s, lb, :k])
                segments &= matches(a, z)

        def old_sort(local):  # the scatter path, loss-free, as the sort ran before
            bucket = part(local).astype(jnp.int32)
            ex = partition_exchange(local, None, bucket, "x", capacity=m, n_buckets=B)
            owner = (jnp.arange(B) * P_) // B
            mine = jnp.sum(jnp.where(owner == jax.lax.axis_index("x"),
                                     jax.lax.psum(ex.counts, "x"), 0))
            return fast_local_sort(ex.recv_keys.reshape(-1)), mine[None]

        slab, cnt = jax.jit(jax.shard_map(old_sort, mesh=mesh, in_specs=P("x"),
                                          out_specs=(P("x"), P("x"))))(jnp.asarray(x))
        slab, cnt = np.asarray(slab).reshape(P_, -1), np.asarray(cnt)
        old_out = np.concatenate([slab[p, :cnt[p]] for p in range(P_)])
        seen = []
        new_slab, valid = cluster_sort(jnp.asarray(x), mesh, "x", mode=mode,
                                       capacity_factor=cf, max_retries=8,
                                       telemetry=lambda **t: seen.append(t), **kw)
        want = np.sort(x)
        out[mode + "-" + name] = dict(
            counts_equal=bool(np.array_equal(old_c, new_c)),
            overflow_equal=bool(old_o == new_o),
            overflowed=bool(old_o),
            segments_equal=bool(segments),
            old_matches=matches(old_out, want),
            new_matches=matches(np.asarray(new_slab)[np.asarray(valid)], want),
            retries=seen[-1]["retries"],
            path=seen[-1]["path"],
        )
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    out = run_with_devices(_CASES % {"modes": MODES, "inputs": INPUTS}, n=4)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("inputs", INPUTS)
@pytest.mark.parametrize("mode", MODES)
def test_sorted_runs_exchange_matches_scatter_path(results, mode, inputs):
    """Counts and overflow equal ``partition_exchange``'s; each (sender,
    bucket) segment holds the same keys; ``cluster_sort`` equals ``np.sort``
    wherever the scatter path did (every integer input), through retries."""
    r = results[f"{mode}-{inputs}"]
    assert r["counts_equal"] and r["overflow_equal"], r
    assert r["path"] == "sorted_runs", r
    if inputs == "tiny_capacity":
        assert r["overflowed"] and r["retries"] >= 1, r
    if not inputs.startswith("float"):
        assert r["old_matches"], r
    if r["old_matches"]:
        assert r["new_matches"] and r["segments_equal"], r


def test_bucket_counts_equals_bincount(rng):
    ids = rng.integers(0, 12, 5000).astype(np.int32)
    got = np.asarray(bucket_counts(jnp.asarray(ids), 12))
    assert np.array_equal(got, np.bincount(ids, minlength=12))


def test_telemetry_path_counter_names_each_exchange(debug_mesh, key):
    """Keys-only ``cluster_sort`` reports ``"sorted_runs"`` on every call;
    ``cluster_sort_kv`` and MoE dispatch report ``"scatter"``."""
    import jax

    from repro.core.cluster_sort import cluster_sort
    from repro.engine import Planner
    from repro.engine.planner import plan_key
    from repro.engine.kv import cluster_sort_kv
    from repro.models.moe import MoEConfig, moe_apply_adaptive, moe_init

    planner = Planner()
    x = jnp.asarray(np.random.default_rng(1).integers(0, 1000, 256), jnp.int32)
    rec = planner.recorder(256, jnp.int32, debug_mesh)
    for mode in ("splitters", "sample", "radix"):
        slab, valid = cluster_sort(x, debug_mesh, "x", mode=mode, telemetry=rec)
        assert np.array_equal(np.asarray(slab)[np.asarray(valid)], np.sort(np.asarray(x)))
    assert planner.telemetry.path_calls == {"sorted_runs": 3}

    cluster_sort_kv(x, jnp.arange(256), debug_mesh, "x", telemetry=rec)
    assert planner.telemetry.path_calls == {"sorted_runs": 3, "scatter": 1}
    assert planner.telemetry.last(plan_key(256, jnp.int32, debug_mesh)).path == "scatter"

    cfg = MoEConfig(d_model=16, d_ff=8, n_experts=4, top_k=1, capacity_factor=2.0)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    moe_apply_adaptive(p, cfg, jax.random.normal(key, (32, 16)), planner=planner)
    assert planner.telemetry.path_calls == {"sorted_runs": 3, "scatter": 2}
