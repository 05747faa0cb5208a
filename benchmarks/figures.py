"""One benchmark per paper table/figure (Alghamdi & Alaghband 2020).

Honesty note (recorded in EXPERIMENTS.md): this container exposes ONE physical
core, so multi-"device"/multi-block wall-clock does not show real parallel
speedup — host devices time-share the core. What these benchmarks measure
faithfully is the *algorithmic* comparison the paper makes (hybrid vs
non-hybrid local sort, partition-first vs merge-tree data movement) on
identical hardware; the roofline analysis covers the scaling story.

Every function returns rows of (name, us_per_call, derived) for run.py's CSV.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _data(n, seed=0):
    """Paper §4.2: random 3-digit integers (100..999)."""
    return np.random.default_rng(seed).integers(100, 1000, size=n).astype(np.int32)


# ---------------------------------------------------------------- figure 5 ---
def fig5_sequential(sizes=(1_000_000, 4_000_000, 10_000_000)):
    """Sequential sorts: recursive merge vs non-recursive merge vs 'quicksort'
    (XLA sort plays the fastest-local-sort role; bitonic = the kernel network).
    Paper: quicksort 1.76x faster than recursive merge at 10M."""
    from repro.core import fast_local_sort, nonrecursive_merge_sort, recursive_merge_sort_host

    rows = []
    for n in sizes:
        x = _data(n)
        xj = jnp.asarray(x)
        t0 = time.perf_counter()
        recursive_merge_sort_host(x)
        t_rec = (time.perf_counter() - t0) * 1e6
        t_nonrec = _timeit(jax.jit(nonrecursive_merge_sort), xj)
        t_quick = _timeit(jax.jit(lambda v: fast_local_sort(v, impl="xla")), xj)
        t_bit = _timeit(jax.jit(lambda v: fast_local_sort(v, impl="bitonic")), xj)
        rows += [
            (f"fig5/recursive_merge/n={n}", t_rec, ""),
            (f"fig5/nonrecursive_merge/n={n}", t_nonrec, f"vs_rec={t_rec/t_nonrec:.2f}x"),
            (f"fig5/quicksort_role_xla/n={n}", t_quick, f"vs_rec={t_rec/t_quick:.2f}x"),
            (f"fig5/bitonic_network/n={n}", t_bit, f"vs_rec={t_rec/t_bit:.2f}x"),
        ]
    return rows


# ---------------------------------------------------------------- figure 6 ---
def fig6_shared_threads(n=4_000_000, threads=(1, 2, 4, 8, 16, 32)):
    """Shared-memory models A vs B across 'thread' (block) counts."""
    from repro.core import shared_memory_sort

    x = jnp.asarray(_data(n))
    base = _timeit(jax.jit(jnp.sort), x)
    rows = [(f"fig6/sequential_xla/n={n}", base, "speedup=1.00")]
    for t in threads:
        for impl, label in (("merge", "A_nonrec_merge"), ("xla", "B_hybrid_quick_merge")):
            us = _timeit(
                jax.jit(lambda v, tt=t, ii=impl: shared_memory_sort(v, n_threads=tt, local_impl=ii)),
                x,
            )
            rows.append((f"fig6/{label}/t={t}/n={n}", us, f"speedup={base/us:.2f}"))
    return rows


# ---------------------------------------------------------------- figure 7 ---
def fig7_vs_radix_baseline(sizes=(1_000_000, 4_000_000)):
    """Our hybrid (model B) vs the Aydin & Alaghband baseline the paper beats:
    one-step MSD-Radix into 10 buckets, then 'quicksort' per bucket.
    Paper: model B 2.55x faster at 4M / 8 threads."""
    from repro.core import shared_memory_sort
    from repro.core.radix import decimal_msd_bucket

    def radix_quick_baseline(x):
        bucket = decimal_msd_bucket(x, digits=3)
        cap = x.shape[0]  # loss-free capacity
        order = jnp.argsort(bucket, stable=True)
        xs = x[order]
        counts = jnp.bincount(bucket, length=10)
        offs = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        pos = jnp.arange(x.shape[0], dtype=jnp.int32) - offs[bucket[order]]
        slab = jnp.full((10, cap), jnp.iinfo(jnp.int32).max, jnp.int32)
        slab = slab.at[bucket[order], pos].set(xs)
        slab = jnp.sort(slab, axis=-1)  # per-bucket "quicksort"
        return slab  # concatenation of valid prefixes is the sorted array

    rows = []
    for n in sizes:
        x = jnp.asarray(_data(n))
        t_base = _timeit(jax.jit(radix_quick_baseline), x)
        t_ours = _timeit(
            jax.jit(lambda v: shared_memory_sort(v, n_threads=8, local_impl="xla")), x
        )
        rows += [
            (f"fig7/baseline_msdradix_quick/n={n}", t_base, ""),
            (f"fig7/ours_hybrid_quick_merge/n={n}", t_ours, f"ours_vs_baseline={t_base/t_ours:.2f}x"),
        ]
    return rows


# ----------------------------------------------------------- figures 8-11 ---
def _run_distributed(P, n):
    """Time sequential / model B / model C / model D on a (P,) mesh built
    in this process from the first P devices (one process drives every
    device; off-accelerator, set ``--xla_force_host_platform_device_count``
    before JAX starts, as ``benchmarks/run.py`` does)."""
    from repro.core import cluster_sort, distributed_merge_sort, shared_memory_sort

    if jax.device_count() < P:
        raise ValueError(f"a P={P} mesh needs {P} devices, this process has {jax.device_count()}")
    mesh = jax.make_mesh((P,), ("x",), devices=jax.devices()[:P])
    x = jnp.asarray(_data(n))

    return [
        _timeit(lambda: jnp.sort(x)),
        _timeit(lambda: shared_memory_sort(x, n_threads=4, local_impl="xla")),
        _timeit(lambda: distributed_merge_sort(x, mesh, "x")),
        _timeit(lambda: cluster_sort(x, mesh, "x", mode="range", lo=100, hi=1000,
                                     capacity_factor=1.5)[0]),
    ]


def fig8_distributed(n=1_000_000, P=4):
    """Model C (distributed merge tree) vs shared-memory B vs sequential."""
    t_seq, t_shared, t_c, t_d = _run_distributed(P, n)
    return [
        (f"fig8/sequential/n={n}", t_seq, "speedup=1.00"),
        (f"fig8/B_shared_hybrid/t=4/n={n}", t_shared, f"speedup={t_seq/t_shared:.2f}"),
        (f"fig8/C_distributed_merge/P={P}/n={n}", t_c, f"speedup={t_seq/t_c:.2f}"),
        (f"fig8/D_cluster/P={P}/n={n}", t_d, f"speedup={t_seq/t_d:.2f}"),
    ]


def fig9_11_cluster_scaling(sizes=(400_000, 1_000_000, 4_000_000), Ps=(2, 8)):
    """Model D across data sizes and 'node' counts (paper figs 9-11: D's
    speedup grows with size; more nodes win only past ~4M)."""
    rows = []
    for n in sizes:
        for P in Ps:
            t_seq, _, t_c, t_d = _run_distributed(P, n)
            rows.append(
                (f"fig9_11/D_cluster/P={P}/n={n}", t_d,
                 f"speedup={t_seq/t_d:.2f};C_speedup={t_seq/t_c:.2f}")
            )
    return rows
