"""Compile the chip's hot path for a described TPU v5e, with no chip attached.

The Pallas kernels at every tile width the planner sweeps, and the model-D
cluster sort and its dense compaction on a 4-chip mesh, and the one-chip
record sort by a multi-word key, go through the TPU compiler here: a kernel Mosaic refuses, a program that does not fit HBM or a
lost collective fails these tests instead of a chip run. Nothing runs, so
they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from conftest import ops_under
from repro.core.cluster_sort import _compiled_cluster_sort
from repro.engine.planner import PALLAS_BLOCK_SWEEP
from repro.exchange import slab_geometry
from repro.exchange.slabs import _compiled_compact
from repro.kernels.bitonic_sort.ops import _pallas_argsort_impl, _pallas_sort_impl

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chip_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("x",))


@pytest.mark.parametrize("block_n", PALLAS_BLOCK_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32], ids=["int32", "float32"])
@pytest.mark.parametrize("impl", [_pallas_sort_impl, _pallas_argsort_impl], ids=["sort", "argsort"])
def test_pallas_kernel_compiles_for_v5e(one_chip, impl, dtype, block_n):
    x = jax.ShapeDtypeStruct((1 << 20,), dtype, sharding=one_chip)
    compiled = impl.lower(x, block_n=block_n, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


CLUSTER_N = 1 << 26


@pytest.fixture(scope="module")
def compiled_cluster(four_chip_mesh):
    """The four-chip model-D program at ``CLUSTER_N`` keys, compiled once per mode."""
    done = {}

    def compile_mode(mode):
        if mode not in done:
            P_ = 4
            part_buckets, n_buckets, cap = slab_geometry(mode, CLUSTER_N // P_, P_, 2.0)
            fn = _compiled_cluster_sort(
                four_chip_mesh, "x", mode, cap, part_buckets, n_buckets, 3, 0, 1, "xla", None
            )
            x = jax.ShapeDtypeStruct(
                (CLUSTER_N,), jnp.int32, sharding=NamedSharding(four_chip_mesh, P("x"))
            )
            done[mode] = fn.lower(x).compile()
        return done[mode]

    return compile_mode


@pytest.mark.parametrize("mode", ["sample", "radix"])
def test_cluster_sort_compiles_for_four_chips(compiled_cluster, mode):
    compiled = compiled_cluster(mode)
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_device = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert 0 < per_device < HBM_BYTES, per_device


@pytest.mark.parametrize("mode,max_sorts", [("splitters", 1), ("sample", 2)])
def test_cluster_sort_partition_is_gather_free(compiled_cluster, mode, max_sorts):
    """The keys-only partition sorts the shard and slices it: no gather and
    no scatter under ``repro.partition``, and at most one sort of a shard's
    m keys (two in sample mode, whose composite splitters sort (key, id))."""
    ops = ops_under(compiled_cluster(mode).as_text(), "repro.partition")
    assert ops, "no instruction carries the repro.partition scope"
    assert not [o for o in ops if o[0] in ("gather", "scatter")], ops
    m = CLUSTER_N // 4
    sorts = [o for o in ops if o[0] == "sort" and f"[{m}]" in o[1]]
    assert len(sorts) <= max_sorts, sorts


def test_compaction_is_gather_free_and_fits_hbm(four_chip_mesh):
    """The dense compaction at ``sort.zipf.4chip``'s shapes (n = 2^28 keys
    from a 2^29-slot slab, 2^27 slots a chip) builds each chip's range from
    slices of the gathered slab: no gather and no scatter under
    ``repro.compact``, and the program fits one chip's HBM."""
    n, total = 1 << 28, 1 << 29
    sharding = NamedSharding(four_chip_mesh, P("x"))
    slab = jax.ShapeDtypeStruct((total,), jnp.int32, sharding=sharding)
    valid = jax.ShapeDtypeStruct((total,), jnp.bool_, sharding=sharding)
    compiled = _compiled_compact(four_chip_mesh, "x", n).lower(slab, valid).compile()
    ops = ops_under(compiled.as_text(), "repro.compact")
    assert ops, "no instruction carries the repro.compact scope"
    assert not [o for o in ops if o[0] in ("gather", "scatter")], ops
    assert [o for o in ops if o[0] == "dynamic-slice"], ops
    mem = compiled.memory_analysis()
    per_device = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert 0 < per_device < HBM_BYTES, per_device


def test_multi_word_record_sort_programs_compile_for_v5e(one_chip):
    """The multi-word record sort's programs for a v5e: the order's sort
    leads with the uint32 key word, the inverse's, one word's and a group's
    (the rank and ``_CARRY_WORDS`` words) with the int32 rank, so a trace
    that names operations by kind still tells ``repro.kv_order`` from
    ``repro.kv_permute``."""
    import re

    from repro.engine.kv import _CARRY_WORDS, _by_rank, _inverse, _word_order

    n = 1 << 12
    u32 = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    s32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    for compiled, first in [
        (_word_order.lower((u32, u32, u32), ascending=True).compile(), "u32"),
        (_inverse.lower(s32).compile(), "s32"),
        (_by_rank.lower(s32, u32).compile(), "s32"),
        (_by_rank.lower(s32, *[u32] * _CARRY_WORDS).compile(), "s32"),
    ]:
        sorts = re.findall(r"= \((\w+)\[\d+\][^ ]*,.*? sort\(", compiled.as_text())
        assert sorts == [first]
        assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
