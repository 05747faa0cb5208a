"""Pallas TPU kernels for the bitonic sort network (VMEM-tiled).

Decomposition (see ref.py): the canonical n-element network is split so that
every O(log^2 block_n) "local" substage runs inside VMEM, and only the
O(log^2 (n/block_n)) cross-block substages touch HBM between kernel launches.
Every compare-exchange is a branch-free select on VREG lanes (VPU work; the
MXU is idle by design — sorting is a bandwidth problem).

Kernels:
  A  block_sort    per-block full network, direction alternating by block
                   parity
  B  block_merge   all substages j < block_n of one merge stage k in a single
                   VMEM pass (the perf-critical fusion: log2(bn) HBM
                   round-trips collapse into one)
  C  cross-block substages j >= block_n: one elementwise compare-exchange over
     block pairs, expressed at the jnp level (pure bandwidth, no reuse to
     exploit — XLA emits the optimal elementwise kernel for it).

A and B share one kernel body, ``_network_kernel``; each also has a ``*_kv``
twin that carries an int32 rank array through the same network with a
lexicographic (key, rank) comparator.  Ranks start as iota, ranks never tie,
so the comparator is a total order and the rank output is the *stable*
sorting permutation — that one permutation is what ``ops.pallas_argsort`` /
``ops.pallas_sort_kv`` gather arbitrary value payloads with.

TPU layout: the 1-D input is viewed as rows of 128 lanes, and each program
owns a ``(rows, 128)`` tile of ``max(block_n, TILE)`` elements (the whole
input when it is shorter), so a program holds whole (8, 128) vregs even when
``block_n`` is smaller: it then runs several blocks side by side.  Element ``p`` of the flat
array sits at row ``p // 128``, lane ``p % 128``.  The partner at distance
``j`` is fetched without any in-kernel reshape: for ``j < 128`` with a lane
roll, for ``j >= 128`` with a sublane roll of ``j / 128`` rows, and the
element's own position bit ``p & j`` picks which of the two rolls holds it.
Mosaic rejects the shape casts a ``(g, 2, j)`` reshape of a 1-D block needs,
which is why the network is written this way.

Comparator caveat (shared with the pure-jnp network in core/bitonic.py): the
compare-exchange uses ``>``, under which NaN compares false everywhere — NaN
keys make the network's output unspecified. Callers that must reject NaN do
so at the boundary (e.g. SortService); XLA's own sort is the NaN-safe path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitonic import _compare_exchange

LANES = 128
# elements per program: one (8, 128) tile of 32-bit vregs
TILE = 8 * LANES


def _tile_shape(n: int, block_n: int):
    """(rows, lanes) of one program's tile for an n-element 1-D input."""
    tile = min(n, max(block_n, TILE))
    lanes = min(tile, LANES)
    return tile // lanes, lanes


def _partner(x, j: int, pos):
    """Value at flat position ``pos ^ j`` for every element of a 2-D tile.

    ``roll(x, s)[i] == x[i - s]`` along the axis, so the element whose bit
    ``j`` is clear reads its partner from the roll by ``size - d`` and the one
    whose bit is set from the roll by ``d``.
    """
    rows, lanes = x.shape
    axis, d, size = (1, j, lanes) if j < lanes else (0, j // lanes, rows)
    ahead = pltpu.roll(x, size - d, axis)
    behind = pltpu.roll(x, d, axis)
    return jnp.where((pos & j) == 0, ahead, behind)


def _network_kernel(*refs, block_n: int, k: int | None, kv: bool):
    """Kernel A (``k is None``) or kernel B (merge stage ``k``) on one tile.

    Kernel A sorts every aligned block of ``block_n`` elements, ascending iff
    the block index is even. Kernel B runs substages j = block_n/2 .. 1 of
    stage ``k > block_n``, whose direction is uniform inside a block: up iff
    ``(pos & k) == 0``.
    """
    if kv:
        x_ref, r_ref, ox_ref, or_ref = refs
        r = r_ref[...]
    else:
        x_ref, ox_ref = refs
    x = x_ref[...]
    rows, lanes = x.shape
    pos = (
        pl.program_id(0) * (rows * lanes)
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * lanes
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    )
    log_bn = block_n.bit_length() - 1
    if k is None:  # kernel A: every stage of the network, inside each block
        block_up = (pos & block_n) == 0
        stages = [(1 << s, 1 << t) for s in range(1, log_bn + 1) for t in reversed(range(s))]
    else:  # kernel B: the in-block substages of merge stage k
        stages = [(k, 1 << t) for t in reversed(range(log_bn))]
    for kk, j in stages:
        if k is None:
            # the block's last stage (kk == block_n) runs in the block's own
            # direction; earlier ones alternate with bit kk of the position
            up = ((pos & kk & (block_n - 1)) == 0) == block_up
        else:
            up = (pos & kk) == 0
        lower = (pos & j) == 0
        p = _partner(x, j, pos)
        a = jnp.where(lower, x, p)  # key of the pair's lower position
        b = jnp.where(lower, p, x)
        gt = a > b
        if kv:
            rp = _partner(r, j, pos)
            ra = jnp.where(lower, r, rp)
            rb = jnp.where(lower, rp, r)
            gt = gt | ((a == b) & (ra > rb))
        swap = gt == up
        x = jnp.where(swap, p, x)
        if kv:
            r = jnp.where(swap, rp, r)
    ox_ref[...] = x
    if kv:
        or_ref[...] = r


def _launch(arrays, block_n: int, k: int | None, interpret: bool):
    n = arrays[0].shape[-1]
    rows, lanes = _tile_shape(n, block_n)
    spec = pl.BlockSpec((rows, lanes), lambda b: (b, 0))
    tiled = [a.reshape(n // lanes, lanes) for a in arrays]
    out = pl.pallas_call(
        functools.partial(_network_kernel, block_n=block_n, k=k, kv=len(arrays) == 2),
        grid=(n // (rows * lanes),),
        in_specs=[spec] * len(arrays),
        out_specs=[spec] * len(arrays),
        # vma: inside shard_map the outputs vary over the same mesh axes
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype, vma=jax.typeof(a).vma) for a in tiled
        ],
        interpret=interpret,
    )(*tiled)
    return [o.reshape(n) for o in out]


def block_sort(x: jax.Array, block_n: int, *, interpret: bool) -> jax.Array:
    """Launch kernel A over all aligned blocks of the last axis (1-D x)."""
    return _launch([x], block_n, None, interpret)[0]


def block_merge(x: jax.Array, block_n: int, k: int, *, interpret: bool) -> jax.Array:
    """Launch kernel B (fused local substages of stage k) over all blocks."""
    return _launch([x], block_n, k, interpret)[0]


def block_sort_kv(x: jax.Array, r: jax.Array, block_n: int, *, interpret: bool):
    """Launch kernel A (kv twin): returns (keys, ranks) per-block sorted."""
    return tuple(_launch([x, r], block_n, None, interpret))


def block_merge_kv(x: jax.Array, r: jax.Array, block_n: int, k: int, *, interpret: bool):
    """Launch kernel B (kv twin) over all blocks."""
    return tuple(_launch([x, r], block_n, k, interpret))


def _global_dir(n: int, j: int, k: int):
    return ((jnp.arange(n // (2 * j)) * 2 * j) // k) % 2 == 0


def global_stage(x: jax.Array, j: int, k: int) -> jax.Array:
    """Cross-block substage (j >= block_n): elementwise compare-exchange.

    Pure-bandwidth step with zero data reuse; left at the jnp level where XLA
    already emits a single fused elementwise kernel (Design choice C above).
    """
    return _compare_exchange(x, None, None, j, _global_dir(x.shape[-1], j, k), ascending=True)[0]


def global_stage_kv(x: jax.Array, r: jax.Array, j: int, k: int):
    """Cross-block substage (kv twin): (key, rank) compare-exchange at jnp level."""
    x, r, _ = _compare_exchange(x, r, None, j, _global_dir(x.shape[-1], j, k), ascending=True)
    return x, r
