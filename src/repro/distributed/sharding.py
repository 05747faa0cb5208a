"""Logical-axis sharding rules -> PartitionSpecs for params / opt state / data.

Parallelism profile (DESIGN.md §5): batch over ("pod","data"); heads / experts
/ ffn-hidden over "model"; parameters 2-D sharded over ("data","model") —
FSDP×TP, XLA inserts the gathers. Optimizer moments follow their param's spec
(int8 moments are flat (nb,128) blocks -> sharded on the block axis over
"data"). Parameters are replicated across pods (grad all-reduce over "pod" is
the only DCN traffic).

Rules are name-based on the param tree paths produced by models/transformer.py;
every leaf gets a spec, unknown large leaves fail loudly.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def auto_mesh(mesh):
    """The mesh the model stack runs on: ``mesh`` with every axis Auto.

    The model pins activations with sharding constraints and leaves the rest
    to XLA's propagation, which Explicit axes (``jax.make_mesh``'s default)
    refuse. Same devices and axis names, so a caller's default mesh works.
    """
    if AxisType.Explicit not in mesh.axis_types:
        return mesh
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _path_names(kp) -> tuple:
    out = []
    for k in kp:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
    return tuple(out)


def _param_spec(names: tuple, leaf) -> P:
    nd = getattr(leaf, "ndim", 0)
    grouped = names and names[0] == "blocks"  # stacked (G, ...) leaves
    lead = (None,) if grouped else ()
    n = set(names)

    def spec(*axes):
        full = lead + tuple(axes)
        assert len(full) == nd, (names, nd, full)
        return P(*full)

    if "table" in n:  # embedding (V, D): vocab-parallel (Megatron), D replicated
        return spec("model", None)
    if "router" in n:  # (D, E) small, replicated
        return spec(*([None] * (nd - len(lead))))
    # MoE expert stacks: (E, D, F) / (E, F, D)
    if nd - len(lead) == 3 and ("w_in" in n or "w_gate" in n):
        return spec("model", "data", None)
    if nd - len(lead) == 3 and "w_out" in n:
        return spec("model", None, "data")
    if names[-1] == "w":
        parent = names[-2]
        if parent in ("wq", "wk", "wv", "w_in", "w_gate", "in_proj"):
            return spec("data", "model")
        if parent in ("wo", "w_out", "out_proj"):
            return spec("model", "data")
    if names[-1] == "b":
        parent = names[-2]
        if parent in ("wq", "wk", "wv", "w_in", "w_gate", "in_proj"):
            return spec("model")
        return spec(None)
    if "conv_w" in n:
        return spec(None, "model")
    if "conv_b" in n:
        return spec("model")
    # norms / scalars / small vectors (A_log, D_skip, dt_bias, scale)
    small = (None,) * (nd - len(lead))
    return spec(*small)


def param_specs(params) -> Any:
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: _param_spec(_path_names(kp), leaf), params
    )


def opt_state_specs(state, pspecs) -> Any:
    """Specs for the optimizer state given the param specs.

    fp32 moments / error-feedback buffers mirror the param spec; int8
    quantized moments {"q","scale"} shard their block axis over "data" (ZeRO-1
    style); count is replicated.
    """
    from repro.optim.adamw import _is_q

    def match(sub):
        return jax.tree.map(
            lambda _, s: s, sub, pspecs
        )

    out = {}
    for key, val in state.items():
        if key == "count":
            out[key] = P()
        elif key in ("m", "v"):
            def q_or_p(leaf_state, spec):
                if _is_q(leaf_state):
                    # rowwise int8: q shards exactly like its param; scale
                    # drops the last (quantized) axis
                    return {"q": spec, "scale": P(*spec[:-1])}
                return spec
            out[key] = jax.tree.map(q_or_p, val, pspecs, is_leaf=_is_q)
        else:  # err buffers
            out[key] = pspecs
    return out


def cache_specs(cache, cfg) -> Any:
    """Specs for the decode cache: batch over ("pod","data"); the *sequence*
    dim of KV caches shards over "model" (flash-decoding split-K across chips:
    XLA turns the sharded-contraction softmax into cheap partial-reduce
    all-reduces); Mamba states shard heads/channels over "model"."""
    bt = ("pod", "data")

    def one(kp, leaf):
        names = _path_names(kp)
        pos = int(names[0][3:])  # "posN"
        kind = cfg.pattern[pos]
        nd = leaf.ndim
        if kind.startswith("attn"):
            if nd == 5:  # (G, B, S, Hk, hd) k or v
                return P(None, bt, "model", None, None)
            return P(None)  # (G,) length
        if nd == 4:  # (G, B, k-1, conv_dim)
            return P(None, bt, None, "model")
        return P(None, bt, "model", None, None)  # (G, B, nh, ds, hp)

    return jax.tree_util.tree_map_with_path(one, cache)


def batch_specs(batch: dict) -> Any:
    """Input batch: leading (global batch) dim over ("pod","data")."""
    def one(leaf):
        nd = getattr(leaf, "ndim", 0)
        return P(("pod", "data"), *([None] * (nd - 1)))

    return jax.tree.map(one, batch)


def fit_spec(shape, spec: P, mesh) -> P:
    """Drop mesh axes that don't exist or don't divide the dim (B=1 decode)."""
    valid = set(mesh.axis_names)
    out = []
    for dim, a in enumerate(spec):
        if a is None:
            out.append(None)
            continue
        axes = a if isinstance(a, tuple) else (a,)
        kept, rem = [], shape[dim]
        for ax in axes:
            if ax in valid and rem % mesh.shape[ax] == 0:
                kept.append(ax)
                rem //= mesh.shape[ax]
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def to_named(tree_specs, mesh, like=None) -> Any:
    """Specs -> NamedShardings on ``auto_mesh(mesh)``; with ``like`` (shape
    tree), fit per-dim."""
    mesh = auto_mesh(mesh)
    if like is None:
        valid = set(mesh.axis_names)

        def fix(s):
            def ok(a):
                if a is None:
                    return None
                if isinstance(a, tuple):
                    kept = tuple(x for x in a if x in valid)
                    return kept if kept else None
                return a if a in valid else None

            return NamedSharding(mesh, P(*(ok(a) for a in s)))

        return jax.tree.map(fix, tree_specs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(
        lambda s, l: NamedSharding(mesh, fit_spec(l.shape, s, mesh)),
        tree_specs,
        like,
        is_leaf=lambda x: isinstance(x, P),
    )
