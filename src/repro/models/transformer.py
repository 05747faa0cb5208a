"""Config-driven decoder stack: dense / MoE / SSM / hybrid, one code path.

The layer stack is a ``lax.scan`` over *pattern groups*: ``cfg.pattern`` is a
period (e.g. ``("attn_g",)*5 + ("attn_l",)`` for gemma3, ``("attn",) +
("mamba",)*7`` for jamba) and parameters are stacked with a leading
``n_layers/len(pattern)`` group axis. Scan keeps the HLO O(1) in depth — that
is what makes 512-way SPMD compiles of 72-layer/398B configs tractable
(DESIGN.md §5) — and ``jax.checkpoint`` around the group body gives the remat
policy a natural boundary.

Block kinds:
  attn    full/global causal attention (+MoE or dense FFN)
  attn_l  sliding-window local attention
  mamba   Mamba-2 SSD (no FFN pairing unless cfg says so — Jamba pairs FFN)
Every block is pre-norm residual: x += Block(RMSNorm(x)); FFN likewise.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (
    AttnConfig,
    KVCache,
    attention_decode,
    attention_train,
    attn_init,
    init_kv_cache,
)
from .layers import Params, embed, embed_init, mlp, mlp_init, rmsnorm, rmsnorm_init, unembed
from .mamba2 import (
    MambaCache,
    MambaConfig,
    init_mamba_cache,
    mamba_decode,
    mamba_init,
    mamba_train,
)
from .moe import (
    MoEConfig,
    moe_apply_ep_replicated,
    moe_apply_local,
    moe_init,
    moe_shard_specs,
)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # layer pattern (period); "attn" | "attn_l" | "mamba"
    pattern: Tuple[str, ...] = ("attn",)
    # which positions in the period carry an FFN ("dense" | "moe" | None)
    ffn_pattern: Tuple[Optional[str], ...] = ("dense",)
    mlp_gated: bool = True
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_local: float = 10_000.0
    sliding_window: int = 0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 2.0
    compress_dispatch: bool = False   # int8 MoE a2a payloads
    # SSM
    ssm_state: int = 128
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    # modality frontend stub ("none" | "vision" | "audio")
    frontend: str = "none"
    n_frontend_tokens: int = 0
    # numerics
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    kv_chunk: int = 1024
    # remat: "dots" saves dot outputs (fast, more memory); "none" recomputes
    # everything per layer group (the giants: activation stash dominates)
    remat_policy: str = "dots"
    # notes for DESIGN/EXPERIMENTS (e.g. technique applicability)
    notes: str = ""

    @property
    def n_groups(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, (self.n_layers, self.pattern)
        return self.n_layers // len(self.pattern)

    def attn_cfg(self, kind: str) -> AttnConfig:
        local = kind == "attn_l"
        return AttnConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            qkv_bias=self.qkv_bias,
            qk_norm=self.qk_norm,
            rope_theta=self.rope_theta_local if local else self.rope_theta,
            sliding_window=self.sliding_window if local else 0,
            kv_chunk=self.kv_chunk,
        )

    def mamba_cfg(self) -> MambaConfig:
        return MambaConfig(
            d_model=self.d_model,
            d_state=self.ssm_state,
            head_dim=self.ssm_head_dim,
            chunk=self.ssm_chunk,
        )

    def moe_cfg(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model,
            d_ff=self.d_ff,
            n_experts=self.n_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            mlp_gated=self.mlp_gated,
            compress_dispatch=self.compress_dispatch,
        )

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + stacked blocks)."""
        D, F = self.d_model, self.d_ff
        per_period = 0
        for kind, ffn in zip(self.pattern, self.ffn_pattern):
            if kind.startswith("attn"):
                per_period += D * self.head_dim * (self.n_heads + 2 * self.n_kv_heads)
                per_period += self.n_heads * self.head_dim * D
            else:
                mc = self.mamba_cfg()
                per_period += D * (2 * mc.d_inner + 2 * mc.n_groups * mc.d_state + mc.n_heads)
                per_period += mc.d_inner * D + mc.conv_kernel * mc.conv_dim
            if ffn == "dense":
                per_period += D * F * (3 if self.mlp_gated else 2)
            elif ffn == "moe":
                per_period += self.n_experts * D * F * (3 if self.mlp_gated else 2)
                per_period += D * self.n_experts
        return self.vocab_size * D + per_period * self.n_groups

    def active_param_count(self) -> int:
        """Per-token active params (MoE counts top_k experts only)."""
        D, F = self.d_model, self.d_ff
        total = self.vocab_size * D
        per_period = 0
        for kind, ffn in zip(self.pattern, self.ffn_pattern):
            if kind.startswith("attn"):
                per_period += D * self.head_dim * (self.n_heads + 2 * self.n_kv_heads)
                per_period += self.n_heads * self.head_dim * D
            else:
                mc = self.mamba_cfg()
                per_period += D * (2 * mc.d_inner + 2 * mc.n_groups * mc.d_state + mc.n_heads)
                per_period += mc.d_inner * D + mc.conv_kernel * mc.conv_dim
            if ffn == "dense":
                per_period += D * F * (3 if self.mlp_gated else 2)
            elif ffn == "moe":
                per_period += self.top_k * D * F * (3 if self.mlp_gated else 2)
                per_period += D * self.n_experts
        return total + per_period * self.n_groups


def embed_tokens(p_embed: Params, tokens: jax.Array, cfg, ctx) -> jax.Array:
    """Vocab-parallel embedding lookup (Megatron-style).

    The table is sharded (V -> ep_axis, D replicated); each shard gathers its
    own vocab range with a mask and the results psum over the EP axis. XLA's
    generic sharded-gather falls back to full rematerialization ("Involuntary
    full rematerialization" — refuted hypothesis H-embed, EXPERIMENTS §Perf),
    so the pattern is expressed explicitly with shard_map.
    """
    if ctx is None or ctx.mesh is None:
        return embed(p_embed, tokens, cfg.compute_dtype)
    from jax.sharding import PartitionSpec as P

    bt = ctx.pick_batch_axes(tokens.shape[0])

    def body(tbl, tok):
        vloc = tbl.shape[0]
        lo = jax.lax.axis_index(ctx.ep_axis) * vloc
        rel = tok - lo
        ok = (rel >= 0) & (rel < vloc)
        out = jnp.where(
            ok[..., None],
            tbl.astype(cfg.compute_dtype)[jnp.clip(rel, 0, vloc - 1)],
            jnp.zeros((), cfg.compute_dtype),
        )
        return jax.lax.psum(out, ctx.ep_axis)

    return jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(P(ctx.ep_axis, None), P(bt)),
        out_specs=P(bt),
        check_vma=False,
    )(p_embed["table"], tokens)


# ------------------------------------------------------------------ init ---


def _block_init(key, cfg: ModelConfig, kind: str, ffn: Optional[str], ep_shards: int) -> Params:
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    p: Params = {"norm1": rmsnorm_init(cfg.d_model, dt)}
    if kind.startswith("attn"):
        p["attn"] = attn_init(ks[0], cfg.attn_cfg(kind), dt)
    else:
        p["mamba"] = mamba_init(ks[0], cfg.mamba_cfg(), dt)
    if ffn is not None:
        p["norm2"] = rmsnorm_init(cfg.d_model, dt)
        if ffn == "dense":
            p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, dt, gated=cfg.mlp_gated)
        else:
            p["moe"] = moe_init(ks[1], cfg.moe_cfg(), dt, ep_shards=ep_shards)
    return p


def padded_vocab(cfg: ModelConfig, ep_shards: int) -> int:
    """Vocab rows padded to the EP-shard multiple (vocab-parallel table)."""
    return math.ceil(cfg.vocab_size / ep_shards) * ep_shards


def model_init(key, cfg: ModelConfig, *, ep_shards: int = 1) -> Params:
    """Init full parameter pytree; block params stacked over the group axis."""
    k_embed, k_blocks, k_final = jax.random.split(key, 3)

    def one_group(k):
        ks = jax.random.split(k, len(cfg.pattern))
        return {
            f"pos{i}": _block_init(ks[i], cfg, kind, ffn, ep_shards)
            for i, (kind, ffn) in enumerate(zip(cfg.pattern, cfg.ffn_pattern))
        }

    group_keys = jax.random.split(k_blocks, cfg.n_groups)
    blocks = jax.vmap(one_group)(group_keys)  # leading axis = groups
    return {
        "embed": embed_init(
            k_embed, padded_vocab(cfg, ep_shards), cfg.d_model, cfg.param_dtype
        ),
        "blocks": blocks,
        "final_norm": rmsnorm_init(cfg.d_model, cfg.param_dtype),
    }


# --------------------------------------------------------------- forward ---


@dataclass(frozen=True)
class ShardCtx:
    """How the model parallelizes. mesh=None -> single-device (smoke tests)."""
    mesh: Any = None
    axes: Tuple[str, ...] = ()      # all mesh axis names, batch shards over them
    ep_axis: str = "model"

    def __post_init__(self):
        if self.mesh is not None:
            from repro.distributed.sharding import auto_mesh

            object.__setattr__(self, "mesh", auto_mesh(self.mesh))

    @property
    def ep_shards(self) -> int:
        return 1 if self.mesh is None else self.mesh.shape[self.ep_axis]

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axes if a != self.ep_axis)

    def pick_batch_axes(self, n: int) -> Tuple[str, ...]:
        """Largest prefix of batch axes whose sizes divide ``n`` (tiny decode
        batches can't use every axis)."""
        axes, rem = [], n
        for a in self.batch_axes:
            sz = self.mesh.shape[a]
            if rem % sz == 0:
                axes.append(a)
                rem //= sz
        return tuple(axes)

    def constrain_batch(self, x: jax.Array) -> jax.Array:
        """Pin dim0 of an activation to the batch axes (scan-carry anchor)."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.batch_axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def constrain_spec(self, x: jax.Array, *axes, allow_uneven: bool = False) -> jax.Array:
        """Pin an activation: entries are "batch", a mesh axis name, or None.

        Non-dividing named dims are dropped unless ``allow_uneven`` (SPMD
        handles padded tilings — needed for 28 heads on a 16-way model axis).
        """
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = []
        for dim, a in enumerate(axes):
            if a == "batch":
                a = self.batch_axes
            if isinstance(a, str):
                if x.shape[dim] % self.mesh.shape[a] and not allow_uneven:
                    a = None
            spec.append(a)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, P(*spec)))


def _apply_ffn(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    ctx: ShardCtx,
    stats: dict,
    *,
    decode=False,
    moe_capacity: Optional[int] = None,
    moe_stats: bool = False,
):
    """``moe_capacity`` overrides the per-(sender, expert) token capacity
    (static — the train loop's capacity controller threads the learned value
    through here, so a bump recompiles once).  ``moe_stats=True`` adds
    ``moe_dropped``/``moe_peak`` to the stats dict — the exchange-telemetry
    signal the between-step learner and AnomalyMonitor read."""
    h = rmsnorm(p["norm2"], x)
    if "ffn" in p:
        return x + mlp(p["ffn"], h), stats
    B, S, D = h.shape
    if ctx.mesh is not None and not decode:
        # sequence-parallel hand-off: (B->batch, S->model) makes the (B*S, D)
        # token flatten a local view of the full-mesh token sharding the MoE
        # shard_map wants; without it SPMD "involuntarily rematerializes" the
        # residual stream (8 GiB/device f32 on jamba — hypothesis H-sp1)
        h = ctx.constrain_spec(h, "batch", ctx.ep_axis, None)
    flat = h.reshape(B * S, D)
    mcfg = cfg.moe_cfg()
    dropped = peak = None
    if ctx.mesh is None:
        if moe_stats:
            y, aux, dropped, _, peak, overflow = moe_apply_ep_replicated(
                p["moe"], mcfg, flat, capacity=moe_capacity, with_stats=True
            )
        else:
            y, aux, overflow = moe_apply_ep_replicated(
                p["moe"], mcfg, flat, capacity=moe_capacity
            )
    elif decode:
        # decode: tokens replicated over EP axis, psum-combined (moe.py doc).
        # Tiny decode batches may not divide the data axes (long_500k B=1):
        # shard tokens only over axes whose size divides the token count.
        from jax.sharding import PartitionSpec as P

        token_axes = []
        rem = flat.shape[0]
        for a in ctx.axes:
            if a == ctx.ep_axis:
                continue
            sz = ctx.mesh.shape[a]
            if rem % sz == 0:
                token_axes.append(a)
                rem //= sz
        token_axes = tuple(token_axes)
        (p_spec, _), _ = moe_shard_specs(p["moe"], mesh_axes=ctx.axes, ep_axis=ctx.ep_axis)

        def body(mp, xt):
            return moe_apply_ep_replicated(mp, mcfg, xt, ctx.ep_axis, ctx.axes)

        y, aux, overflow = jax.shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(p_spec, P(token_axes)),
            out_specs=(P(token_axes), P(), P()),
            check_vma=False,
        )(p["moe"], flat)
    else:
        # train/prefill: the paper's model-D all_to_all dispatch
        (p_spec, x_spec), out_specs = moe_shard_specs(
            p["moe"], mesh_axes=ctx.axes, ep_axis=ctx.ep_axis, with_stats=moe_stats
        )

        def body(mp, xt):
            res = moe_apply_local(
                mp, mcfg, xt, ctx.ep_axis, ctx.axes,
                capacity=moe_capacity, with_stats=moe_stats,
            )
            if not moe_stats:
                return res
            out, aux, dropped, counts, peak, overflow = res
            rest = tuple(a for a in ctx.axes if a != ctx.ep_axis)
            if rest:  # stats are EP-group-global; fold in the other axes
                dropped = jax.lax.psum(dropped, rest)
                counts = jax.lax.psum(counts, rest)
                peak = jax.lax.pmax(peak, rest)
            return out, aux, dropped, counts, peak, overflow

        res = jax.shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(p_spec, x_spec),
            out_specs=out_specs,
            check_vma=False,
        )(p["moe"], flat)
        if moe_stats:
            y, aux, dropped, _, peak, overflow = res
        else:
            y, aux, overflow = res
    stats = dict(stats)
    stats["moe_aux"] = stats.get("moe_aux", 0.0) + aux
    stats["moe_overflow"] = jnp.logical_or(
        stats.get("moe_overflow", jnp.asarray(False)), overflow
    )
    if moe_stats:
        # layer totals: tokens lost this step sum over layers, the hottest
        # per-(sender, expert) count maxes — what the capacity learner reads
        stats["moe_dropped"] = stats.get("moe_dropped", 0) + dropped
        stats["moe_peak"] = jnp.maximum(stats.get("moe_peak", 0), peak)
    y = y.reshape(B, S, D)
    if ctx.mesh is not None and not decode:
        y = ctx.constrain_spec(y, "batch", ctx.ep_axis, None)
    return x + y, stats


def _apply_block(
    p: Params, cfg: ModelConfig, kind: str, ffn, x, ctx, stats,
    *, moe_capacity: Optional[int] = None, moe_stats: bool = False,
):
    h = rmsnorm(p["norm1"], x)
    pin = ctx.constrain_spec if ctx.mesh is not None else None
    if kind.startswith("attn"):
        # head pinning is a fix for the non-divisible-heads pathology only;
        # where H % TP == 0 XLA already shards heads and pins add reshards
        # (H-gqa refinement, EXPERIMENTS §Perf iteration 3)
        attn_pin = pin if (pin and cfg.n_heads % ctx.mesh.shape[ctx.ep_axis]) else None
        x = x + attention_train(p["attn"], cfg.attn_cfg(kind), h, constrain=attn_pin)
    else:
        x = x + mamba_train(p["mamba"], cfg.mamba_cfg(), h, constrain=pin)
    if ffn is not None:
        x, stats = _apply_ffn(
            p, cfg, x, ctx, stats, moe_capacity=moe_capacity, moe_stats=moe_stats
        )
    return x, stats


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    ctx: ShardCtx = ShardCtx(),
    frontend_embeds: Optional[jax.Array] = None,
    remat: bool = True,
) -> Tuple[jax.Array, dict]:
    """tokens (B,S) -> (logits (B,S,V) fp32, stats). Full-sequence pass."""
    x = embed_tokens(params["embed"], tokens, cfg, ctx)
    if frontend_embeds is not None:
        F = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x[:, F:]], axis=1)

    aux0 = jnp.zeros((), jnp.float32)
    ovf0 = jnp.asarray(False)

    def group_body(carry, gp):
        x, aux, ovf = carry
        x = ctx.constrain_batch(x)  # anchor the scan carry's batch sharding
        stats = {"moe_aux": aux, "moe_overflow": ovf}
        for i, (kind, ffn) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
            x, stats = _apply_block(gp[f"pos{i}"], cfg, kind, ffn, x, ctx, stats)
        return (x, stats["moe_aux"], stats["moe_overflow"]), None

    body = group_body
    if remat:
        policy = (
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            if cfg.remat_policy == "dots"
            else None
        )
        body = jax.checkpoint(group_body, policy=policy)
    (x, aux, ovf), _ = jax.lax.scan(body, (x, aux0, ovf0), params["blocks"])
    x = rmsnorm(params["final_norm"], x)
    logits = unembed(params["embed"], x, cfg.vocab_size)
    return logits, {"moe_aux": aux / max(cfg.n_layers, 1), "moe_overflow": ovf}


# ---------------------------------------------------------------- decode ---


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Per-group stacked caches (scan-compatible)."""

    def one(kind: str):
        if kind.startswith("attn"):
            c = init_kv_cache(cfg.attn_cfg(kind), batch, max_len, cfg.compute_dtype)
        else:
            c = init_mamba_cache(cfg.mamba_cfg(), batch, cfg.compute_dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_groups,) + a.shape), c
        )

    return {f"pos{i}": one(kind) for i, kind in enumerate(cfg.pattern)}


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,     # (B, 1) next-token ids
    cache,
    *,
    ctx: ShardCtx = ShardCtx(),
):
    """One decode step through the whole stack. Returns (logits, new_cache)."""
    x = embed_tokens(params["embed"], tokens, cfg, ctx)

    def group_body(x, inputs):
        gp, gcache = inputs
        new_gcache = {}
        for i, (kind, ffn) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
            p = gp[f"pos{i}"]
            h = rmsnorm(p["norm1"], x)
            if kind.startswith("attn"):
                out, nc = attention_decode(p["attn"], cfg.attn_cfg(kind), h, gcache[f"pos{i}"])
            else:
                out, nc = mamba_decode(p["mamba"], cfg.mamba_cfg(), h, gcache[f"pos{i}"])
            x = x + out
            new_gcache[f"pos{i}"] = nc
            if ffn is not None:
                x, _ = _apply_ffn(p, cfg, x, ctx, {}, decode=True)
        return x, new_gcache

    x, new_cache = jax.lax.scan(group_body, x, (params["blocks"], cache))
    x = rmsnorm(params["final_norm"], x)
    logits = unembed(params["embed"], x, cfg.vocab_size)
    return logits, new_cache
