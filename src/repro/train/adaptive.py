"""Between-step MoE capacity control: the training half of the adaptive loop.

Serving learns expert capacity inside the call (``moe_apply_adaptive`` /
``moe_apply_local_adaptive`` retry with doubled capacity); a jitted train
step cannot retry — recomputing the batch would change optimizer state — so
training closes the same loop *between* steps instead:

1. before a step, ``MoECapacityController.capacity`` converts the planner's
   learned factor for this (n_experts, top_k, token bucket, mesh) cell into
   a static per-(sender, expert) capacity (``train_step(moe_capacity=...)``);
2. the jitted step threads ``moe_dropped``/``moe_peak`` out of the stack
   (``repro.train.steps``);
3. after the step, ``observe`` folds them into the planner as an
   ``ExchangeObservation`` — the same telemetry schema serving reports — so
   the learned factor jumps above the observed peak and the *next* step's
   capacity recompiles once at the provisioned size.

Factors persist through the fcntl-locked plan cache, so capacity learned in
training warms serving and vice versa (docs/exchange.md, docs/plan-cache.md).
"""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp

from repro.core.bitonic import next_pow2
from repro.exchange import ExchangeObservation, expert_capacity
from repro.models.moe import MoEConfig, moe_plan_key


class MoECapacityController:
    """Host-side capacity policy for one (model, token shape, mesh) cell.

    ``tokens`` is the *global* token count one forward pass dispatches (one
    microbatch: ``batch * seq / n_microbatch``); the per-sender slice that
    sizes slabs is derived from the mesh in ``ctx`` (every mesh axis shards
    the token flatten — ``moe_shard_specs``'s convention — so a 2x4 mesh
    splits 512 tokens into 64-token senders; ``ctx.mesh is None`` means the
    replicated single-sender path).

    The controller is deliberately dumb: all learning lives in the planner's
    ``CapacityLearner`` (jump on pressure, decay toward the config default),
    all persistence in the plan cache. This class only converts between the
    step function's static-capacity world and the planner's factor world.
    """

    def __init__(self, cfg: MoEConfig, tokens: int, *, ctx, planner,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.tokens = int(tokens)
        self.planner = planner
        n_dev = 1
        if ctx.mesh is not None:
            for a in ctx.axes:
                n_dev *= ctx.mesh.shape[a]
        if self.tokens % n_dev:
            raise ValueError(
                f"tokens {self.tokens} must divide the {n_dev}-device mesh"
            )
        self.t_loc = self.tokens // n_dev       # per-sender token slice
        self.m = self.t_loc * cfg.top_k         # per-sender assignments
        self.key = moe_plan_key(self.tokens, cfg, dtype, ctx.mesh)

    @property
    def factor(self) -> float:
        """The cell's current learned capacity factor (config default until
        telemetry taught the planner otherwise)."""
        return self.planner.capacity_factor_for(
            self.key, default=self.cfg.capacity_factor
        )

    @property
    def capacity(self) -> int:
        """Per-(sender, expert) token capacity for the next step — static,
        so the driver keys its compiled step functions on it and a learned
        bump costs exactly one recompile.

        The raw factor-derived capacity is **bucketed to the next power of
        two** (the same pow2 bucketing token counts use), clamped to ``m``
        — the per-sender assignment count, beyond which capacity is
        loss-free by construction.  Without the bucket, a gradually
        decaying learned factor would shift the raw capacity by one or two
        tokens step after step, and since the driver keys compiled step
        functions on capacity, every shift would be a fresh lowering; with
        it, the factor must halve the raw capacity before a new executable
        is built.
        """
        raw = expert_capacity(
            self.t_loc, self.cfg.top_k, self.cfg.n_experts, self.factor
        )
        return min(next_pow2(max(raw, 1)), max(self.m, 1))

    def observe(self, metrics: dict, *, capacity: Optional[int] = None) -> None:
        """Fold one completed step's ``moe_dropped``/``moe_peak`` metrics
        into the planner (and its telemetry ledger, which AnomalyMonitor
        may be watching).  ``capacity`` is the value the step actually ran
        at; defaults to the current one for callers that don't cache it.

        A training step never retries, so every dropped token reached the
        served (trained-on) output: ``dropped`` is reported as real loss,
        never as averted.
        """
        cap = int(self.capacity if capacity is None else capacity)
        # peak is maxed over layers and microbatches; dropped sums layers
        # and microbatches of one step. With L MoE layers a steady skew
        # reports ~L * per-layer drops — fine: the learner reads peak, and
        # dropped>0 only gates the overflow flag / anomaly counter.
        dropped = int(metrics.get("moe_dropped", 0))
        peak = int(metrics.get("moe_peak", 0))
        obs = ExchangeObservation(
            m=self.m,
            part_buckets=max(self.cfg.n_experts, 1),
            capacity=cap,
            peak=peak,
            overflowed=bool(dropped > 0 or peak > cap),
            retries=0,
            recompiles=0,
            dropped=dropped,
            path="scatter",
        )
        self.planner.observe_exchange(
            self.key, obs, default=self.cfg.capacity_factor
        )


def parse_mesh_spec(spec: str):
    """``"data=2,model=4"`` -> a ``jax.Mesh`` plus its axis-name tuple.

    The train driver's --mesh flag: axis order is the spec's order (tokens
    shard over every axis, experts over the ``model`` axis by ShardCtx
    convention).  Raises ValueError when the requested devices exceed what
    the runtime has.

    >>> mesh, axes = parse_mesh_spec("data=1,model=1")
    >>> axes
    ('data', 'model')
    >>> dict(mesh.shape)
    {'data': 1, 'model': 1}
    """
    import jax

    pairs = []
    for part in spec.split(","):
        name, _, size = part.partition("=")
        if not name or not size:
            raise ValueError(f"bad mesh spec {spec!r} (want axis=size,...)")
        pairs.append((name.strip(), int(size)))
    names = tuple(n for n, _ in pairs)
    sizes = tuple(s for _, s in pairs)
    need = math.prod(sizes)
    have = len(jax.devices())
    if need > have:
        raise ValueError(f"mesh {spec!r} needs {need} devices, have {have}")
    return jax.make_mesh(sizes, names), names
