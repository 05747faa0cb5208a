"""Training-side MoE capacity loop: empty/single-token dispatch edges, the
train_step stats plumbing, the between-step learning loop (a skewed router
pays its overflow at most once, with zero fresh lowerings after the one
bump), and the train -> serve warm start through the shared plan cache."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, run_with_devices
from repro.models.moe import (
    MoEConfig,
    moe_apply_adaptive,
    moe_apply_ep_replicated,
    moe_apply_local_adaptive,
    moe_init,
)

# ------------------------------------------------- T=0 / T=1 edge cases ---


@pytest.mark.parametrize("T", [0, 1])
def test_replicated_path_handles_tiny_batches(key, T):
    """T=0 (drained microbatch) and T=1 must produce finite outputs and a
    finite aux loss — the router's load-balance term divides by T."""
    cfg = MoEConfig(d_model=8, d_ff=4, n_experts=4, top_k=2, capacity_factor=2.0)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, 8))
    y, aux, dropped, counts, peak, overflow = moe_apply_ep_replicated(
        p, cfg, x, with_stats=True
    )
    assert y.shape == (T, 8)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(float(aux))
    assert int(dropped) == 0 and not bool(overflow)
    assert int(counts.sum()) == T * cfg.top_k
    assert int(peak) <= max(T, 1)


@pytest.mark.parametrize("T", [0, 1])
def test_adaptive_paths_handle_tiny_batches(key, T):
    """Both adaptive entry points (replicated and 1-device mesh) survive
    empty and single-token batches: expert_capacity floors at 1, so the
    compiled forwards always see well-formed >=1-slot slabs."""
    cfg = MoEConfig(d_model=8, d_ff=4, n_experts=4, top_k=2, capacity_factor=2.0)
    p = moe_init(key, cfg, jnp.float32, ep_shards=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 8))

    y, aux, counts = moe_apply_adaptive(p, cfg, x, capacity_factor=2.0)
    assert y.shape == (T, 8) and np.isfinite(np.asarray(y)).all()
    assert int(counts.sum()) == T * cfg.top_k

    mesh = jax.make_mesh((1,), ("x",))
    y2, aux2, counts2 = moe_apply_local_adaptive(
        p, cfg, x, mesh, axes=("x",), ep_axis="x", capacity_factor=2.0
    )
    assert y2.shape == (T, 8) and np.isfinite(np.asarray(y2)).all()
    assert np.isfinite(float(aux2))
    assert int(counts2.sum()) == T * cfg.top_k
    if T:  # identical routing on 1 device -> identical outputs
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-5)


# ------------------------------------- train_step stats + capacity loop ---

_TINY_MOE_ARCH = """
    from dataclasses import replace
    import jax.numpy as jnp
    from repro.configs.base import ARCHS
    cfg = replace(
        ARCHS["qwen3-0.6b"], name="t",
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=32, vocab_size=64, kv_chunk=16,
        pattern=("attn",), ffn_pattern=("moe",),
        n_experts=8, top_k=2, capacity_factor=1.0,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
    )
"""


def test_train_step_surfaces_drop_and_peak_stats():
    """loss_fn/train_step thread moe_dropped/moe_peak out of the jitted
    stack on a forced expert-parallel mesh: a collapsed router at a starved
    capacity reports drops and a peak above capacity; a generous capacity
    reports zero drops.  This is the signal the between-step controller
    feeds on — if it silently vanishes, capacity learning dies."""
    run_with_devices(_TINY_MOE_ARCH + """
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.moe import collapse_router
    from repro.models.transformer import ShardCtx, model_init
    from repro.optim.adamw import OptConfig, init_opt_state
    from repro.train.adaptive import parse_mesh_spec
    from repro.train.steps import loss_fn, train_step

    mesh, axes = parse_mesh_spec("data=2,model=4")
    ctx = ShardCtx(mesh=mesh, axes=axes)
    params = model_init(jax.random.PRNGKey(0), cfg, ep_shards=ctx.ep_shards)
    params["blocks"] = {
        pos: ({**gp, "moe": collapse_router(gp["moe"], 6.0)} if "moe" in gp else gp)
        for pos, gp in params["blocks"].items()
    }
    rng = np.random.default_rng(0)
    tok = rng.integers(1, cfg.vocab_size, (4, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tok[:, :-1]), "labels": jnp.asarray(tok[:, 1:])}

    # loss_fn alone surfaces the stats (the controller's signal source)
    loss, stats = loss_fn(params, cfg, batch, ctx=ctx, loss_chunk=32, moe_capacity=2)
    assert {"moe_dropped", "moe_peak"} <= set(stats), sorted(stats)
    assert int(stats["moe_dropped"]) > 0
    assert int(stats["moe_peak"]) > 2

    ocfg = OptConfig(peak_lr=1e-4, warmup_steps=2, total_steps=4)
    opt = init_opt_state(params, ocfg)
    step = functools.partial(train_step, cfg=cfg, opt_cfg=ocfg, ctx=ctx,
                             n_microbatch=1, loss_chunk=32)
    _, _, m_starved = jax.jit(functools.partial(step, moe_capacity=2))(params, opt, batch)
    assert int(m_starved["moe_dropped"]) > 0
    assert int(m_starved["moe_peak"]) > 2
    assert np.isfinite(float(m_starved["loss"]))

    # generous capacity: every assignment lands, peak is the true demand
    _, _, m_full = jax.jit(functools.partial(step, moe_capacity=31))(params, opt, batch)
    assert int(m_full["moe_dropped"]) == 0
    assert int(m_full["moe_peak"]) == int(m_starved["moe_peak"])
    print("ok")
    """)


def test_capacity_loop_pays_overflow_once_and_persists(tmp_path):
    """The acceptance loop: a skewed-router MoE LM trained through the
    MoECapacityController overflows on step 0, recompiles once at the
    learned capacity, then runs drop-free with ZERO fresh jit lowerings —
    and the learned factor lands in the plan cache under the mesh cell."""
    plans = str(tmp_path / "plans.json")
    run_with_devices(_TINY_MOE_ARCH + f"""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax._src import test_util as jtu
    from repro.engine.planner import Planner
    from repro.models.moe import collapse_router
    from repro.models.transformer import ShardCtx, model_init
    from repro.optim.adamw import OptConfig, init_opt_state
    from repro.train.adaptive import MoECapacityController, parse_mesh_spec
    from repro.train.steps import train_step

    mesh, axes = parse_mesh_spec("data=2,model=4")
    ctx = ShardCtx(mesh=mesh, axes=axes)
    params = model_init(jax.random.PRNGKey(0), cfg, ep_shards=ctx.ep_shards)
    params["blocks"] = {{
        pos: ({{**gp, "moe": collapse_router(gp["moe"], 6.0)}} if "moe" in gp else gp)
        for pos, gp in params["blocks"].items()
    }}
    ocfg = OptConfig(peak_lr=1e-4, warmup_steps=2, total_steps=4)
    opt = init_opt_state(params, ocfg)
    planner = Planner({plans!r})
    ctl = MoECapacityController(cfg.moe_cfg(), tokens=4 * 32, ctx=ctx,
                                planner=planner, dtype=cfg.compute_dtype)

    @functools.lru_cache(maxsize=None)
    def step_fn(cap):
        return jax.jit(functools.partial(
            train_step, cfg=cfg, opt_cfg=ocfg, ctx=ctx,
            n_microbatch=1, loss_chunk=32, moe_capacity=cap))

    rng = np.random.default_rng(0)

    def one_step():
        tok = rng.integers(1, cfg.vocab_size, (4, 33)).astype(np.int32)
        batch = {{"tokens": jnp.asarray(tok[:, :-1]),
                  "labels": jnp.asarray(tok[:, 1:])}}
        cap = ctl.capacity
        params2, opt2, m = step_fn(cap)(params, opt, batch)
        m = {{k: float(v) if jnp.ndim(v) == 0 else v for k, v in m.items()}}
        ctl.observe(m, capacity=cap)
        return cap, int(m["moe_dropped"]), float(m["loss"])

    caps, drops, losses = [], [], []
    for _ in range(2):
        c, d, l = one_step()
        caps.append(c); drops.append(d); losses.append(l)

    # steps 2..3 run at the learned capacity: no drops, no fresh lowerings
    with jtu.count_jit_and_pmap_lowerings() as count:
        for _ in range(2):
            c, d, l = one_step()
            caps.append(c); drops.append(d); losses.append(l)
    assert count() == 0, f"steady-state train step re-traced: {{count()}}"

    assert drops[0] > 0, "collapsed router at cf=1.0 must overflow step 0"
    assert drops[1:] == [0, 0, 0], f"overflow paid more than once: {{drops}}"
    assert caps[0] < caps[1] and len(set(caps[1:])) == 1, caps
    assert all(np.isfinite(l) for l in losses), losses
    assert "/data=2,model=4" in ctl.key, ctl.key
    planner.save()
    print("cell", ctl.key, "cf", ctl.factor)
    """)
    # the factor is durable: a fresh planner (fresh process would do the
    # same) reads it back above the config default
    from repro.engine.planner import Planner

    doc = json.load(open(plans))
    assert doc["version"] == 3
    cells = [k for k in doc["learned"] if k.startswith("moe/")]
    assert len(cells) == 1 and "data=2,model=4" in cells[0], cells
    assert Planner(plans).capacity_factor_for(cells[0], default=1.0) > 1.0


def test_capacity_bucketing_pins_lowerings_under_decay():
    """A calm era geometrically decays the learned factor toward the config
    default; since the driver keys compiled step functions on the static
    capacity, an *unbucketed* capacity would drift by a few tokens step
    after step and pay a fresh lowering almost every time.  The pow2 bucket
    must compress a whole decay trace into a handful of lowerings — this
    deterministic trace pins the count."""
    from types import SimpleNamespace

    from repro.exchange import expert_capacity
    from repro.train.adaptive import MoECapacityController

    # the factor trace a CapacityLearner produces after skew ends: geometric
    # decay from the skew-era high-water mark back to the default
    factors = [max(1.0, 4.0 * (0.93 ** i)) for i in range(40)]

    class DecayPlanner:
        def __init__(self):
            self.i = 0

        def capacity_factor_for(self, key, default=1.0):
            return factors[min(self.i, len(factors) - 1)]

    cfg = MoEConfig(d_model=8, d_ff=4, n_experts=8, top_k=2, capacity_factor=1.0)
    ctl = MoECapacityController(
        cfg, tokens=128, ctx=SimpleNamespace(mesh=None, axes=()),
        planner=DecayPlanner(),
    )

    caps, lowered = [], set()
    for i in range(len(factors)):
        ctl.planner.i = i
        cap = ctl.capacity
        caps.append(cap)
        lowered.add(cap)  # the lru-keyed step table compiles once per value

    raw = [
        expert_capacity(ctl.t_loc, cfg.top_k, cfg.n_experts, f) for f in factors
    ]
    assert len(set(raw)) > 10, "the decay must actually move the raw capacity"
    assert len(lowered) <= 4, f"bucketed decay must stay cheap: {sorted(lowered)}"
    # the bucket only ever rounds *up* (and m is the loss-free ceiling), so
    # bucketing never makes a step lossier than the raw capacity would be
    assert all(c >= r or c >= ctl.m for c, r in zip(caps, raw))
    assert all(c <= ctl.m for c in caps)
    assert caps == sorted(caps, reverse=True), "decay trace must be monotone"


def test_train_learned_factor_warm_starts_serving(tmp_path):
    """Cross-half acceptance: train a tiny skewed MoE LM (mesh=None cell),
    then start serve.py --moe against the same plan file and the same
    (E, k, token-bucket) cell — serving must warm-start at the trained
    factor with zero retries and zero dropped tokens."""
    plans = str(tmp_path / "plans.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_SORT_PLANS"] = plans
    env.pop("XLA_FLAGS", None)  # single device -> mesh=None -> local/cpu cell

    # train: 1 step, so the router is still fully collapsed when the factor
    # persists — serving's identically-skewed router needs the same peak
    # (more steps rebalance the router and the factor legitimately decays)
    train = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "train_lm.py"),
         "--moe", "--steps", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert train.returncode == 0, train.stderr
    assert "moe-train-smoke" in train.stdout, train.stdout
    doc = json.load(open(plans))
    trained = [k for k in doc["learned"] if k.startswith("moe/")]
    assert trained, doc["learned"].keys()

    # serve: same E=8/k=2, same T=4*32=128 token bucket, same local mesh
    serve = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--moe",
         "--moe-skew", "6.0", "--batch", "4", "--prompt-len", "32",
         "--gen", "2", "--experts", "8", "--stats"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert serve.returncode == 0, serve.stderr
    assert "(retries=0)" in serve.stdout, serve.stdout
    assert "dropped=0 " in serve.stdout, serve.stdout
    assert "overflows=0" in serve.stdout, serve.stdout
