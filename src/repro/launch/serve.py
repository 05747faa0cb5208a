"""Batched serving driver: prefill a prompt batch, decode N tokens.

Greedy/temperature sampling over the vocab-parallel logits; the decode loop
uses the serving top-k from the sort engine (repro.engine.topk, a stable
descending argsort) — the serving-path integration from DESIGN.md §3.

``--topk-queue`` routes each row's top-k through a one-tenant
``repro.engine.SortFrontend`` instead: every row is an independent
single-request producer, and the frontend coalesces them back into one
executable call per step — the serving shape docs/serving.md describes,
with frontend stats printed at exit.  ``--stats`` (implies ``--topk-queue``)
also prints the full service ledger, including the ``overflow_retries`` /
``recompiles`` exchange-path counters.

``--moe`` serves MoE expert routing through the adaptive exchange engine
instead of decoding: a (deliberately skew-able, ``--moe-skew``) router
dispatches ``--batch x --prompt-len`` tokens per step via
``moe_apply_adaptive``, which runs at the planner's *learned* expert
capacity factor, retries-over-drops on overflow, and feeds the telemetry
ledger ``--stats`` prints (drop/overflow/retry/recompile counts and the
learned factor).  Point ``$REPRO_SORT_PLANS`` at a JSON file and the
learned capacity survives restarts — the second serve run's first step
already sizes expert buffers right (docs/exchange.md).

``--tenants web:3:0,batch:1:1`` serves the top-k path through a
multi-tenant frontend instead: decode rows are assigned round-robin across
the named tenants (weight and priority per spec), each stamped with the
``--slo-ms`` deadline, and the exit line reports per-tenant served counts
and SLO misses.  ``--warmup`` (implies ``--topk-queue``) AOT-compiles the
vocab-size argsort ladder before traffic so the first decode step pays
zero fresh compiles (docs/serving.md).

Usage:
  python -m repro.launch.serve --arch qwen3-0.6b --reduced --batch 4 \
      --prompt-len 32 --gen 16 [--topk-queue] [--stats]
  python -m repro.launch.serve --moe --batch 4 --prompt-len 64 --gen 8 \
      --experts 8 --moe-skew 6.0 --stats
  python -m repro.launch.serve --reduced --batch 4 --gen 8 \
      --tenants web:3:0,batch:1:1 --warmup --slo-ms 50 --stats
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCHS, reduced
from repro.engine import SortFrontend, Tenant, topk
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import ShardCtx, model_init
from repro.train.steps import prefill_step, serve_decode_step


def sample_next(logits: jax.Array, key, *, temperature: float, top_k: int,
                frontend=None, tenants=(), ticket_log=None):
    """(B, V) logits -> (B,) token ids. top_k via the engine's stable argsort
    (same tie behaviour as lax.top_k; the serving-path integration).

    With ``frontend=`` (a ``SortFrontend``) each row becomes one
    ``submit(kind='argsort', ascending=False)`` request, round-robin across
    ``tenants``; the frontend coalesces the B rows into a single executable
    call per decode step.  Each row carries its tenant's SLO deadline, and
    admitted tickets land in ``ticket_log`` so ``main`` can report
    per-tenant SLO misses at exit.
    """
    if frontend is not None:
        rows = np.asarray(logits, np.float32)
        futs = [
            frontend.submit(tenants[i % len(tenants)], r,
                            kind="argsort", ascending=False)
            for i, r in enumerate(rows)
        ]
        if ticket_log is not None:
            ticket_log.extend(futs)
        order = np.stack([np.asarray(f.result())[:top_k] for f in futs])
        idx = jnp.asarray(order.astype(np.int32))
        if temperature <= 0:
            return idx[:, 0]
        vals = jnp.take_along_axis(jnp.asarray(rows), idx, axis=1)
    else:
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        vals, idx = topk(logits, top_k)
    probs = jax.nn.softmax(vals / temperature, axis=-1)
    choice = jax.random.categorical(key, jnp.log(jnp.maximum(probs, 1e-20)))
    return jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)


def run_moe_serving(args):
    """--moe: serve expert routing through the adaptive exchange engine.

    Every step dispatches one token batch with ``moe_apply_adaptive`` — the
    MoE consumer of ``repro.exchange`` — through the process-wide planner,
    so expert capacity factors are learned (and, with $REPRO_SORT_PLANS,
    persisted) exactly like model-D sort capacities.  A skewed router pays
    its overflow retry on the first step; every later step — and every step
    of a restarted process — runs at the learned factor with zero retries.
    """
    from repro.engine.planner import default_planner
    from repro.models.moe import (
        MoEConfig,
        collapse_router,
        moe_apply_adaptive,
        moe_init,
        moe_plan_key,
    )

    cfg = MoEConfig(
        d_model=64, d_ff=32, n_experts=args.experts, top_k=args.moe_top_k
    )
    planner = default_planner()
    p = moe_init(jax.random.PRNGKey(args.seed), cfg, jnp.float32, ep_shards=1)
    if args.moe_skew:
        # worst-case routing skew, so the capacity loop has something to
        # learn from (a fresh random router is the near-uniform case the
        # aux loss trains toward — no overflow, no story)
        p = collapse_router(p, args.moe_skew)

    T = args.batch * args.prompt_len
    key = moe_plan_key(T, cfg, jnp.float32)
    rng = np.random.default_rng(args.seed)
    led = planner.telemetry
    # the default planner's ledger is process-wide; snapshot every counter so
    # --stats reports this run's deltas, not whatever ran before in-process
    base = {name: getattr(led, name) for name in (
        "calls", "total_dropped", "total_dropped_averted", "overflow_events",
        "total_retries", "total_recompiles")}
    retries0 = base["total_retries"]

    t_start = time.time()
    y = None
    first_retries = 0
    t_warm = dt = 0.0
    for step in range(args.gen):
        x = jnp.asarray(rng.standard_normal((T, cfg.d_model)), jnp.float32)
        y, aux, counts = moe_apply_adaptive(p, cfg, x, planner=planner)
        if step == 0:
            # step 0 pays the XLA compiles (plus any overflow-retry
            # recompiles); keep it out of the steady-state rate
            jax.block_until_ready(y)
            first_retries = led.total_retries - retries0
            t_warm = time.time() - t_start
            t0 = time.time()
    jax.block_until_ready(y)
    if args.gen > 1:
        dt = time.time() - t0
    steady_steps = max(args.gen - 1, 1)

    cf = planner.capacity_factor_for(key, default=cfg.capacity_factor)
    steady = (
        f"steady {dt / steady_steps * 1e3:.2f} ms/step "
        f"({T * (args.gen - 1) / max(dt, 1e-9):.0f} tokens/s)"
        if args.gen > 1 else "steady n/a (needs --gen >= 2)"
    )
    print(f"moe-serve: experts={cfg.n_experts} top_k={cfg.top_k} "
          f"tokens/step={T} steps={args.gen}")
    print(f"moe-serve: warmup {t_warm * 1e3:.1f} ms "
          f"(retries={first_retries}); {steady} learned_cf={cf:.2f}")
    if args.stats:
        # dropped = tokens the served outputs actually lost (retry budget
        # exhausted); dropped_averted = losses retried attempts recomputed
        # away — the telemetry schema keeps the two separate (docs/exchange.md)
        d = {name: getattr(led, name) - v for name, v in base.items()}
        # routing is constant across this run's steps, so the final
        # observation's required factor IS the run's peak requirement (the
        # ledger-wide peak_factor would mix in pre-run in-process traffic)
        last = led.last(key)
        rf = last.required_factor() if d["calls"] and last else 0.0
        print(f"moe-stats: calls={d['calls']} "
              f"dropped={d['total_dropped']} "
              f"dropped_averted={d['total_dropped_averted']} "
              f"overflows={d['overflow_events']} "
              f"retries={d['total_retries']} "
              f"recompiles={d['total_recompiles']} "
              f"required_factor={rf:.2f}")
    late = led.total_retries - retries0 - first_retries
    if late:
        # later batches out-skewed the learned margin; the learner has
        # already jumped again, so this is a one-off per skew level
        print(f"moe-serve: note — {late} post-warmup retrie(s) "
              f"(skew exceeded the learned margin; factor re-learned)")
    return y


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--topk-queue", action="store_true",
                    help="route per-row top-k through a one-tenant "
                         "SortFrontend (docs/serving.md)")
    ap.add_argument("--stats", action="store_true",
                    help="print the full service ledger at exit, incl. the "
                         "overflow_retries / recompiles exchange counters "
                         "(implies --topk-queue: the ledger lives on the "
                         "sort service)")
    ap.add_argument("--moe", action="store_true",
                    help="serve MoE expert routing through the adaptive "
                         "exchange engine instead of decoding; --stats "
                         "prints drop/overflow/retry counts (docs/exchange.md)")
    ap.add_argument("--experts", type=int, default=8,
                    help="expert count for --moe serving")
    ap.add_argument("--moe-top-k", type=int, default=2,
                    help="router top-k for --moe serving")
    ap.add_argument("--moe-skew", type=float, default=6.0,
                    help="router logit bias onto a hot expert subset (0 = "
                         "uniform routing, nothing for the loop to learn)")
    ap.add_argument("--tenants", default="",
                    help="serve the top-k path through a multi-tenant "
                         "SortFrontend; comma-separated "
                         "name[:weight[:priority]] specs, decode rows "
                         "assigned round-robin (docs/serving.md)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request deadline budget for --tenants rows; "
                         "late rows are still answered (serving must emit a "
                         "token) and counted as SLO misses at exit")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the serving sort cells (vocab-size "
                         "argsort across the batch ladder) before traffic, "
                         "so the first decode step pays zero compiles "
                         "(implies --topk-queue)")
    args = ap.parse_args(argv)

    if args.moe:
        return run_moe_serving(args)

    frontend = None
    fe_tickets: list = []
    specs: list = []
    if args.tenants:
        for spec in args.tenants.split(","):
            parts = spec.split(":")
            specs.append(Tenant(
                parts[0],
                weight=float(parts[1]) if len(parts) > 1 else 1.0,
                priority=int(parts[2]) if len(parts) > 2 else 0,
                slo_ms=args.slo_ms,
            ))
    elif args.topk_queue or args.stats or args.warmup:
        specs = [Tenant("decode")]
    if specs:
        # shed_expired=False: a decode row must produce a token no matter
        # what, so late rows are served and the miss is counted instead
        frontend = SortFrontend(tenants=specs, max_batch=args.batch,
                                shed_expired=False, start=True)
    fe_tenants = [t.name for t in specs]

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)

    if args.warmup:
        # AOT-warm every executable the decode loop's top-k can touch: a
        # descending float32 argsort of one vocab row, at every pow2 batch
        # bucket up to --batch (partial flushes produce partial batches)
        rep = frontend.warmup(cells=[(cfg.vocab_size, "float32")],
                              kinds=("argsort",), ascending=(False,))
        print(rep.summary())

    ctx = ShardCtx()
    key = jax.random.PRNGKey(args.seed)
    params = model_init(key, cfg, ep_shards=ctx.ep_shards)

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)), jnp.int32
    )
    fe = None
    if cfg.frontend != "none":
        fe = jnp.asarray(
            rng.standard_normal((args.batch, cfg.n_frontend_tokens, cfg.d_model)),
            cfg.compute_dtype,
        )

    t0 = time.time()
    cache_len = args.prompt_len + args.gen
    logits, cache = jax.jit(
        lambda p, t, f: prefill_step(p, cfg, t, ctx=ctx, frontend_embeds=f,
                                     cache_len=cache_len)
    )(params, prompts, fe)
    t_prefill = time.time() - t0

    decode = jax.jit(lambda p, t, c: serve_decode_step(p, cfg, t, c, ctx=ctx))
    out_tokens = []
    tok = sample_next(logits, key, temperature=args.temperature,
                      top_k=args.top_k, frontend=frontend,
                      tenants=fe_tenants, ticket_log=fe_tickets)
    out_tokens.append(tok)
    t0 = time.time()
    for i in range(args.gen - 1):
        key, sub = jax.random.split(key)
        lg, cache = decode(params, tok[:, None], cache)
        tok = sample_next(lg[:, 0], sub, temperature=args.temperature,
                          top_k=args.top_k, frontend=frontend,
                          tenants=fe_tenants, ticket_log=fe_tickets)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill {t_prefill*1e3:.1f} ms; decode {t_decode/max(args.gen-1,1)*1e3:.2f} ms/tok")
    print("sampled token ids (first row):", gen[0][:16].tolist())
    if frontend is not None:
        frontend.close()
        st = frontend.stats
        served = " ".join(f"{k}={v}"
                          for k, v in sorted(st.tenant_served.items()))
        misses = sum(1 for t in fe_tickets if not t.slo_met)
        print(f"frontend: tenants[{served}] batches={st.batches} "
              f"fill={st.fill_ratio():.2f} compiles={st.compiles} "
              f"slo_misses={misses}/{len(fe_tickets)} "
              f"shed={st.shed_total()}")
        if args.stats:
            pct = st.latency_percentiles()
            print(f"service-stats: requests={st.requests} "
                  f"keys_in={st.keys_in} cache_hits={st.cache_hits} "
                  f"overflow_retries={st.overflow_retries} "
                  f"recompiles={st.recompiles} "
                  f"peak_mean_ratio={st.peak_mean_ratio:.2f} "
                  f"latency p50={pct[50]*1e3:.2f} ms p99={pct[99]*1e3:.2f} ms "
                  f"throughput={st.throughput_keys_per_s():.0f} keys/s")
    assert gen.min() >= 0 and gen.max() < cfg.vocab_size, "pad-vocab leak!"
    return gen


if __name__ == "__main__":
    main()
