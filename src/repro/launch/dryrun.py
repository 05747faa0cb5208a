import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices stand in for 2 TPU v5e pods. For each cell we AOT-lower the right
step function (train_step / prefill_step / serve_decode_step) with
ShapeDtypeStruct inputs carrying their production NamedShardings, compile,
and record:

  * memory_analysis()  — bytes per device (proves it fits)
  * cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * collective bytes   — parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import re
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ARCHS, SHAPES, all_cells, cell_applicable, input_specs
from repro.distributed.sharding import (
    batch_specs,
    cache_specs,
    opt_state_specs,
    param_specs,
    to_named,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import ModelConfig, ShardCtx, init_cache, model_init
from repro.optim.adamw import OptConfig, init_opt_state
from repro.train.steps import prefill_step, serve_decode_step, train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute"
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _line_bytes(type_str: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


_COLL_RE = re.compile(
    r"=\s+(.+?)\s+((?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)[\w-]*)\("
)
_WHILE_RE = re.compile(r"while\(.*?body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"while\(.*?condition=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict:
    """computation name -> list of body lines (optimized HLO text format)."""
    comps, name, body = {}, None, []
    for line in hlo_text.splitlines():
        if (
            line
            and not line.startswith((" ", "}"))
            and line.rstrip().endswith("{")
            and "->" in line
        ):
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", line)
            if m:
                name, body = m.group(1), []
                comps[name] = body
                continue
        if line.startswith("}"):
            name = None
        elif name is not None:
            body.append(line.strip())
    return comps


def collective_bytes(hlo_text: str) -> dict:
    """Sum collective bytes in optimized HLO, *multiplying by loop trip counts*.

    jax scans lower to `while` ops: a collective inside the layer scan runs
    G times per step, inside the microbatch scan G*mb times. cost_analysis()
    ignores loop trip counts (refuted hypothesis H-acct, EXPERIMENTS.md §Perf)
    so we walk the computation graph and multiply. Trip counts are read from
    the loop condition's s32 constant (jax emits constant trip counts for
    scan); heuristic: the max s32 constant in the condition body.
    """
    comps = _split_computations(hlo_text)
    entry = next((n for n in comps if "main" in n), None)
    if entry is None and comps:
        entry = next(iter(comps))

    trip_re = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

    def cond_trips(line: str, cond_name: str) -> int:
        m = trip_re.search(line)  # XLA records the trip count on the while op
        if m:
            return int(m.group(1))
        consts = [int(c) for c in _CONST_RE.findall("\n".join(comps.get(cond_name, [])))]
        return max(consts) if consts else 1

    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    active = set()

    def walk(name: str, mult: float):
        if name not in comps or name in active:
            return
        active.add(name)
        for line in comps[name]:
            cm = _COLL_RE.search(line)
            if cm and not cm.group(2).endswith("-done"):
                kind = next(k for k in _COLLECTIVES if cm.group(2).startswith(k))
                out[kind] += int(_line_bytes(cm.group(1)) * mult)
                counts[kind] += int(mult)
            wm = _WHILE_RE.search(line)
            if wm:
                cnd = _COND_RE.search(line)
                trips = cond_trips(line, cnd.group(1) if cnd else "")
                walk(wm.group(1), mult * trips)
                continue
            fm = _CALL_RE.search(line)
            if fm:
                walk(fm.group(1), mult)
        active.discard(name)

    if entry:
        walk(entry, 1.0)
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def _shaped(tree, specs, mesh):
    named = to_named(specs, mesh, like=tree)

    def one(leaf, ns):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=ns)

    return jax.tree.map(one, tree, named)


def build_cell(arch: str, shape: str, mesh):
    """Returns (fn, shaped_args tuple) ready for jit(...).lower(*args)."""
    cfg = ARCHS[arch]
    from dataclasses import replace
    cf = os.environ.get("DRYRUN_CF")
    if cf and cfg.n_experts:
        cfg = replace(cfg, capacity_factor=float(cf))
    if os.environ.get("DRYRUN_COMPRESS_DISPATCH") and cfg.n_experts:
        cfg = replace(cfg, compress_dispatch=True)
    S, B, kind = SHAPES[shape]
    ctx = ShardCtx(mesh=mesh, axes=tuple(mesh.axis_names), ep_axis="model")

    p_shapes = jax.eval_shape(partial(model_init, cfg=cfg, ep_shards=ctx.ep_shards),
                              jax.random.PRNGKey(0))
    pspecs = param_specs(p_shapes)
    p_in = _shaped(p_shapes, pspecs, mesh)

    specs_in = input_specs(cfg, shape)
    b_in = _shaped(specs_in, batch_specs(specs_in), mesh)

    if kind == "train":
        # int8 moments for the giants (DESIGN.md §5), f32 otherwise
        ocfg = OptConfig(state_dtype="int8" if cfg.param_count() > 3e10 else "f32")
        n_micro = int(os.environ.get("DRYRUN_MICROBATCH", "4"))
        o_shapes = jax.eval_shape(partial(init_opt_state, cfg=ocfg), p_shapes)
        o_in = _shaped(o_shapes, opt_state_specs(o_shapes, pspecs), mesh)

        def fn(params, opt_state, batch):
            return train_step(
                params, opt_state, batch, cfg=cfg, opt_cfg=ocfg, ctx=ctx,
                loss_chunk=512, remat=True, n_microbatch=n_micro,
            )

        return fn, (p_in, o_in, b_in)

    if kind == "prefill":
        def fn(params, batch):
            return prefill_step(
                params, cfg, batch["tokens"], ctx=ctx,
                frontend_embeds=batch.get("frontend_embeds"),
            )

        return fn, (p_in, b_in)

    # decode: one token against a cache of length S
    c_shapes = jax.eval_shape(partial(init_cache, cfg=cfg, batch=B, max_len=S))
    c_in = _shaped(c_shapes, cache_specs(c_shapes, cfg), mesh)

    def fn(params, batch, cache):
        return serve_decode_step(params, cfg, batch["tokens"], cache, ctx=ctx)

    return fn, (p_in, b_in, c_in)


def run_cell(arch: str, shape: str, mesh_kind: str, outdir: str) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    fn, args = build_cell(arch, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    n_dev = mesh.devices.size
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "n_devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes_per_device": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            ),
        },
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed", "transcendentals")
                 if isinstance(cost, dict) and k in cost},
        "collectives": coll,
    }
    if outdir:
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, f"{arch}__{shape}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    meshes = ("pod", "multipod") if args.mesh == "both" else (args.mesh,)
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    failures = 0
    for arch, shape in cells:
        if not cell_applicable(arch, shape):
            print(f"SKIP {arch} {shape} (documented: needs sub-quadratic path)")
            continue
        for mk in meshes:
            try:
                rec = run_cell(arch, shape, mk, args.out)
                peak = rec["memory"]["peak_bytes_per_device"] or 0
                print(
                    f"OK   {arch:28s} {shape:12s} {mk:8s} "
                    f"peak/dev={peak/2**30:7.2f}GiB "
                    f"flops={rec['cost'].get('flops', float('nan')):.3e} "
                    f"coll={rec['collectives']['total_bytes']/2**30:.2f}GiB "
                    f"compile={rec['compile_s']:.0f}s"
                )
            except Exception as e:
                failures += 1
                print(f"FAIL {arch} {shape} {mk}: {type(e).__name__}: {e}")
                traceback.print_exc(limit=3)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
