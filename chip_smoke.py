"""Smoke run of the sort service on a TPU: every phase checked against numpy.

    python chip_smoke.py               # one chip: the service's main path
    python chip_smoke.py --chips 4     # four chips: model D and its checks only

One process drives every chip it uses. Keys are made on the device from
``--seed``; every phase compares what the public entry point returned with
numpy on the same keys and raises on the first mismatch. Off a TPU the script
exits nonzero before doing any work. The lines it prints before the last are
smoke-run timings (first call includes compilation), not measurements. The
last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Qwen3-0.6B's vocabulary (src/repro/configs/qwen3_0_6b.py): the width of one
# decode-time logits row that a top-k request ranks
VOCAB = 151936
ZIPF_S = 0.99  # YCSB's Zipfian constant
ZIPF_ITEMS = 1 << 20


def _die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _require_tpu(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _die(f"needs a TPU, JAX found {devices[0].platform!r} devices; nothing was run")
    if len(devices) < chips:
        _die(f"--chips {chips} needs {chips} TPU devices, JAX found {len(devices)}")
    return devices


def _keys(seed: int, n: int, dist: str, sharding=None):
    """int32 keys made on the device: uniform over int32, or YCSB-style
    scrambled Zipf (item ranks with P(k) ~ 1/k^0.99 drawn by YCSB's
    ZipfianGenerator formula, elementwise, then hashed over int32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # the generator's constants (Gray et al., as in YCSB's ZipfianGenerator)
    zetan = float(np.sum(np.arange(1, ZIPF_ITEMS + 1, dtype=np.float64) ** -ZIPF_S))
    zeta2 = 1.0 + 0.5**ZIPF_S
    eta = (1 - (2 / ZIPF_ITEMS) ** (1 - ZIPF_S)) / (1 - zeta2 / zetan)

    def make(key):
        if dist == "uniform":
            return jax.lax.bitcast_convert_type(jax.random.bits(key, (n,), jnp.uint32), jnp.int32)
        u = jax.random.uniform(key, (n,))
        uz = u * zetan
        tail = ZIPF_ITEMS * (eta * u - eta + 1) ** (1 / (1 - ZIPF_S))
        rank = jnp.where(uz < 1, 0, jnp.where(uz < zeta2, 1, tail.astype(jnp.int32)))
        scrambled = rank.astype(jnp.uint32) * jnp.uint32(2654435761)
        return jax.lax.bitcast_convert_type(scrambled, jnp.int32)

    x = jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))
    return x.block_until_ready()


def _stable_argsort(x):
    """``np.argsort(x, kind="stable")`` for int32 keys, via one int64 sort of
    (key, index) pairs: the same permutation, without the slow stable path."""
    import numpy as np

    pairs = (x.astype(np.int64) << 32) | np.arange(x.size, dtype=np.int64)
    return (np.sort(pairs) & 0xFFFFFFFF).astype(np.int32)


def _check(name: str, got, want) -> None:
    import numpy as np

    got = np.asarray(got)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = np.flatnonzero(got != want)[:5] if got.shape == want.shape else "shape"
        raise AssertionError(f"{name}: result differs from numpy (first bad {bad})")


def _timed(fn):
    """(result, first-call seconds incl. compile, second-call seconds)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def _log(phase: str, first: float, second: float) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "n/a")
    print(
        f"smoke run (not a measurement) {phase}: first call {first:.3f} s "
        f"(incl. compile), second call {second:.3f} s, device 0 peak bytes {peak}",
        flush=True,
    )


def _assert_kernel_compiled(text: str, what: str) -> None:
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled program")


# ------------------------------------------------------------ one chip ---
def phase_default_sort(seed: int, n: int) -> None:
    """repro.sort with the planner's default rule on large int32 keys."""
    import numpy as np

    import repro

    for dist in ("uniform", "zipf"):
        x = _keys(seed, n, dist)
        out, first, second = _timed(lambda: repro.sort(x))
        _check(f"sort/{dist}/n={n}", out, np.sort(np.asarray(x)))
        _log(f"sort/{dist}/n={n}", first, second)


def phase_pallas_sort(seed: int, n: int, blocks) -> None:
    """The Pallas kernel behind repro.sort at every swept tile width."""
    import numpy as np

    import repro
    from repro.core.shared_sort import shared_memory_sort

    x = _keys(seed, n, "uniform")
    want = np.sort(np.asarray(x))
    for b in blocks:
        out, first, second = _timed(
            lambda: repro.sort(x, strategy="shared", local_impl="pallas", block_n=b)
        )
        _check(f"pallas_sort/block_n={b}", out, want)
        # the program the call ran: repro.sort's shared plan with these fields
        compiled = shared_memory_sort.lower(
            x, n_threads=8, local_impl="pallas", ascending=True, block_n=b
        ).compile()
        _assert_kernel_compiled(compiled.as_text(), f"pallas_sort/block_n={b}")
        _log(f"pallas_sort/block_n={b}/n={n}", first, second)


def phase_kv(seed: int, n: int, width: int) -> None:
    """Key-value sort with a record payload, and the Pallas stable argsort."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import argsort, sort_kv

    x = _keys(seed, n, "zipf")
    payload = jax.random.randint(jax.random.PRNGKey(seed + 1), (n, width), 0, 1 << 30, jnp.int32)
    x_np, payload_np = np.asarray(x), np.asarray(payload)
    order = np.argsort(x_np, kind="stable")
    (k, v), first, second = _timed(lambda: sort_kv(x, payload))
    _check(f"sort_kv/keys/n={n}", k, x_np[order])
    _check(f"sort_kv/payload/n={n}x{width}", v, payload_np[order])
    _log(f"sort_kv/n={n}x{width}", first, second)
    perm, first, second = _timed(lambda: argsort(x, impl="pallas"))
    _check(f"argsort/pallas/n={n}", perm, order)
    _log(f"argsort/pallas/n={n}", first, second)


def phase_serving(seed: int, batches: int, rows: int, vocab: int, k: int) -> None:
    """SortFrontend serving decode-time top-k: descending argsort per logits
    row, warmed ahead of traffic, no compile once traffic starts."""
    import numpy as np

    from repro.engine import Planner, SortFrontend, SortService, Tenant

    svc = SortService(planner=Planner())
    fe = SortFrontend(svc, tenants=[Tenant("decode")], max_batch=rows, shed_expired=False)
    t0 = time.perf_counter()
    report = fe.warmup(cells=[(vocab, "float32")], kinds=("argsort",), ascending=(False,))
    print(f"smoke run (not a measurement) serving warmup: {report.compiled} "
          f"executables in {time.perf_counter() - t0:.3f} s", flush=True)
    compiles_before = svc.stats.compiles
    logits = np.random.default_rng(seed).standard_normal((batches * rows, vocab), np.float32)
    t0 = time.perf_counter()
    tickets = [fe.submit("decode", row, kind="argsort", ascending=False) for row in logits]
    n_batches = fe.poll()
    results = [np.asarray(t.result()) for t in tickets]
    elapsed = time.perf_counter() - t0
    if n_batches != batches:
        raise AssertionError(f"serving: {n_batches} batches for {len(tickets)} requests")
    compiled_in_traffic = svc.stats.compiles - compiles_before
    if compiled_in_traffic:
        raise AssertionError(f"serving: {compiled_in_traffic} compiles in traffic after warmup")
    for i, (row, got) in enumerate(zip(logits, results)):
        want = np.argsort(-row, kind="stable")
        _check(f"serving/argsort/row={i}", got, want)
        _check(f"serving/topk/row={i}", row[got[:k]], row[want[:k]])
    print(f"smoke run (not a measurement) serving: {len(tickets)} requests in "
          f"{n_batches} batches, {elapsed:.3f} s, 0 compiles in traffic", flush=True)


# --------------------------------------------------------------- 4 chips ---
def phase_model_d(seed: int, n: int, dump_dir: str) -> None:
    """Model D on a (4,) mesh built with jax.make_mesh's defaults."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro
    from repro.engine import sort_kv
    from repro.exchange import compact_slabs

    mesh = jax.make_mesh((4,), ("x",))
    if set(mesh.devices.flat) != set(jax.devices()):
        raise AssertionError("the default (4,) mesh does not hold every device")
    sharding = NamedSharding(mesh, P("x"))
    for dist in ("uniform", "zipf"):
        x = _keys(seed, n, dist, sharding)
        x_np = np.asarray(x)
        order = _stable_argsort(x_np)
        want = x_np[order]
        (slab, valid), first, second = _timed(lambda: repro.sort(x, mesh=mesh, axis="x"))
        out = compact_slabs(slab, valid, n, mesh, "x")
        if len(out.sharding.device_set) != 4:
            raise AssertionError(f"sort/{dist}: result on {len(out.sharding.device_set)} devices")
        _check(f"mesh_sort/{dist}/n={n}", out, want)
        _log(f"mesh_sort/{dist}/n={n}", first, second)
        idx = jax.jit(lambda: jnp.arange(n, dtype=jnp.int32), out_shardings=sharding)()
        (k, v), first, second = _timed(lambda: sort_kv(x, idx, mesh=mesh, axis="x"))
        if len(k.sharding.device_set) != 4:
            raise AssertionError(f"sort_kv/{dist}: result on {len(k.sharding.device_set)} devices")
        _check(f"mesh_sort_kv/{dist}/keys/n={n}", k, want)
        _check(f"mesh_sort_kv/{dist}/payload/n={n}", v, order)
        _log(f"mesh_sort_kv/{dist}/n={n}", first, second)
    for program in ("cluster_sort_local", "cluster_kv_local"):
        texts = [
            open(os.path.join(dump_dir, f)).read()
            for f in os.listdir(dump_dir)
            if f"jit_{program}" in f and f.endswith("after_optimizations.txt")
        ]
        if not texts or not all("all-to-all" in t for t in texts):
            raise AssertionError(f"{program}: compiled program without an all-to-all")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dump = None
    if args.chips == 4:
        # XLA writes each model-D program it compiles here, so the check reads
        # the compiler's own output; only a fresh compile writes it, so this
        # path keeps the persistent cache off
        dump = tempfile.TemporaryDirectory()
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"),
            f"--xla_dump_to={dump.name}",
            "--xla_dump_hlo_as_text",
            "--xla_dump_hlo_module_re=jit_cluster_(sort|kv)_local",
        ]))
    import jax

    devices = _require_tpu(args.chips)
    if dump is None:
        from repro.launch.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        phase_default_sort(args.seed, 1 << 27)
        from repro.engine.planner import PALLAS_BLOCK_SWEEP

        phase_pallas_sort(args.seed, 1 << 24, PALLAS_BLOCK_SWEEP)
        phase_kv(args.seed, 1 << 24, 4)
        phase_serving(args.seed, batches=3, rows=64, vocab=VOCAB, k=50)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
        with dump:
            phase_model_d(args.seed, 1 << 28, dump.name)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
