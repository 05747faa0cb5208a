"""The program's own trace marks: named scopes on the compiled phases of model
B and model D, host spans on the hot path, and the compile counter.

Named scopes live in the compiled program's ``op_name`` metadata, so the
tests read the HLO text; host spans are read back from a profiler session.
"""
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, ops_under, run_with_devices

OP_NAME = re.compile(r'op_name="([^"]*)"')


def scopes_of(text: str, op: str) -> list:
    """The ``repro.*`` scope of every ``op`` instruction in HLO text."""
    out = []
    for line in text.splitlines():
        if re.search(rf"\s{op}\(", line) and " = " in line:
            m = OP_NAME.search(line)
            parts = m.group(1).split("/") if m else []
            out.append(next((p for p in parts if p.startswith("repro.")), "unscoped"))
    return out


def test_model_b_sorts_carry_tile_sort_and_merge_scopes():
    from repro.core.shared_sort import shared_memory_sort

    x = jnp.zeros((1 << 12,), jnp.int32)
    text = shared_memory_sort.lower(x, n_threads=8).compile().as_text()
    found = scopes_of(text, "sort")
    # one tile sort, then log2(8) merge rounds of 4, 2 and 1 run pairs, each
    # pair sorted on its own (fewer than 8 rows a round)
    assert found.count("repro.tile_sort") == 1, found
    assert found.count("repro.merge") == 4 + 2 + 1, found
    assert set(found) == {"repro.tile_sort", "repro.merge"}


@pytest.mark.parametrize("shape,n_threads", [
    ((1 << 12,), 2), ((1 << 12,), 8), ((1 << 16,), 2), ((1 << 16,), 8), ((16, 1 << 12), 8),
])
def test_model_b_merge_sorts_fill_eight_rows_or_run_1d(shape, n_threads):
    """No merge-round sort has 1-7 rows along its sorted axis: a round with
    fewer than 8 run pairs sorts each as a 1-D array, and a batch of 16 rows
    keeps its batched sorts."""
    from repro.core.shared_sort import shared_memory_sort

    x = jnp.zeros(shape, jnp.int32)
    text = shared_memory_sort.lower(x, n_threads=n_threads).compile().as_text()
    dims = [[int(d) for d in re.search(r"\[([\d,]*)\]", shape_).group(1).split(",")]
            for op, shape_ in ops_under(text, "repro.merge") if op == "sort"]
    assert dims, "no sort carries the repro.merge scope"
    rows = [int(np.prod(d[:-1])) for d in dims]
    assert not [r for d, r in zip(dims, rows) if len(d) > 1 and r < 8], dims
    if len(shape) > 1:
        assert len(dims) == 3 and min(rows) >= 8, dims
    else:
        assert all(len(d) == 1 for d in dims), dims
        assert len(dims) == n_threads - 1, dims


def test_model_d_and_compaction_carry_their_scopes():
    out = run_with_devices(
        """
        import re
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.cluster_sort import _compiled_cluster_sort
        from repro.exchange import slab_geometry
        from repro.exchange.slabs import _compiled_compact

        mesh = jax.make_mesh((4,), ("x",))
        n = 1 << 12
        pb, nb, cap = slab_geometry("splitters", n // 4, 4, 2.0)
        fn = _compiled_cluster_sort(mesh, "x", "splitters", cap, pb, nb, 3, 0, 1, "xla", None)
        x = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=NamedSharding(mesh, P("x")))
        total = 4 * (nb // 4) * cap * 4
        s = jax.ShapeDtypeStruct((total,), jnp.int32, sharding=NamedSharding(mesh, P("x")))
        v = jax.ShapeDtypeStruct((total,), jnp.bool_, sharding=NamedSharding(mesh, P("x")))
        texts = [fn.lower(x).compile().as_text(),
                 _compiled_compact(mesh, "x", n).lower(s, v).compile().as_text()]
        for t in texts:
            print(sorted(set(re.findall(r"/(repro\\.[a-z_]+)", t))))
        """,
        n=4,
    )
    sort_scopes, compact_scopes = out.strip().splitlines()[-2:]
    assert eval(sort_scopes) == ["repro.all_to_all", "repro.counts", "repro.local_sort", "repro.partition"]
    assert eval(compact_scopes) == ["repro.compact"]


def host_events(fn, prefix="repro."):
    """The host spans under ``prefix`` that one call of ``fn`` leaves in a
    profiler session, in order: (name, arguments) each."""
    import tempfile

    from jax.profiler import ProfileData

    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [pb] = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(pb).planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name.startswith(prefix)]


def host_spans(fn, prefix="repro."):
    """Names of the host spans under ``prefix`` that one call of ``fn`` leaves
    in a profiler session, with their counts."""
    names = {}
    for name, _ in host_events(fn, prefix):
        names[name] = names.get(name, 0) + 1
    return names


def test_sort_entry_leaves_a_dispatch_span():
    import repro

    x = jnp.arange(64, dtype=jnp.int32)[::-1]
    repro.sort(x).block_until_ready()
    spans = host_spans(lambda: repro.sort(x).block_until_ready())
    assert spans == {"repro.sort.dispatch": 1}


def test_served_path_leaves_pump_pad_execute_and_copy_back_spans():
    from repro.engine import SortFrontend, Tenant

    fe = SortFrontend(tenants=[Tenant("t")], max_batch=4, shed_expired=False)
    row = np.random.default_rng(0).standard_normal(100).astype(np.float32)

    def one():
        ticket = fe.submit("t", row, kind="argsort", ascending=False)
        fe.pump()
        return ticket.result()

    want = np.argsort(-row, kind="stable")
    np.testing.assert_array_equal(one(), want)  # compiles outside the session
    spans = host_spans(one)
    assert spans == {"repro.frontend.pump": 1, "repro.service.pad": 1,
                     "repro.service.execute": 1, "repro.service.copy_back": 1}


def test_model_d_call_leaves_attempt_wait_and_compact_spans():
    out = run_with_devices(
        """
        import glob, os, tempfile
        import jax, jax.numpy as jnp
        from jax.profiler import ProfileData
        import repro
        from repro.exchange import compact_slabs

        mesh = jax.make_mesh((4,), ("x",))
        x = jax.device_put(jnp.arange(1 << 12, dtype=jnp.int32)[::-1],
                           jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("x")))

        def call():
            slab, valid = repro.sort(x, mesh=mesh, axis="x")
            compact_slabs(slab, valid, x.shape[0], mesh, "x").block_until_ready()

        call()
        d = tempfile.mkdtemp()
        jax.profiler.start_trace(d)
        call()
        jax.profiler.stop_trace()
        [pb] = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        names = sorted(e.name for p in ProfileData.from_file(pb).planes if p.name == "/host:CPU"
                       for l in p.lines for e in l.events if e.name.startswith("repro."))
        print(names)
        """,
        n=4,
    )
    assert eval(out.strip().splitlines()[-1]) == [
        "repro.compact.dispatch", "repro.exchange.attempt", "repro.exchange.overflow_wait",
        "repro.sort.dispatch",
    ]


def test_compile_counter_event_name_is_jaxs():
    from jax._src import dispatch

    from repro.launch.compile_cache import BACKEND_COMPILE_EVENT

    assert BACKEND_COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT


def test_compile_counter_counts_a_recompile_in_a_traced_window_and_not_a_warm_call(tmp_path):
    from repro.launch.compile_cache import compile_count

    f = jax.jit(lambda a: jnp.sort(a) + 1)
    warm, fresh = np.arange(32, dtype=np.int32), np.arange(48, dtype=np.int32)
    f(warm).block_until_ready()

    def traced(fn):
        before = compile_count(traced=True)
        jax.profiler.start_trace(str(tmp_path / f"t{before}"))
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return compile_count(traced=True) - before

    assert traced(lambda: f(warm).block_until_ready()) == 0
    assert traced(lambda: f(fresh).block_until_ready()) == 1
    # outside a profiler session a compile counts in the total only
    before, before_traced = compile_count(), compile_count(traced=True)
    f(np.arange(80, dtype=np.int32)).block_until_ready()
    assert (compile_count() - before, compile_count(traced=True) - before_traced) == (1, 0)


def test_cache_loads_count_and_a_changed_scope_is_not_served_stale(tmp_path):
    """A program read back from the persistent cache counts as one; the same
    program under another scope name compiles anew, so its profile names the
    new scope and not the cached build's."""
    code = textwrap.dedent("""
        import sys
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import compile_count, enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        def f(a):
            with jax.named_scope(sys.argv[1]):
                return jnp.sort(a) * 3
        spec = jax.ShapeDtypeStruct((64,), jnp.int32)
        before = compile_count()
        c = jax.jit(f).lower(spec).compile()
        print(compile_count() - before, sys.argv[1] in c.as_text())
    """)
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_"))}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                "PYTHONPATH": os.path.join(REPO, "src")})

    def run(scope):
        out = subprocess.run([sys.executable, "-c", code, scope], env=env, capture_output=True,
                             text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
        return out.stdout.split()

    def entries():
        return sorted(p for p in os.listdir(tmp_path) if "jit_f" in p)

    assert run("repro.first") == ["1", "True"]
    written = entries()
    assert len(written) == 1
    assert run("repro.first") == ["1", "True"]  # read back from the cache
    assert entries() == written
    assert run("repro.second") == ["1", "True"]  # metadata is in the key: not served stale
    assert len(entries()) == 2
