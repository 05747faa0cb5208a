"""End-to-end system tests: the training driver and serving driver run,
converge, checkpoint-restart works, and the dry-run machinery's loop-aware
collective accounting parses real HLO."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


def test_train_driver_end_to_end(tmp_path):
    losses = train_main([
        "--arch", "qwen3-0.6b", "--reduced", "--steps", "12", "--batch", "4",
        "--seq", "32", "--lr", "5e-3", "--ckpt-dir", str(tmp_path),
        "--ckpt-every", "6", "--log-every", "100",
    ])
    assert losses[-1] < losses[0]
    from repro.checkpoint.manager import CheckpointManager

    assert CheckpointManager(str(tmp_path)).latest_step() == 12


def test_serve_driver_end_to_end():
    gen = serve_main([
        "--arch", "qwen3-0.6b", "--reduced", "--batch", "2",
        "--prompt-len", "12", "--gen", "4",
    ])
    assert gen.shape == (2, 4)


def test_serve_driver_topk_queue_matches_direct_path(capsys):
    """--topk-queue (per-row argsort through the one-tenant SortFrontend)
    samples the same tokens as the direct engine.topk path — same seed,
    same model — and serves every row through that tenant."""
    args = ["--arch", "qwen3-0.6b", "--reduced", "--batch", "2",
            "--prompt-len", "12", "--gen", "4"]
    direct = serve_main(args)
    capsys.readouterr()
    queued = serve_main(args + ["--topk-queue"])
    out = capsys.readouterr().out
    assert queued.shape == (2, 4)
    assert (queued == direct).all()
    assert "tenants[decode=8]" in out              # 2 rows x 4 steps
    assert "shed=0" in out


def test_serve_driver_multi_tenant_frontend_matches_direct_path(capsys):
    """--tenants + --warmup (rows through the SLO SortFrontend) samples the
    same tokens as the direct path, serves every row (shed_expired=False on
    the decode path), and pays zero compiles once traffic starts."""
    args = ["--arch", "qwen3-0.6b", "--reduced", "--batch", "2",
            "--prompt-len", "12", "--gen", "4"]
    direct = serve_main(args)
    capsys.readouterr()
    fronted = serve_main(args + ["--tenants", "web:3:0,batch:1:1",
                                 "--warmup", "--slo-ms", "60000", "--stats"])
    out = capsys.readouterr().out
    assert (fronted == direct).all()
    assert "compiled" in out                       # warmup report printed
    assert "slo_misses=0/8" in out                 # 2 rows x 4 steps, all met
    assert "web=4" in out and "batch=4" in out     # round-robin row split
    assert "shed=0" in out


def test_collective_parser_on_real_hlo():
    """Loop-aware accounting: a psum inside a scan counts trip_count times."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.dryrun import collective_bytes

    mesh = jax.make_mesh((1,), ("x",))

    def body(x):
        def inner(c, i):
            return c + (jax.lax.psum(x * i, "x")).sum(), None

        out, _ = jax.lax.scan(inner, 0.0, jnp.arange(5.0))
        return out[None]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    txt = f.lower(jnp.ones((8, 4), jnp.float32)).compile().as_text()
    res = collective_bytes(txt)
    # x*i is loop-varying so the psum must stay inside the while: 5 x 128 bytes
    # (or the compiler removed the trivial 1-device collective entirely — then
    # both counts are zero and the parser must agree)
    assert res["total_bytes"] in (640, 0), res
