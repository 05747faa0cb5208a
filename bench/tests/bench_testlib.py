"""Helpers of the benchmark's CPU tests: drive a cell at a size a test can
hold, in a fresh interpreter, so that no test touches the JAX state of the
process that runs the others."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

# sizes a test can hold, one per configuration
SMALL = {
    "sort-int32-2p27-1chip": {"keys": 1 << 14},
    "sort-int32-2p28-4chip": {"keys": 1 << 14},
    "decode-topk-qwen3-v151936": {"vocab_size": 3000, "pool_rows": 8, "max_batch": 8, "sample": 16},
}
SMALL_TRAFFIC = {"decode_steps": {"rate_per_s": 10, "rows_per_arrival": 4, "drain_s": 10}}


def clean_env(tmp_path, devices: int = 1) -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_"))}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "TMPDIR": str(tmp_path),
    })
    return env


def drive(tmp_path, workload: str, body: str = "", *, system: str = "None",
          seconds: float = 0.5, seed: int = 3, devices: int = 1) -> dict:
    """Run ``workload`` once at its small size in a fresh interpreter, past
    the look for a chip; ``body`` runs first (a fault planted underneath),
    and ``system`` is an expression for the loop's ``system`` argument.
    Returns the result line."""
    import harness

    cell = harness.entry(harness.benchmark(candidates=True)["workloads"], workload, "workload")
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {BENCH!r})
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        import numpy as np
        import run, control
    """) + textwrap.dedent(body) + textwrap.dedent(f"""
        result, _ = run.run_cell({workload!r}, {seed}, {seconds}, require_chip=False,
                                 system={system},
                                 config_overrides={SMALL[cell["config"]]!r},
                                 traffic_overrides={SMALL_TRAFFIC.get(cell["traffic"], {})!r},
                                 candidates=True)
        print(json.dumps(result))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=clean_env(tmp_path, devices),
                         capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
