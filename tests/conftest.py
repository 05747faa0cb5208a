"""Shared test plumbing: multi-device subprocess runner + common fixtures.

Multi-device tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` because device count
is fixed at first jax import — the main pytest process stays at 1 device
(the dry-run isolation rule).  Import ``run_with_devices`` from here instead
of redefining it per file.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    """Run ``code`` in a fresh interpreter with ``n`` forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


_HLO_OP = re.compile(r"= (?P<shape>.*?) (?P<op>[a-z][a-z-]*)\((?P<rest>.*)$")


def ops_under(hlo: str, scope: str):
    """(opcode, result shape) of every HLO instruction whose ``op_name`` lies
    under ``scope``, fused computations included."""
    out = []
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        op = _HLO_OP.search(line)
        if name and op and scope in name.group(1).split("/"):
            out.append((op.group("op"), op.group("shape")))
    return out


@pytest.fixture
def rng():
    """Deterministic numpy Generator, fresh per test."""
    return np.random.default_rng(0)


@pytest.fixture
def key():
    """Deterministic jax PRNG key, fresh per test."""
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture
def debug_mesh():
    """1-device mesh over whatever the main process exposes (api-level tests)."""
    import jax

    return jax.make_mesh((1,), ("x",))
