"""Back-to-back batch sorts through ``repro.sort``: the closed loop of a user
who sorts one large array after another.

Traffic parameters (``bench/traffic/<mix>.json``): ``keys``, the key
distribution (``uniform`` or ``zipf``, with ``zipf_items`` and ``zipf_s``).
Configuration (``bench/configs/<config>.json``): ``keys`` (how many), ``chips``,
``mesh_axis`` and ``reference``.

One call is ``repro.sort(x)`` on one chip. On a mesh it is
``repro.sort(x, mesh=mesh, axis=axis)`` and then ``compact_slabs`` to the dense
``(n,)`` result, unless the entry point already returns a dense array. Each
call ends in ``block_until_ready`` on that result. Calls run back to back
until the window's seconds have passed, and the last call is finished.
"""
from __future__ import annotations

import time

import numpy as np

import harness
import keys as keygen


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class _Exchanges:
    """Exchange observations the program reports while the window is open."""

    def __init__(self):
        self.open = False
        self.seen = []

    def __call__(self, key, obs):
        if self.open:
            self.seen.append(obs)


class Loop:
    """One cell's inputs, warm-up, window and check.

    ``system``, if given, is called with this loop once its inputs exist and
    returns what is run in the program's place (``x -> sorted x``): the
    control and the fault tests use it.
    """

    def __init__(self, config, traffic, seed, devices, system=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.engine.planner import default_planner

        self.n = int(config["keys"])
        self.reference = harness.module("reference", config["reference"])
        self.mesh, self.axis, sharding = None, None, None
        if int(config["chips"]) > 1:
            self.axis = config["mesh_axis"]
            self.mesh = jax.make_mesh((len(devices),), (self.axis,), devices=devices)
            sharding = NamedSharding(self.mesh, P(self.axis))
        self.keys_per_device = self.n // len(devices)
        self.x = keygen.make_keys(
            seed, self.n, traffic["keys"],
            zipf_items=int(traffic.get("zipf_items", 1 << 20)),
            zipf_s=float(traffic.get("zipf_s", 0.99)),
            sharding=sharding,
        )
        self.exchanges = _Exchanges()
        default_planner().telemetry.subscribe(self.exchanges)
        self.system = system(self) if system is not None else self.program
        self.outs = []
        self.attempted = self.failed = 0
        self.counters = {}

    def program(self, x):
        import repro

        if self.mesh is None:
            return repro.sort(x)
        out = repro.sort(x, mesh=self.mesh, axis=self.axis)
        if isinstance(out, tuple):
            from repro.exchange import compact_slabs

            slab, valid = out
            with _span("bench.compact"):
                out = compact_slabs(slab, valid, self.n, self.mesh, self.axis)
        return out

    def call(self):
        with _span("bench.sort_call"):
            out = self.system(self.x)
        with _span("bench.block"):
            if hasattr(out, "block_until_ready"):
                out.block_until_ready()
        return out

    def warm(self):
        """One whole call: every program the window runs is loaded, and a
        first-call capacity retry on a mesh happens here."""
        self.call()

    def run(self, seconds: float) -> dict:
        self.outs = []
        self.exchanges.open = True
        t0 = time.perf_counter()
        with _span("bench.window"):
            while True:
                self.outs.append(self.call())
                if time.perf_counter() - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        self.exchanges.open = False
        self.attempted = len(self.outs)
        ratios = [o.peak_mean_ratio() for o in self.exchanges.seen]
        self.counters = {
            "calls": len(self.outs),
            "window_s": t1 - t0,
            "keys_per_device": self.keys_per_device,
            "key_bytes": int(np.dtype(self.x.dtype).itemsize),
            "peak_mean_ratio": max(ratios) if ratios else None,
        }
        return {"sort_records_per_s": len(self.outs) * self.n / (t1 - t0)}

    def release(self):
        """Keep only what the check needs: the input on the host, and each
        call's result until it is compared."""
        self.x_host = np.asarray(self.x)
        self.x = None
        self.exchanges.seen = []

    def check(self) -> list:
        want = self.reference.reference(self.x_host)
        bad_keys = 0
        for i, out in enumerate(self.outs):
            self.outs[i] = None
            got = np.asarray(out)
            bad = int(np.count_nonzero(got != want)) if got.shape == want.shape else want.size
            bad_keys += bad
            self.failed += int(bad > 0)
        self.outs = []
        return [("mismatched_keys", bad_keys, 0)]


def control_system(control):
    """Put ``control`` (a reference at lower precision) in the program's
    place: every call computes it on the host from this cell's keys."""

    def make(loop):
        return lambda x: control(np.asarray(x))

    return make
