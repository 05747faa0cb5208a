"""Back-to-back record sorts through ``repro.engine.sort_kv``: the closed loop
of a user who sorts one large table of records by its key after another.

Traffic parameters (``bench/traffic/<mix>.json``): as ``batch.py``'s (the key
distribution). Configuration (``bench/configs/<config>.json``): ``keys`` (how
many records), ``payload_columns`` and ``payload_dtype`` (the columns each
record carries, named ``c0``, ``c1``, ...), ``chips`` (1) and ``reference``.

One call is ``sort_kv(keys, cols)`` on one chip, then ``block_until_ready``
on every leaf of the result. The window, its spans and the held results are
``batch.py``'s; the check compares keys and every column with the plain
reference.
"""
from __future__ import annotations

import numpy as np

import harness

batch = harness.module("traffic", "batch")


class Loop(batch.Loop):
    """``batch.Loop`` for records. ``system``, if given, is called with this
    loop once its inputs exist and returns what is run in the program's
    place (``(keys, cols) -> (sorted keys, sorted cols)``)."""

    def __init__(self, config, traffic, seed, devices, system=None):
        import jax
        import jax.numpy as jnp

        if int(config["chips"]) != 1:
            raise ValueError("batch_kv runs sort_kv on one chip")
        super().__init__(config, traffic, seed, devices)
        dtype = jnp.dtype(config["payload_dtype"])
        self.names = [f"c{i}" for i in range(int(config["payload_columns"]))]
        shape = (len(self.names), self.n)

        def make(key):
            bits = jax.random.bits(key, shape, jnp.dtype(f"uint{8 * dtype.itemsize}"))
            return list(jax.lax.bitcast_convert_type(bits, dtype))

        cols = jax.jit(make)(jax.random.fold_in(harness.seed_key(seed), 1))
        self.cols = dict(zip(self.names, jax.block_until_ready(cols)))
        self.record_bytes = int(np.dtype(self.x.dtype).itemsize) + len(self.names) * dtype.itemsize
        self.system = system(self) if system is not None else self.program

    def program(self, x, cols):
        from repro.engine import sort_kv

        return sort_kv(x, cols)

    def call(self):
        import jax

        with batch._span("bench.sort_call"):
            out = self.system(self.x, self.cols)
        with batch._span("bench.block"):
            jax.block_until_ready(out)
        return out

    def run(self, seconds: float) -> dict:
        e2e = super().run(seconds)
        self.counters.update(records_per_device=self.n, record_bytes=self.record_bytes)
        return e2e

    def release(self):
        super().release()
        self.cols_host = {name: np.asarray(col) for name, col in self.cols.items()}
        self.cols = None

    def check(self) -> list:
        want_k, want_c = self.reference.reference(self.x_host, self.cols_host)
        bad_keys = bad_payload = 0
        for i, out in enumerate(self.outs):
            self.outs[i] = None
            got_k, got_c = out
            got_k = np.asarray(got_k)
            bk = int(np.count_nonzero(got_k != want_k)) if got_k.shape == want_k.shape else self.n
            differs = np.zeros(self.n, bool)
            for name, want in want_c.items():
                got = np.asarray(got_c[name]) if name in got_c else None
                if got is None or got.shape != want.shape:
                    differs[:] = True
                    break
                differs |= got != want
            bp = int(np.count_nonzero(differs))
            bad_keys += bk
            bad_payload += bp
            self.failed += int(bk > 0 or bp > 0)
        self.outs = []
        return [("mismatched_keys", bad_keys, 0), ("mismatched_payload", bad_payload, 0)]


def control_system(control):
    """Put ``control`` (a reference at lower precision) in the program's
    place: every call computes it on the host from this cell's records."""

    def make(loop):
        return lambda x, cols: control(np.asarray(x), {k: np.asarray(v) for k, v in cols.items()})

    return make
