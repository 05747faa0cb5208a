"""Back-to-back Sort Benchmark record sorts through ``repro.engine.sort_kv``
with a multi-word key: the closed loop of a node of a distributed sort that
sorts one chip's share of 100-byte records by their 10-byte key after
another.

Traffic parameters (``bench/traffic/<mix>.json``): as ``batch.py``'s (the
distribution of the item behind key bytes 0-7). Configuration
(``bench/configs/<config>.json``): ``records`` (how many), ``record_words``
(uint32 words a record), ``key_bytes``, ``chips`` (1) and ``reference``.

A record is ``record_words`` big-endian uint32 words ``w0``, ``w1``, ...,
held as one 1-D array per word. ``w0`` is ``bench/keys.py``'s key (a hash of
the item's rank) and ``w1`` a second fixed hash of it (murmur3's finalizer),
so records of one item share key bytes 0-7. The other words are random from
the seed: key bytes 8-9 (the upper half of ``w2``) and the payload.

One call derives the key words ``(w0, w1, w2 & 0xFFFF0000)`` from the table,
runs ``sort_kv(key words, table)`` and ends in ``block_until_ready`` on the
sorted table. Unlike ``batch.py``'s window, this one holds at most two
results: the one before last is dropped before the next call starts, so at
most one held result and the call in flight share the device. The check
compares the last two calls' tables with the plain reference.
"""
from __future__ import annotations

import time

import numpy as np

import harness

batch = harness.module("traffic", "batch")
batch_kv = harness.module("traffic", "batch_kv")

HELD = 2  # results the window keeps for the check


def _fmix32(h):
    """murmur3's 32-bit finalizer: a fixed bijection of uint32."""
    import jax.numpy as jnp

    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _key_low(w2):
    """Key bytes 8-9: the upper half of word 2."""
    import jax.numpy as jnp

    return w2 & jnp.uint32(0xFFFF0000)


class Loop(batch_kv.Loop):
    """``batch_kv.Loop`` for whole records keyed by several words. The table
    is made here (``batch_kv``'s columns are not). ``system``, if given, is
    called with this loop once its inputs exist and returns what is run in
    the program's place (``table -> sorted table``)."""

    def __init__(self, config, traffic, seed, devices, system=None):
        import jax
        import jax.numpy as jnp

        if int(config["chips"]) != 1:
            raise ValueError("batch_rec runs sort_kv on one chip")
        n, words = int(config["records"]), int(config["record_words"])
        # the Zipf keys of batch.py become word 0
        batch.Loop.__init__(self, {**config, "keys": n}, traffic, seed, devices)

        def make(x, key):
            w0 = jax.lax.bitcast_convert_type(x, jnp.uint32)
            rest = jax.random.bits(key, (words - 2, n), jnp.uint32)
            return [w0, _fmix32(w0)] + list(rest)

        table = jax.jit(make)(self.x, jax.random.fold_in(harness.seed_key(seed), 1))
        self.x = None
        self.names = [f"w{i}" for i in range(words)]
        self.cols = dict(zip(self.names, jax.block_until_ready(table)))
        self.key_low = jax.jit(_key_low)
        self.record_bytes = words * 4
        self.key_bytes = int(config["key_bytes"])
        # what sort_kv's sorts move a record: the three key words and the
        # index, the inverse's order and index, then each word beside its rank
        self.carried_bytes = 4 * (3 + 1) + 4 * 2 + words * 4 * 2
        self.system = system(self) if system is not None else self.program

    def key_words(self, table):
        return table["w0"], table["w1"], self.key_low(table["w2"])

    def program(self, table):
        from repro.engine import sort_kv

        return sort_kv(self.key_words(table), table)[1]

    def call(self):
        import jax

        with batch._span("bench.sort_call"):
            out = self.system(self.cols)
        with batch._span("bench.block"):
            jax.block_until_ready(out)
        return out

    def run(self, seconds: float) -> dict:
        self.outs, calls = [], 0
        t0 = time.perf_counter()
        with batch._span("bench.window"):
            while True:
                del self.outs[: 1 - HELD]
                self.outs.append(self.call())
                calls += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        self.attempted = calls
        self.counters = {
            "calls": calls,
            "window_s": t1 - t0,
            "records_per_device": self.n,
            "record_bytes": self.record_bytes,
            "key_bytes": self.key_bytes,
            "carried_bytes": self.carried_bytes,
        }
        return {"sort_records_per_s": calls * self.n / (t1 - t0)}

    def release(self):
        self.table_host = {name: np.asarray(word) for name, word in self.cols.items()}
        self.cols = None

    def check(self) -> list:
        want = self.reference.reference(self.table_host)
        want_key = self.reference.key_words(want)
        bad_keys = bad_payload = 0
        self.counters["calls_checked"] = len(self.outs)
        for i, out in enumerate(self.outs):
            self.outs[i] = None
            got = {name: np.asarray(out[name]) for name in self.names if name in out}
            if set(got) != set(self.names) or any(g.shape != (self.n,) for g in got.values()):
                bk = bp = self.n  # a word or a record missing: every position counts
            else:
                key_differs = np.zeros(self.n, bool)
                for g, w in zip(self.reference.key_words(got), want_key):
                    key_differs |= g != w
                payload_differs = ((got["w2"] ^ want["w2"]) & ~self.reference.KEY_HALF_MASK) != 0
                for name in self.names[3:]:
                    payload_differs |= got[name] != want[name]
                bk = int(np.count_nonzero(key_differs))
                bp = int(np.count_nonzero(payload_differs))
            bad_keys += bk
            bad_payload += bp
            self.failed += int(bk > 0 or bp > 0)
        self.outs = []
        return [("mismatched_keys", bad_keys, 0), ("mismatched_payload", bad_payload, 0)]


def control_system(control):
    """Put ``control`` (a reference at lower precision) in the program's
    place: every call computes it on the host from this cell's table."""

    def make(loop):
        return lambda table: control({name: np.asarray(w) for name, w in table.items()})

    return make
