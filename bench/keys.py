"""Sort keys made on the device from the seed, in one jitted call.

``uniform``: every int32 equally likely. ``zipf``: YCSB's scrambled Zipfian
(Cooper et al., SoCC 2010): item ranks with P(k) ~ 1/k^s drawn by YCSB's
ZipfianGenerator formula (Gray et al.), elementwise, then hashed over int32
so that the hot items are spread over the key range.
"""
from __future__ import annotations

import numpy as np

from harness import seed_key

DISTS = ("uniform", "zipf")


def zipf_constants(items: int, s: float):
    """(zetan, zeta2, eta) of YCSB's ZipfianGenerator."""
    zetan = float(np.sum(np.arange(1, items + 1, dtype=np.float64) ** -s))
    zeta2 = 1.0 + 0.5**s
    eta = (1 - (2 / items) ** (1 - s)) / (1 - zeta2 / zetan)
    return zetan, zeta2, eta


def make_keys(seed: int, n: int, dist: str, *, zipf_items: int = 1 << 20,
              zipf_s: float = 0.99, sharding=None):
    """``n`` int32 keys on the device (laid out by ``sharding`` if given)."""
    import jax
    import jax.numpy as jnp

    if dist not in DISTS:
        raise ValueError(f"key distribution must be one of {DISTS}, got {dist!r}")
    zetan, zeta2, eta = zipf_constants(zipf_items, zipf_s)

    def make(key):
        if dist == "uniform":
            return jax.lax.bitcast_convert_type(jax.random.bits(key, (n,), jnp.uint32), jnp.int32)
        u = jax.random.uniform(key, (n,))
        tail = zipf_items * (eta * u - eta + 1) ** (1 / (1 - zipf_s))
        uz = u * zetan
        rank = jnp.where(uz < 1, 0, jnp.where(uz < zeta2, 1, tail.astype(jnp.int32)))
        scrambled = rank.astype(jnp.uint32) * jnp.uint32(2654435761)
        return jax.lax.bitcast_convert_type(scrambled, jnp.int32)

    x = jax.jit(make, out_shardings=sharding)(seed_key(seed))
    return x.block_until_ready()
