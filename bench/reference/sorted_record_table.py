"""Plain reference of a Sort Benchmark record sort: numpy's stable
``lexsort`` by the 10-byte key, applied to every word of every record.

A table is ``{"w0": ..., "w24": ...}``, one uint32 array per word of the
100-byte records, each word four big-endian bytes. The key is bytes 0-9:
``w0``, ``w1`` and the upper half of ``w2``. Comparing those three unsigned
words lexicographically is comparing the key bytes in memcmp order.

``control`` is the same sort one precision below the configuration's 10-byte
key: records ordered by bytes 0-7 (``w0``, ``w1``) alone, stably. Where the
first 8 bytes tie, bytes 8-9 come out in input order, so it breaks the
guarantee that keys come out in memcmp order, and the comparison has to fail
it.
"""
from __future__ import annotations

import numpy as np

KEY_HALF_MASK = np.uint32(0xFFFF0000)  # bytes 8-9 of the key, in word 2


def key_words(table: dict):
    """``(w0, w1, w2k)``: the 10-byte key as three unsigned words."""
    return table["w0"], table["w1"], table["w2"] & KEY_HALF_MASK


def _take(order: np.ndarray, table: dict) -> dict:
    return {name: word[order] for name, word in table.items()}


def reference(table: dict) -> dict:
    w0, w1, w2k = key_words(table)
    return _take(np.lexsort((w2k, w1, w0)), table)


def control(table: dict) -> dict:
    w0, w1, _ = key_words(table)
    return _take(np.lexsort((w1, w0)), table)
