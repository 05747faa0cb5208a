"""Plain reference of a record sort: numpy's stable ascending argsort of the
keys, applied to the keys and to every payload column.

``control`` is the same sort at the precision below the configuration's
int32 keys: records ordered by the upper 16 bits of their key alone (an
int16 sort), stably. It breaks the configuration's guarantee that keys come
out in ascending order, and the comparison has to fail it.
"""
from __future__ import annotations

import numpy as np


def _take(order: np.ndarray, keys: np.ndarray, cols: dict):
    return keys[order], {name: col[order] for name, col in cols.items()}


def reference(keys: np.ndarray, cols: dict):
    return _take(np.argsort(keys, kind="stable"), keys, cols)


def control(keys: np.ndarray, cols: dict):
    return _take(np.argsort((keys >> 16).astype(np.int16), kind="stable"), keys, cols)
