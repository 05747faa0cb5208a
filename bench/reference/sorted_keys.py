"""Plain reference of a keys-only sort: numpy's ascending sort.

``control`` is the same sort at the precision below the configuration's
int32 keys: keys ordered by their upper 16 bits alone (an int16 sort),
stably. It breaks the configuration's guarantee that every key comes out in
ascending order, and the comparison has to fail it.
"""
from __future__ import annotations

import numpy as np


def reference(x: np.ndarray) -> np.ndarray:
    return np.sort(x)


def control(x: np.ndarray) -> np.ndarray:
    return x[np.argsort((x >> 16).astype(np.int16), kind="stable")]
