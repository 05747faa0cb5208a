"""Plain reference of a served top-k request: numpy's stable descending
argsort of one float32 logits row (ties keep their index order).

``control`` is the same argsort of the row rounded to bfloat16, the
precision below the configuration's float32 logits.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def reference(row: np.ndarray) -> np.ndarray:
    return np.argsort(-row, kind="stable").astype(np.int32)


def control(row: np.ndarray) -> np.ndarray:
    low = row.astype(ml_dtypes.bfloat16).astype(np.float32)
    return np.argsort(-low, kind="stable").astype(np.int32)
