"""The plain reference for predict: every tree walked by its raw
thresholds, leaf values summed in float64, the binary sigmoid on top.

`dtype` is for the control only: the same sum with the leaf values and the
running score held in a lower precision.
"""
from __future__ import annotations

import numpy as np

from modeltext import route


def predict_proba(trees: list, X: np.ndarray, dtype=np.float64) -> np.ndarray:
    raw = np.zeros(X.shape[0], dtype=dtype)
    for tree in trees:
        raw = (raw + tree.leaf_value.astype(dtype)[route(tree, X)]).astype(
            dtype)
    return 1.0 / (1.0 + np.exp(-raw.astype(np.float64)))
