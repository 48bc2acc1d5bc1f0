"""Plain reference scorer of a complete-tree ensemble, in numpy.

It reads the benchmark's own model description (``bench/data/forest_pool``)
and imports nothing of the program.  A node sends a row left when it is
unsplit or when ``x[feature] <= edges[feature, thr_bin]``; the children of
node ``i`` are ``2i+1`` and ``2i+2``; tree ``t`` adds its leaf value to
class ``t % C``.  Scores are summed in float64.

``precision="bfloat16"`` is the control: rows, thresholds and leaf values
rounded to bfloat16 and the class sums accumulated in bfloat16, tree by
tree.
"""

from __future__ import annotations

import numpy as np


def leaves(model: dict, x: np.ndarray, cast=None) -> np.ndarray:
    """(n, T) leaf slot that each row reaches in each tree."""
    feature, is_split = model["feature"], model["is_split"]
    T, I = feature.shape
    thr = model["edges"][feature, model["thr_bin"]]               # (T, I)
    if cast is not None:
        x, thr = x.astype(cast), thr.astype(cast)
    n = x.shape[0]
    trees = np.arange(T)[None, :]
    rows = np.arange(n)[:, None]
    idx = np.zeros((n, T), np.int64)
    for _ in range(model["max_depth"]):
        f = feature[trees, idx]
        left = ~is_split[trees, idx] | (x[rows, f] <= thr[trees, idx])
        idx = 2 * idx + np.where(left, 1, 2)
    return idx - I


def score(model: dict, x: np.ndarray, precision: str = "float64") -> np.ndarray:
    """(n, C) ensemble scores of raw float32 rows."""
    C = model["n_classes"]
    T = model["feature"].shape[0]
    if precision == "float64":
        leaf = leaves(model, x)
        v = model["leaf_values"].astype(np.float64)[
            model["leaf_ref"][np.arange(T)[None, :], leaf]]          # (n, T)
        out = v.reshape(len(x), T // C, C).sum(axis=1)
        return out + model["base_score"].astype(np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        bf = ml_dtypes.bfloat16
        leaf = leaves(model, x, cast=bf)
        v = model["leaf_values"].astype(bf)[
            model["leaf_ref"][np.arange(T)[None, :], leaf]]
        v = v.reshape(len(x), T // C, C)
        acc = np.broadcast_to(model["base_score"].astype(bf), (len(x), C)).copy()
        for r in range(T // C):
            acc = (acc + v[:, r, :]).astype(bf)
        return acc.astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")
