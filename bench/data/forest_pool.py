"""A served ensemble drawn from a fixed model seed, with ToaD-style reuse.

The model is part of the configuration (``model_seed``): every run serves
the same trees and tables, and ``--seed`` draws only the rows scored.  It
has ``n_rounds * n_classes`` complete trees of ``max_depth``, round-major
(tree ``t`` scores class ``t % n_classes``).  Its bin edges are the
quantiles of rows from the covtype generator drawn with the model seed.
Each node's feature is drawn from the features, and its threshold from a
small pool of that feature's edges that every tree shares, as a
ToaD-trained model reuses thresholds; leaf values come from a shared table
of ``leaf_pool`` values.  A node at depth ``>= 2`` is left unsplit with
probability ``p_unsplit`` (it routes left; the leaves under its right
child are unreachable and reference slot 0).

The description is plain numpy, so the reference can score it without the
program.
"""

from __future__ import annotations

import numpy as np

from bench.data.covtype import make_covtype


def quantile_edges(x: np.ndarray, n_bins: int) -> np.ndarray:
    """(d, n_bins - 1) float32 edges, duplicates moved to +inf."""
    q = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    e = np.quantile(x.astype(np.float64), q, axis=0).T
    out = np.full_like(e, np.inf)
    for f in range(e.shape[0]):
        kept = np.unique(e[f])
        out[f, : kept.size] = kept
    return out.astype(np.float32)


def make(config: dict) -> dict:
    """The model description: arrays shaped as the program's Forest."""
    rng = np.random.default_rng(int(config["model_seed"]))
    C = int(config["n_classes"])
    T = int(config["n_rounds"]) * C
    D = int(config["max_depth"])
    I, L = 2**D - 1, 2**D
    x, y = make_covtype(int(config["edge_rows"]), int(config["model_seed"]))
    d = x.shape[1]
    edges = quantile_edges(x, int(config["n_bins"]))
    n_finite = np.isfinite(edges).sum(axis=1)
    P = int(config["thr_pool"])
    size = np.minimum(P, n_finite)
    pool = np.zeros((d, P), np.int64)
    for f in range(d):
        pool[f, : size[f]] = rng.choice(n_finite[f], size=size[f], replace=False)

    usable = np.flatnonzero(n_finite > 0)      # a constant column never splits
    feature = usable[rng.integers(0, usable.size, size=(T, I))].astype(np.int32)
    pick = (rng.random((T, I)) * size[feature]).astype(np.int64)
    thr_bin = pool[feature, pick].astype(np.int32)
    depth = np.floor(np.log2(np.arange(I) + 1)).astype(int)
    is_split = ~((depth >= 2)[None, :] & (rng.random((T, I)) < float(config["p_unsplit"])))
    # a node in the right subtree of an unsplit node is never reached
    for i in range(1, I):
        is_split[:, i] &= _live(is_split, i)
    feature = np.where(is_split, feature, 0).astype(np.int32)
    thr_bin = np.where(is_split, thr_bin, 0).astype(np.int32)

    V = int(config["leaf_pool"])
    leaf_values = (float(config["leaf_scale"]) * rng.standard_normal(V)).astype(np.float32)
    leaf_ref = rng.integers(0, V, size=(T, L)).astype(np.int32)
    leaf_ref[~reachable_leaves(is_split)] = 0
    prior = np.bincount(y.astype(int), minlength=C) / y.size
    base_score = np.log(np.clip(prior, 1e-6, 1.0)).astype(np.float32)
    return dict(feature=feature, thr_bin=thr_bin, is_split=is_split,
                leaf_ref=leaf_ref, leaf_values=leaf_values, edges=edges,
                base_score=base_score, n_classes=C, max_depth=D)


def _live(is_split: np.ndarray, i: int) -> np.ndarray:
    """(T,) whether node ``i`` is reached by some row: no ancestor is an
    unsplit node whose right subtree holds it."""
    live = np.ones(is_split.shape[0], bool)
    while i > 0:
        parent = (i - 1) // 2
        if i == 2 * parent + 2:
            live &= is_split[:, parent]
        i = parent
    return live


def reachable_leaves(is_split: np.ndarray) -> np.ndarray:
    """(T, L) whether each leaf slot can be reached."""
    T, I = is_split.shape
    L = I + 1
    out = np.zeros((T, L), bool)
    for leaf in range(L):
        out[:, leaf] = _live(is_split, I + leaf)
    return out
