"""The trainer routes rows and reads leaf values without per-row gathers
(``trainer._route``, ``trainer._contrib``).  The gather expressions they
replaced are the oracle here, and every result must match them bit for bit:
routing is integer work, and the leaf values pass by bit pattern."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gbdt import GBDTConfig, trainer
from repro.kernels.histogram import bins_on_lanes

D, N_BINS = 28, 256


def route_gather(pos, level, t_feat, t_thr, t_split, bins_t, n_bins):
    """The routing the trainer did with gathers: the node's feature,
    threshold and split flag by ``pos``, then the row's bin of that feature;
    an unsplit node sends its rows left."""
    f_n, e_n, s_n = t_feat[pos], t_thr[pos], t_split[pos]
    xb = jnp.take_along_axis(bins_t.T, f_n[:, None], axis=1)[:, 0]
    go_left = jnp.where(s_n, xb <= e_n, True)
    return 2 * pos + jnp.where(go_left, 1, 2)


def contrib_gather(leaf_local, leaf_values, lref):
    return leaf_values[lref[leaf_local]]


def _tree(rng, depth: int, n_bins: int = N_BINS):
    """Node tables of a complete tree: about a third of the nodes unsplit
    (their feature and threshold are whatever the slots hold), thresholds
    drawn from ``0``, ``n_bins - 2`` and values between."""
    n_int = 2**depth - 1
    thr = np.concatenate([[0, n_bins - 2], rng.integers(0, n_bins - 1, 6)])
    return (jnp.asarray(rng.integers(0, D, n_int), jnp.int32),
            jnp.asarray(rng.choice(thr, n_int), jnp.int32),
            jnp.asarray(rng.random(n_int) < 0.65))


def _bins(rng, n: int, n_bins: int = N_BINS):
    """(n, D) bins with whole rows and columns at the top bin ``n_bins - 1``
    and at bin 0."""
    bins = rng.integers(0, n_bins, (n, D))
    bins[rng.random(n) < 0.1] = n_bins - 1
    bins[rng.random(n) < 0.05] = 0
    bins[:, 3] = n_bins - 1
    return jnp.asarray(bins, jnp.int32)


def _bits(x):
    """Floats by bit pattern, so -0.0 differs from 0.0; other dtypes as is."""
    a = np.asarray(x)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("n", [1000, 4099])
@pytest.mark.parametrize("depth", range(1, 9))
def test_route_matches_gathers(depth, n):
    """Every level of a depth-``depth`` tree: rows routed down from the root,
    and rows placed on every node of the level, dead and unsplit ones too."""
    rng = np.random.default_rng(100 * depth + n)
    t_feat, t_thr, t_split = _tree(rng, depth)
    bins_t = bins_on_lanes(_bins(rng, n))[:, :n]
    pos = jnp.zeros((n,), jnp.int32)
    for level in range(depth):
        base = 2**level - 1
        anywhere = jnp.asarray(rng.integers(base, 2 * base + 1, n), jnp.int32)
        for p in (pos, anywhere):
            args = (p, level, t_feat, t_thr, t_split, bins_t, N_BINS)
            np.testing.assert_array_equal(
                trainer._route(*args), route_gather(*args))
        pos = route_gather(pos, level, t_feat, t_thr, t_split, bins_t, N_BINS)
    leaves = np.asarray(pos) - (2**depth - 1)
    assert leaves.min() >= 0 and leaves.max() < 2**depth


@pytest.mark.parametrize("n_bins", [2, 17, 256])
def test_route_unsplit_nodes_send_every_bin_left(n_bins):
    """The packed threshold of an unsplit node, ``n_bins - 1``, passes the
    top bin; a split at ``n_bins - 2`` sends only the top bin right."""
    n = 515
    bins = jnp.asarray(np.arange(n)[:, None] % n_bins * np.ones((1, D), int),
                       jnp.int32)
    bins_t = bins_on_lanes(bins)[:, :n]
    pos = jnp.zeros((n,), jnp.int32)
    for split, left in ((False, np.ones(n, bool)),
                        (True, np.arange(n) % n_bins <= n_bins - 2)):
        args = (pos, 0, jnp.asarray([D - 1], jnp.int32),
                jnp.asarray([n_bins - 2], jnp.int32), jnp.asarray([split]),
                bins_t, n_bins)
        got = trainer._route(*args)
        np.testing.assert_array_equal(got, route_gather(*args))
        np.testing.assert_array_equal(got, np.where(left, 1, 2))


@pytest.mark.parametrize("depth", [1, 5, 8])
def test_route_under_vmap_matches_gathers(depth):
    """``train_grid`` batches positions and node tables; the bins are shared."""
    rng = np.random.default_rng(depth)
    n, grid, level = 1000, 3, depth - 1
    tables = [_tree(rng, depth) for _ in range(grid)]
    t_feat, t_thr, t_split = (jnp.stack(t) for t in zip(*tables))
    base = 2**level - 1
    pos = jnp.asarray(rng.integers(base, 2 * base + 1, (grid, n)), jnp.int32)
    bins_t = bins_on_lanes(_bins(rng, n))[:, :n]
    in_axes = (0, None, 0, 0, 0, None, None)
    args = (pos, level, t_feat, t_thr, t_split, bins_t, N_BINS)
    got = jax.vmap(trainer._route, in_axes)(*args)
    want = jax.vmap(route_gather, in_axes)(*args)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [1000, 4099])
@pytest.mark.parametrize("depth", [1, 4, 8])
def test_contrib_matches_gather_bit_for_bit(depth, n):
    """Through the shared table, with -0.0, 0.0, a subnormal, infinities and
    slots the tree never references."""
    rng = np.random.default_rng(depth + n)
    leaf_values = rng.normal(size=64).astype(np.float32)
    leaf_values[:6] = [-0.0, 0.0, 1e-45, -np.inf, np.inf, -3.0e38]
    leaf_values = jnp.asarray(leaf_values)
    lref = jnp.asarray(rng.integers(0, 40, 2**depth), jnp.int32)
    leaf_local = jnp.asarray(rng.integers(0, 2**depth, n), jnp.int32)
    got = trainer._contrib(leaf_local, leaf_values, lref)
    want = contrib_gather(leaf_local, leaf_values, lref)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("task,n_classes", [("binary", 0), ("multiclass", 3)])
def test_trained_forest_matches_gather_routing(monkeypatch, task, n_classes):
    """Whole ``train`` runs, depth 6 under the ToaD penalties: the Forest and
    the predictions equal those of the trainer routing by gathers."""
    rng = np.random.default_rng(11)
    n, d, n_bins = 1234, 6, 16
    bins = jnp.asarray(rng.integers(0, n_bins, (n, d)), jnp.int32)
    score = (bins[:, 0] - bins[:, 1] + (bins[:, 2] > 11) * 4).astype(jnp.float32)
    y = (jnp.digitize(score, jnp.asarray([-3.0, 4.0])) if n_classes
         else score > 0).astype(jnp.float32)
    edges = jnp.tile(jnp.arange(n_bins - 1, dtype=jnp.float32), (d, 1))
    cfg = GBDTConfig(task=task, n_classes=n_classes, n_rounds=3, max_depth=6,
                     min_child_samples=5, toad_penalty_feature=2.0,
                     toad_penalty_threshold=0.5)

    def fit():  # a new function, so each call traces the trainer anew
        return jax.jit(lambda b, t, e: trainer.train(cfg, b, t, e))(bins, y, edges)

    forest, _, aux = fit()
    monkeypatch.setattr(trainer, "_route", route_gather)
    monkeypatch.setattr(trainer, "_contrib", contrib_gather)
    want_forest, _, want_aux = fit()

    assert int(np.sum(forest.is_split)) > 3 * forest.n_ensembles
    for name in ("feature", "thr_bin", "is_split", "leaf_ref", "leaf_values"):
        np.testing.assert_array_equal(
            _bits(getattr(forest, name)), _bits(getattr(want_forest, name)),
            err_msg=name)
    np.testing.assert_array_equal(_bits(aux["preds"]), _bits(want_aux["preds"]))
