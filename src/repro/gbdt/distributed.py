"""Data-parallel ToaD training via shard_map (the distributed-LightGBM map).

Rows are sharded over a mesh axis; every shard builds local histograms and
one `psum` per tree level merges them, after which each shard deterministically
commits identical splits.  The model state (forest arrays, used sets, leaf
table) is therefore replicated by construction, and the only collective
traffic is the (nodes × d × bins × 3) histogram — optionally quantized to
int16/int8 (`hist_quant_bits`).

At cluster scale the same function nests under extra mesh axes:
hyperparameter search (the paper's grids) is `vmap`-ed *inside* the
shard_map, giving (grid × data)-parallel training with one fused collective
per level across all grid points.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.gbdt.trainer import GBDTConfig, train


def pad_to_shards(x: np.ndarray, n_shards: int, pad_value=0):
    """Pad rows so the leading dim divides the data axis."""
    n = x.shape[0]
    pad = -n % n_shards
    if pad:
        pad_block = np.full((pad,) + x.shape[1:], pad_value, dtype=x.dtype)
        x = np.concatenate([x, pad_block], axis=0)
    return x


def train_data_parallel(
    cfg: GBDTConfig,
    bins,
    y,
    edges,
    mesh: Mesh,
    axis: str = "data",
    penalty_feature=None,
    penalty_threshold=None,
    forestsize=None,
    hist_quant_bits: int | None = None,
):
    """Train with rows sharded over ``mesh[axis]``.

    Padding rows (if any) must be pre-assigned weight zero by the caller —
    or simply use `pad_to_shards` with a repeated real row, which only
    perturbs histogram counts by the duplicates.  The returned forest and
    history are replicated; `aux['preds']` stays row-sharded.

    ``hist_quant_bits`` is a DEPRECATED alias for
    ``GBDTConfig.hist_quant_bits`` (overrides the config when passed).
    """
    if hist_quant_bits is not None:
        import dataclasses
        import warnings

        warnings.warn(
            "the hist_quant_bits kwarg of train_data_parallel() is "
            "deprecated; set GBDTConfig(hist_quant_bits=...) instead",
            DeprecationWarning,
            stacklevel=2,
        )
        cfg = dataclasses.replace(cfg, hist_quant_bits=int(hist_quant_bits))
    n_shards = mesh.shape[axis]
    assert bins.shape[0] % n_shards == 0, "rows must divide the data axis"

    fn = partial(
        train,
        cfg,
        axis_name=axis,
    )

    def shard_fn(bins, y, edges, pf, pt, fs):
        return fn(bins, y, edges, pf, pt, fs)

    pf = jax.numpy.float32(
        cfg.toad_penalty_feature if penalty_feature is None else penalty_feature
    )
    pt = jax.numpy.float32(
        cfg.toad_penalty_threshold if penalty_threshold is None else penalty_threshold
    )
    fs = jax.numpy.float32(cfg.toad_forestsize if forestsize is None else forestsize)

    # probe output structure to build out_specs: everything replicated except
    # the row-sharded per-sample predictions.
    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(), P()),
        out_specs=_out_specs(cfg, axis),
        check_vma=False,
    )
    return mapped(bins, y, edges, pf, pt, fs)


def _out_specs(cfg: GBDTConfig, axis: str):
    """(forest, history, aux) spec tree: replicated but per-row leaves."""
    from repro.gbdt.forest import Forest

    forest_spec = Forest(
        feature=P(),
        thr_bin=P(),
        is_split=P(),
        leaf_ref=P(),
        leaf_values=P(),
        n_leaf_values=P(),
        n_trees=P(),
        edges=P(),
        base_score=P(),
        n_ensembles=cfg.n_ensembles,
    )
    history_spec = dict(
        bytes=P(), accepted=P(), n_fu=P(), n_thr=P(), n_leaf=P(), n_splits=P()
    )
    aux_spec = dict(
        used_feat=P(),
        used_thr=P(),
        preds=P(axis),
        node_gain=P(),
        leaf_cnt=P(),
        toad_bytes=P(),
    )
    return (forest_spec, history_spec, aux_spec)
