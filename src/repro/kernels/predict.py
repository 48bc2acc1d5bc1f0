"""Pallas TPU kernel: inference over the bit-packed ToaD ensemble.

The packed artifact (uint32 node words + global feature / threshold / leaf
tables) stays the deployment format.  The jitted wrapper decodes it once
per call, in XLA, into three per-tree tables the kernel can select from
without gathers:

  node_feat  (T, I)  raw feature index of each internal node (-1 = no split)
  node_thr   (T, I)  its threshold value
  node_leaf  (T, L)  each leaf's value (``leaf_values[leaf_ref]``)

Mosaic lowers no per-lane 1-D gather, so every lookup in the kernel is a
one-hot compare-and-reduce: per depth step each sample lane selects its
node's feature and threshold (compare against a node iota, masked sum over
the node axis), then ``x[feature]`` (compare against a feature iota, masked
sum over the feature axis), and advances ``idx <- 2*idx + 1 + [x > μ]``
(pointer-less traversal).  Masked selects, not multiplies, so a NaN or inf
in an unselected feature never leaks into the sum, and a sum with a single
non-zero term is exact.  The node tables are a few KB per tree block and
stay VMEM-resident; only the sample tile streams from HBM.

Tree batching: the grid is 2-D — (sample tiles × tree blocks) — and each
grid step traverses a block of trees (statically unrolled).  The tree-block
axis is innermost, so each output tile is revisited consecutively and
accumulated in place.  Trees are round-major (``cls = tree % C``) and the
block size is ``TREE_BLOCK`` rounded up to a multiple of C, which makes
each tree's class column static; the leaf value is added into it through a
one-hot class mask.  The node tables are laid out ``(n_blocks,
tree_block, nodes)`` so a block's last two dims equal the array's for any
``tree_block`` (14 for C=7).  Padded trees have no splits and zero leaves,
so they add exactly 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256
TREE_BLOCK = 8
LANES = 128


def _decision_final():
    # lazy: repro.gbdt.__init__ imports the trainer which imports this
    # module, so a top-level import of repro.gbdt.early_exit would cycle
    from repro.gbdt.early_exit import decision_final_mask

    return decision_final_mask


def _tree_block(n_ensembles: int) -> int:
    """TREE_BLOCK rounded up to a multiple of C: static class columns."""
    return -(-TREE_BLOCK // n_ensembles) * n_ensembles


def _node_tables(words, leaf_ref, leaf_values, thr_table, thr_offsets,
                 used_features, *, tidx_bits: int, tree_block: int):
    """Decode packed node words into (n_blocks, tree_block, nodes) tables.

    Returns ``(feat, thr, leaf)`` float32; trees pad to a multiple of
    ``tree_block`` and the node/leaf axes to a multiple of 128 lanes.
    Padding has feature -1 (no split) and leaf value 0.
    """
    T, I = words.shape
    n_fu = used_features.shape[0]
    words = words.astype(jnp.uint32)
    ref = (words >> tidx_bits).astype(jnp.int32)
    if n_fu:
        tix = (words & jnp.uint32((1 << tidx_bits) - 1)).astype(jnp.int32)
        split = ref < n_fu
        safe = jnp.minimum(ref, n_fu - 1)
        feat = jnp.where(split, used_features.astype(jnp.int32)[safe], -1)
        thr = jnp.where(
            split, thr_table.astype(jnp.float32)[thr_offsets[safe] + tix], 0.0)
    else:  # fully-unsplit ensemble: no node ever consults a feature
        feat = jnp.full((T, I), -1, jnp.int32)
        thr = jnp.zeros((T, I), jnp.float32)
    leaf = leaf_values.astype(jnp.float32)[leaf_ref.astype(jnp.int32)]

    t_pad = -T % tree_block

    def blocked(a, fill):
        a = jnp.pad(a, ((0, t_pad), (0, -a.shape[1] % LANES)),
                    constant_values=fill)
        return a.reshape(-1, tree_block, a.shape[1])

    return (blocked(feat.astype(jnp.float32), -1.0), blocked(thr, 0.0),
            blocked(leaf, 0.0))


def _traverse(x, feat_ref, thr_ref, leaf_ref, *, max_depth: int,
              n_ensembles: int):
    """(TILE, C) sum of one tree block's leaf values for the sample tile."""
    tile, d = x.shape
    tree_block, n_nodes = feat_ref.shape[1:]
    n_leaves = leaf_ref.shape[2]
    first_leaf = (1 << max_depth) - 1
    iota = lambda n: jax.lax.broadcasted_iota(jnp.int32, (tile, n), 1)
    node_iota, leaf_iota, cls_iota = (
        iota(n_nodes), iota(n_leaves), iota(n_ensembles))
    feat_iota = iota(d).astype(jnp.float32)

    def select(mask, table):  # per-lane pick of one table column
        return jnp.sum(jnp.where(mask, table, 0.0), axis=1, keepdims=True)

    acc = jnp.zeros((tile, n_ensembles), jnp.float32)
    for k in range(tree_block):
        feats = feat_ref[0, k:k + 1, :]          # (1, n_nodes)
        thrs = thr_ref[0, k:k + 1, :]
        idx = jnp.zeros((tile, 1), jnp.int32)
        for _ in range(max_depth):
            at = node_iota == idx
            f = select(at, feats)                # (TILE, 1); -1 = no split
            xv = select(feat_iota == f, x)
            go_left = (f < 0.0) | (xv <= select(at, thrs))
            idx = 2 * idx + jnp.where(go_left, 1, 2)
        v = select(leaf_iota == idx - first_leaf, leaf_ref[0, k:k + 1, :])
        # tree_block % C == 0, so the class column k % C is static
        acc = acc + jnp.where(cls_iota == k % n_ensembles, v, 0.0)
    return acc


def _kernel(x_ref, feat_ref, thr_ref, leaf_ref, base_ref, out_ref, *,
            max_depth: int, n_ensembles: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.broadcast_to(base_ref[...], out_ref.shape)

    out_ref[...] += _traverse(x_ref[...], feat_ref, thr_ref, leaf_ref,
                              max_depth=max_depth, n_ensembles=n_ensembles)


def _specs(d: int, tables, C: int):
    """BlockSpecs shared by both kernels: sample tile, tree-block tables,
    base scores."""
    table = lambda a: pl.BlockSpec((1,) + a.shape[1:], lambda i, t: (t, 0, 0))
    return [
        pl.BlockSpec((TILE, d), lambda i, t: (i, 0)),
        *(table(a) for a in tables),
        pl.BlockSpec((1, C), lambda i, t: (0, 0)),
    ]


def _prepare(x, words, leaf_ref, leaf_values, thr_table, thr_offsets,
             used_features, base_score, *, tidx_bits: int, n_ensembles: int):
    n = x.shape[0]
    x = jnp.pad(x.astype(jnp.float32), ((0, -n % TILE), (0, 0)))
    tables = _node_tables(
        words, leaf_ref, leaf_values, thr_table, thr_offsets, used_features,
        tidx_bits=tidx_bits, tree_block=_tree_block(n_ensembles))
    base = base_score.astype(jnp.float32).reshape(1, n_ensembles)
    return x, tables, base


@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "tidx_bits", "n_ensembles", "interpret"),
)
def packed_predict(
    x,
    words,
    leaf_ref,
    leaf_values,
    thr_table,
    thr_offsets,
    used_features,
    base_score,
    *,
    max_depth: int,
    tidx_bits: int,
    n_ensembles: int,
    interpret: bool = True,
):
    """(n, d) raw floats -> (n, C) ensemble scores from the packed model."""
    n, d = x.shape
    C = n_ensembles
    if words.shape[0] == 0:  # zero-tree artifact: base scores only
        return jnp.broadcast_to(base_score[None, :].astype(jnp.float32), (n, C))
    x, tables, base = _prepare(
        x, words, leaf_ref, leaf_values, thr_table, thr_offsets,
        used_features, base_score, tidx_bits=tidx_bits, n_ensembles=C)
    out = pl.pallas_call(
        functools.partial(_kernel, max_depth=max_depth, n_ensembles=C),
        grid=(x.shape[0] // TILE, tables[0].shape[0]),
        in_specs=_specs(d, tables, C),
        out_specs=pl.BlockSpec((TILE, C), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], C), jnp.float32),
        interpret=interpret,
    )(x, *tables, base)
    return out[:n]


def _kernel_ee(
    x_ref,
    feat_ref,
    thr_ref,
    leaf_ref,
    base_ref,
    rem_ref,
    slack_ref,
    out_ref,
    exit_ref,
    *,
    max_depth: int,
    n_ensembles: int,
    n_trees: int,
    n_rows: int,
    guard: float,
):
    """Early-exit variant of ``_kernel``: tile retirement between blocks.

    ``exit_ref`` (TILE, 1) int32 is the cross-block carry: the stream
    prefix at which each row became decision-final (sentinel ``T+1`` while
    undecided).  A tile is skipped — mask-and-skip, no partial-row masking
    — once *every* row has exited, so rows that never exit accumulate the
    exact op sequence of the plain kernel (bit-identical scores), and
    already-exited rows in a still-live tile keep accumulating, which is
    harmless: decision-final means no suffix can change their label.
    ``rem_ref`` (n_blocks, C) and ``slack_ref`` (1, C) are SMEM scalars; the
    bound row is picked by the tree-block index.
    """
    i = pl.program_id(0)
    tb = pl.program_id(1)
    C = n_ensembles
    tree_block = feat_ref.shape[1]
    sentinel = n_trees + 1

    @pl.when(tb == 0)
    def _init():
        out_ref[...] = jnp.broadcast_to(base_ref[...], out_ref.shape)
        ridx = i * TILE + jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)
        # padding rows "exit" at 0 so they never hold a tile open
        exit_ref[...] = jnp.where(ridx >= n_rows, 0, sentinel)

    start = tb * tree_block
    done = jnp.max(exit_ref[...]) <= start

    @pl.when(jnp.logical_not(done))
    def _block():
        out_ref[...] += _traverse(x_ref[...], feat_ref, thr_ref, leaf_ref,
                                  max_depth=max_depth, n_ensembles=C)
        rem = [rem_ref[tb, c] for c in range(C)]    # bound after this block
        slack = [slack_ref[0, c] for c in range(C)]
        fin = _decision_final()(out_ref[...], rem, slack, guard,
                                keepdims=True)     # (TILE, 1)
        boundary = jnp.minimum(start + tree_block, n_trees)
        cur = exit_ref[...]
        exit_ref[...] = jnp.where(fin & (cur == sentinel), boundary, cur)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_depth", "tidx_bits", "n_ensembles", "n_rows", "guard",
        "interpret",
    ),
)
def _packed_predict_ee_call(
    x,
    words,
    leaf_ref,
    leaf_values,
    thr_table,
    thr_offsets,
    used_features,
    base_score,
    rem_blocks,
    slack,
    *,
    max_depth: int,
    tidx_bits: int,
    n_ensembles: int,
    n_rows: int,
    guard: float,
    interpret: bool = True,
):
    d = x.shape[1]
    C = n_ensembles
    x, tables, base = _prepare(
        x, words, leaf_ref, leaf_values, thr_table, thr_offsets,
        used_features, base_score, tidx_bits=tidx_bits, n_ensembles=C)
    n_pad = x.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out, exit_tree = pl.pallas_call(
        functools.partial(
            _kernel_ee,
            max_depth=max_depth,
            n_ensembles=C,
            n_trees=words.shape[0],
            n_rows=n_rows,
            guard=guard,
        ),
        grid=(n_pad // TILE, tables[0].shape[0]),
        in_specs=_specs(d, tables, C) + [smem, smem],
        out_specs=[
            pl.BlockSpec((TILE, C), lambda i, t: (i, 0)),
            pl.BlockSpec((TILE, 1), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, C), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        x, *tables, base,
        rem_blocks.astype(jnp.float32),
        slack.astype(jnp.float32).reshape(1, C),
    )
    return out[:n_rows], exit_tree[:n_rows, 0]


def _round_up_f32(x64: np.ndarray) -> np.ndarray:
    """float64 -> float32, rounding toward +inf (keeps bounds sound)."""
    x32 = x64.astype(np.float32)
    low = x32.astype(np.float64) < x64
    return np.where(low, np.nextafter(x32, np.float32(np.inf)), x32)


def packed_predict_early_exit(
    x,
    words,
    leaf_ref,
    leaf_values,
    thr_table,
    thr_offsets,
    used_features,
    base_score,
    bound,
    slack,
    *,
    max_depth: int,
    tidx_bits: int,
    n_ensembles: int,
    guard: float = 0.0,
    min_trees: int = 0,
    interpret: bool = True,
):
    """Early-exit packed inference: (scores, trees_evaluated, exited).

    ``bound`` is the (T+1, C) float64 ``remaining_mass`` table for the
    packed tree order; ``slack`` the (C,) policy slack.  Both are rounded
    *up* when narrowed to the kernel's float32, so narrowing can only make
    exits later, never unsound.  Exit checks before ``min_trees`` are
    disabled by forcing those bound rows to +inf.  ``trees_evaluated`` is
    the per-row decision-final prefix (block-aligned); the kernel's actual
    compute skips whole sample tiles once every row in the tile has
    exited.
    """
    n = x.shape[0]
    C = n_ensembles
    T = words.shape[0]
    if T == 0:
        scores = jnp.broadcast_to(
            base_score[None, :].astype(jnp.float32), (n, C))
        return scores, np.zeros(n, np.int32), np.zeros(n, bool)

    tree_block = _tree_block(C)
    n_tblocks = -(-T // tree_block)
    bound64 = np.asarray(bound, np.float64)
    if bound64.shape != (T + 1, C):
        raise ValueError(f"bound table shape {bound64.shape} != {(T + 1, C)}")
    boundaries = np.minimum((np.arange(n_tblocks) + 1) * tree_block, T)
    rem_blocks = _round_up_f32(bound64[boundaries])
    rem_blocks[boundaries < int(min_trees)] = np.inf
    slack32 = _round_up_f32(np.asarray(slack, np.float64))

    scores, exit_tree = _packed_predict_ee_call(
        x, words, leaf_ref, leaf_values, thr_table, thr_offsets,
        used_features, base_score, jnp.asarray(rem_blocks),
        jnp.asarray(slack32),
        max_depth=max_depth, tidx_bits=tidx_bits, n_ensembles=n_ensembles,
        n_rows=n, guard=float(guard), interpret=interpret,
    )
    exit_tree = np.asarray(exit_tree)
    # a decision at the final boundary saved nothing — not an exit (matches
    # the reference evaluator, which stops checking at p == T)
    exited = exit_tree < T
    return scores, np.minimum(exit_tree, T).astype(np.int32), exited
