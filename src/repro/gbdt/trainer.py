"""Fixed-shape, jit-able histogram GBDT with the ToaD penalties.

Faithful pieces (paper Sec. 3.1 / App. A):
  * split gain `Δ_l = Δ − s_f·ι − s_t·ξ` against *global* used-feature /
    used-threshold sets that persist across trees, classes and rounds;
  * within a level, splits commit node-sequentially, so a feature paid for
    by an earlier node is free for every later node (greedy semantics);
  * global shared leaf-value table with reuse (Sec. 3.2.2), fixed capacity,
    exact-match (optionally quantized) reuse inside jit;
  * `toad_forestsize`: the exact ToaD stream size (core.memory.toad_bits)
    is evaluated inside the jitted round loop; a round that would overflow
    the budget is reverted and training stops — LightGBM-ToaD's
    `toad_forestsize` behaviour;
  * multiclass = one ensemble per class, trees stored round-major.

Adaptation (recorded in DESIGN.md): growth is level-wise over complete
trees rather than LightGBM's leaf-wise queue.  A leaf whose best penalized
gain was non-positive is reconsidered on later levels through its left
child (used-sets evolve, so a split may become worthwhile), which preserves
the greedy always-positive-gain property.

Everything is fixed-shape, so the whole trainer can be `jax.vmap`-ed over
(ι, ξ, forestsize) — the paper's 676-model grid searches are a single
batched jit call (see benchmarks/fig7_multivariate.py).

Histogram hot path (§Perf): per level the (nodes, d, B, 3) histograms come
from the pluggable ``repro.kernels.ops.build_histogram`` dispatch
(``hist_method``: auto = fused matmul path on CPU/GPU, Pallas MXU kernel on
TPU; "ref" keeps the segment-sum oracle).  At every level >= 1 only *left*
children are histogrammed and each right child is derived from the cached
parent level as ``parent − left`` (LightGBM's sibling subtraction,
``hist_subtract``) — half the histogram work and, data-parallel, half the
per-level all-reduce bytes (with quantized collectives the subtraction is
disabled so per-level quantization error cannot compound through derived
right children).  ``hist_dtype="bf16"`` is a numerics-ablation knob: it
rounds the g/h channels to bf16 before accumulation, but accumulation is
always fp32 and the count channel is never rounded, so
``min_child_samples``/``min_child_weight`` gating stays exact.  (It no
longer shrinks memory or wire bytes — use ``hist_quant_bits`` for cheap
histogram collectives.)

Routing without gathers (§Perf): XLA on the TPU runs a per-row gather from
a small table at about 8 ns a row, so at 10.5M rows the node-table and bin
gathers took 3.9 s of a 6.3 s round (TPU v5e, depth 8).  ``_route`` instead
has each row compare its node id with the level's nodes and select one
packed (feature, threshold) word, then compare that feature with ``0..d-1``
over the ``(d, n)`` samples-on-lanes bins and select its bin.  The bins view
is the histogram kernel's own (``bins_on_lanes``): on the Pallas path XLA
keeps one copy for both.
``_contrib`` selects each row's leaf value from the tree's small value table
the same way.  Both are exact, so the trees are those gathers would grow;
the work is one read of the bins per level, 0.027 s a round on the v5e.

Device phases (``PHASES``): every operation of a round runs under one
``jax.named_scope``, which XLA keeps in each instruction's
``metadata={op_name=...}``, so a profiler trace attributes device time by
phase.  A scope is trace-time metadata: the compiled program is the same.

  toad.grad    gradients and hessians; the (n, 3) histogram channels
  toad.hist    histogram build (Pallas call ``histogram`` and its wrapper's
               pads, transposes and part sums), sibling subtraction,
               cross-shard reduction
  toad.split   cumulative sums, node totals, gains, validity
  toad.commit  the sequential per-node commit loop
  toad.route   routing samples to children by compare-and-select (no
               gathers), dead-node bookkeeping
  toad.leaf    leaf statistics, leaf values, the shared-table insert loop,
               per-sample contributions
  toad.update  tree writes, prediction update, ToaD size, acceptance, state
               merge, per-round history
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.memory import toad_bits
from repro.gbdt.forest import Forest
from repro.gbdt.losses import make_loss
from repro.kernels.histogram import bins_on_lanes
from repro.kernels.ops import build_histogram, sibling_subtraction_histograms

#: the ``jax.named_scope`` of each phase of a round (module docstring)
GRAD, HIST, SPLIT, COMMIT, ROUTE, LEAF, UPDATE = PHASES = (
    "toad.grad", "toad.hist", "toad.split", "toad.commit", "toad.route",
    "toad.leaf", "toad.update")


@dataclasses.dataclass(frozen=True)
class GBDTConfig:
    task: str = "regression"          # regression | binary | multiclass
    n_classes: int = 0
    n_rounds: int = 64                # K boosting rounds (trees per class)
    max_depth: int = 4
    learning_rate: float = 0.1
    reg_lambda: float = 1.0           # λ
    gamma: float = 0.0                # γ per-leaf complexity
    min_child_weight: float = 1e-3
    min_child_samples: int = 1
    toad_penalty_feature: float = 0.0   # ι
    toad_penalty_threshold: float = 0.0 # ξ
    toad_forestsize: float = 0.0      # byte budget; 0 = unlimited
    leaf_capacity: int = 4096         # global leaf-value table capacity
    leaf_match_tol: float = 0.0       # reuse tolerance (0 = exact match)
    leaf_quant: float = 0.0           # optional leaf rounding grid
    cegb_penalty_split: float = 0.0   # CEGB (Peter et al.) per-split cost × n_node/n
    hist_dtype: str = "f32"           # f32 | bf16 g/h rounding (numerics
                                      # ablation); counts always exact f32
    hist_method: str = "auto"         # auto | ref | fused | pallas (kernels.ops)
    hist_subtract: bool = True        # sibling subtraction at levels >= 1
    hist_quant_bits: int = 0          # 0 = exact fp32 histogram all-reduce;
                                      # 8/16 = quantized collectives
                                      # (data-parallel training only)

    @property
    def n_ensembles(self) -> int:
        return self.n_classes if self.task == "multiclass" else 1


def _select(idx, choices):
    """``choices[idx[i]]`` (a ``(k,)`` table) or ``choices[idx[i], i]`` (a
    ``(k, n)`` array) for every row ``i``, without a gather: each row compares
    its id with ``0..k-1`` and keeps the one match, so the sum is exact.
    ``idx``: (n,) int32 in ``[0, k)``; ``choices``: int32."""
    if choices.ndim == 1:
        choices = choices[:, None]
    hit = jnp.arange(choices.shape[0], dtype=idx.dtype)[:, None] == idx[None, :]
    return jnp.sum(jnp.where(hit, choices, 0), axis=0)


def _route(pos, level, t_feat, t_thr, t_split, bins_t, n_bins):
    """Each row's child at ``level`` (``2*pos + 1`` left, ``+ 2`` right),
    without a gather.

    One int32 word per node of the level packs its feature over its
    threshold's bits; an unsplit node's threshold ``n_bins - 1`` sends every
    bin left.  Each row selects its node's word by ``pos``, then its bin of
    that feature from ``bins_t``, the ``(>= d, n)`` bins with samples on
    lanes.
    """
    base = 2**level - 1
    lvl = slice(base, 2 * base + 1)
    thr_bits = max(n_bins - 1, 1).bit_length()
    thr = jnp.where(t_split[lvl], t_thr[lvl], n_bins - 1)
    word = _select(pos - base, (t_feat[lvl] << thr_bits) | thr)
    xb = _select(word >> thr_bits, bins_t)
    go_left = xb <= (word & ((1 << thr_bits) - 1))
    return 2 * pos + jnp.where(go_left, 1, 2)


def _contrib(leaf_local, leaf_values, lref):
    """``leaf_values[lref[leaf_local]]`` without a per-row gather: the tree's
    small table first, then a select by bit pattern, so every value, -0.0
    included, passes unchanged."""
    table = jax.lax.bitcast_convert_type(leaf_values[lref], jnp.int32)
    return jax.lax.bitcast_convert_type(_select(leaf_local, table), jnp.float32)


def _grow_tree(cfg: GBDTConfig, bins, g, h, edges, state, reduce_fn=None):
    """Grow one complete tree level-wise.  Returns tree arrays + new state.

    state: (used_feat, used_thr, leaf_values, n_leaf, pen_f, pen_t)
    reduce_fn: cross-shard histogram reduction (data-parallel training);
      identity when None.
    """
    used_feat, used_thr, leaf_values, n_leaf, pen_f, pen_t = state
    shard_reduce = reduce_fn  # None = single-shard training
    reduce_fn = reduce_fn or (lambda x: x)
    n, d = bins.shape
    E = edges.shape[1]
    B = E + 1
    D = cfg.max_depth
    I = 2**D - 1
    L = 2**D
    lam = cfg.reg_lambda
    with jax.named_scope(SPLIT):
        valid_edge = jnp.isfinite(edges)  # (d, E)

    with jax.named_scope(COMMIT):
        t_feat = jnp.zeros((I,), jnp.int32)
        t_thr = jnp.zeros((I,), jnp.int32)
        t_split = jnp.zeros((I,), bool)
        t_gain = jnp.zeros((I,), jnp.float32)  # recorded for CCP post-pruning
        n_splits = jnp.zeros((), jnp.int32)
    with jax.named_scope(ROUTE):
        pos = jnp.zeros((n,), jnp.int32)
        dead = jnp.zeros((1,), bool)
        # (d_pad, n) bins, samples on lanes: the histogram kernel's own view
        bins_t = bins_on_lanes(bins)[:, :n]

    # Loop-invariant histogram inputs, hoisted out of the level loop.  bins
    # keep their storage dtype (int8 preferred: 4x less HBM traffic than
    # int32 — §Perf); the upcast fuses into each method's id computation.
    # hist_dtype="bf16" rounds g/h here (numerics ablation only);
    # accumulation stays fp32 and the count channel is exact regardless.
    hdt = jnp.bfloat16 if cfg.hist_dtype == "bf16" else jnp.float32
    with jax.named_scope(GRAD):
        gh = jnp.stack(
            [
                g.astype(hdt).astype(jnp.float32),
                h.astype(hdt).astype(jnp.float32),
                jnp.ones((n,), jnp.float32),
            ],
            axis=-1,
        )  # (n, 3)
    hist_method = None if cfg.hist_method == "auto" else cfg.hist_method
    parent_hist = None

    for level in range(D):
        n_nodes = 2**level
        base_idx = n_nodes - 1

        # --- gradient/hessian/count histograms: (nodes, d, B, 3) -----------
        # data-parallel training: one all-reduce of the histogram per level
        # (left children only under sibling subtraction) — the
        # distributed-LightGBM pattern.
        with jax.named_scope(HIST):
            node_local = pos - base_idx  # (n,) in [0, n_nodes)
            if level >= 1 and cfg.hist_subtract:
                hist = sibling_subtraction_histograms(
                    bins, gh, node_local, parent_hist, n_bins=B,
                    method=hist_method, reduce_fn=shard_reduce,
                )
            else:
                hist = reduce_fn(
                    build_histogram(
                        bins, gh, node_local, n_nodes=n_nodes, n_bins=B,
                        method=hist_method,
                    )
                )
        parent_hist = hist

        # --- standard gain for every (node, feature, edge) ------------------
        with jax.named_scope(SPLIT):
            G, H, CNT = hist[..., 0], hist[..., 1], hist[..., 2]
            GL = jnp.cumsum(G, axis=-1)[..., :E]
            HL = jnp.cumsum(H, axis=-1)[..., :E]
            CL = jnp.cumsum(CNT, axis=-1)[..., :E]
            # node totals are identical across features — reduce feature 0 once
            totG = jnp.sum(G[:, 0, :], axis=-1)  # (nodes,)
            totH = jnp.sum(H[:, 0, :], axis=-1)
            totC = jnp.sum(CNT[:, 0, :], axis=-1)
            GR = totG[:, None, None] - GL
            HR = totH[:, None, None] - HL
            CR = totC[:, None, None] - CL
            gain = (
                0.5
                * (
                    GL**2 / (HL + lam)
                    + GR**2 / (HR + lam)
                    - (totG**2 / (totH + lam))[:, None, None]
                )
                - cfg.gamma
            )
            valid = (
                (CL >= cfg.min_child_samples)
                & (CR >= cfg.min_child_samples)
                & (HL >= cfg.min_child_weight)
                & (HR >= cfg.min_child_weight)
                & valid_edge[None, :, :]
            )

        # --- sequential (greedy) commit: later nodes see earlier nodes' ----
        # --- newly used features/thresholds, per the paper's used sets  ----
        def commit(j, carry):
            used_feat, used_thr, t_feat, t_thr, t_split, t_gain, n_splits = carry
            pen = pen_f * (~used_feat[:, None]) + pen_t * (~used_thr)
            # CEGB (Peter et al. 2017): per-split evaluation cost scaled by
            # the fraction of samples that must traverse this node.
            split_cost = cfg.cegb_penalty_split * totC[j] / n
            eff = jnp.where(valid[j], gain[j] - pen - split_cost, -jnp.inf)
            flat = jnp.argmax(eff)
            f = (flat // E).astype(jnp.int32)
            e = (flat % E).astype(jnp.int32)
            ok = (eff.reshape(-1)[flat] > 0.0) & ~dead[j]
            node = base_idx + j
            t_feat = t_feat.at[node].set(jnp.where(ok, f, t_feat[node]))
            t_thr = t_thr.at[node].set(jnp.where(ok, e, t_thr[node]))
            t_split = t_split.at[node].set(ok | t_split[node])
            t_gain = t_gain.at[node].set(
                jnp.where(ok, gain[j].reshape(-1)[flat], t_gain[node])
            )
            used_feat = used_feat.at[f].set(used_feat[f] | ok)
            used_thr = used_thr.at[f, e].set(used_thr[f, e] | ok)
            return used_feat, used_thr, t_feat, t_thr, t_split, t_gain, n_splits + ok

        with jax.named_scope(COMMIT):
            used_feat, used_thr, t_feat, t_thr, t_split, t_gain, n_splits = (
                jax.lax.fori_loop(
                    0,
                    n_nodes,
                    commit,
                    (used_feat, used_thr, t_feat, t_thr, t_split, t_gain, n_splits),
                )
            )

        # --- route samples (unsplit nodes route left) -----------------------
        with jax.named_scope(ROUTE):
            pos = _route(pos, level, t_feat, t_thr, t_split, bins_t, B)

            # left child of a live unsplit node stays live (may split later
            # once penalties have been paid by other nodes); right child is
            # dead.
            split_lvl = jax.lax.dynamic_slice_in_dim(t_split, base_idx, n_nodes)
            dead = jnp.stack([dead, dead | ~split_lvl], axis=1).reshape(-1)

    # ---------------- leaves ------------------------------------------------
    with jax.named_scope(LEAF):
        leaf_local = pos - (2**D - 1)
        leaf_stats = reduce_fn(
            jax.ops.segment_sum(
                jnp.stack([g, h, jnp.ones_like(g)], axis=-1), leaf_local,
                num_segments=L,
            )
        )
        G_leaf, H_leaf, C_leaf = leaf_stats[:, 0], leaf_stats[:, 1], leaf_stats[:, 2]
        raw_v = jnp.where(
            C_leaf > 0, -cfg.learning_rate * G_leaf / (H_leaf + lam), 0.0
        ).astype(jnp.float32)
        if cfg.leaf_quant > 0:
            raw_v = jnp.round(raw_v / cfg.leaf_quant) * cfg.leaf_quant
        reachable = ~dead  # (L,) leaf-level liveness

        V = leaf_values.shape[0]

        def insert(j, carry):
            leaf_values, n_leaf, lref = carry
            v = raw_v[j]
            valid_slot = jnp.arange(V) < n_leaf
            diffs = jnp.where(valid_slot, jnp.abs(leaf_values - v), jnp.inf)
            best = jnp.argmin(diffs).astype(jnp.int32)
            match = diffs[best] <= cfg.leaf_match_tol
            can_append = n_leaf < V
            reach = reachable[j]
            use_new = reach & ~match & can_append
            ref = jnp.where(match | ~can_append, best, n_leaf)
            ref = jnp.where(reach, ref, 0).astype(jnp.int32)
            appended = leaf_values.at[n_leaf].set(v)
            leaf_values = jnp.where(use_new, appended, leaf_values)
            n_leaf = n_leaf + use_new.astype(jnp.int32)
            return leaf_values, n_leaf, lref.at[j].set(ref)

        leaf_values, n_leaf, lref = jax.lax.fori_loop(
            0, L, insert, (leaf_values, n_leaf, jnp.zeros((L,), jnp.int32))
        )

        # per-sample contribution of this tree (through the shared table, so
        # any lossy reuse is reflected in subsequent gradients)
        contrib = _contrib(leaf_local, leaf_values, lref)

    new_state = (used_feat, used_thr, leaf_values, n_leaf, pen_f, pen_t)
    tree = (t_feat, t_thr, t_split, lref, t_gain, C_leaf)
    return tree, contrib, n_splits, new_state


def train(
    cfg: GBDTConfig,
    bins: jax.Array,
    y: jax.Array,
    edges: jax.Array,
    penalty_feature: jax.Array | float | None = None,
    penalty_threshold: jax.Array | float | None = None,
    forestsize: jax.Array | float | None = None,
    axis_name: str | None = None,
    hist_quant_bits: int | None = None,
):
    """Train a ToaD-regularized GBDT.  Fully jittable; vmappable over the
    three runtime hyperparameters.

    Args:
      cfg: static configuration (includes ``hist_quant_bits``: 0 = exact
        fp32 all-reduce; 8/16 = quantized histogram collectives, Shi et
        al. 2022 style, to cut ICI bytes).
      bins: (n, d) int32 pre-binned features (see gbdt.binning).
      y: (n,) float32 targets (class ids as floats for classification).
      edges: (d, E) float32 bin edges (+inf = invalid candidate).
      penalty_feature/penalty_threshold/forestsize: runtime overrides of
        ι, ξ and the byte budget (default: the cfg values).
      axis_name: when run under shard_map with rows sharded over this mesh
        axis, histograms/leaf stats/base scores are psum'd so every shard
        grows identical trees (distributed-LightGBM data parallelism).
      hist_quant_bits: DEPRECATED alias for ``cfg.hist_quant_bits`` (every
        other knob lives on the config); overrides the config when passed.

    Returns:
      (Forest, history dict of per-round arrays, aux dict).
    """
    if hist_quant_bits is not None:
        import warnings

        warnings.warn(
            "the hist_quant_bits kwarg of train() is deprecated; set "
            "GBDTConfig(hist_quant_bits=...) instead",
            DeprecationWarning,
            stacklevel=2,
        )
        cfg = dataclasses.replace(cfg, hist_quant_bits=int(hist_quant_bits))
    loss = make_loss(cfg.task, cfg.n_classes)
    C = loss.n_ensembles
    n, d = bins.shape
    E = edges.shape[1]
    D = cfg.max_depth
    I = 2**D - 1
    L = 2**D
    M = cfg.n_rounds
    T = M * C

    pen_f = jnp.float32(cfg.toad_penalty_feature if penalty_feature is None else penalty_feature)
    pen_t = jnp.float32(cfg.toad_penalty_threshold if penalty_threshold is None else penalty_threshold)
    budget = jnp.float32(cfg.toad_forestsize if forestsize is None else forestsize)

    if axis_name is None:
        reduce_fn = None
    elif cfg.hist_quant_bits:
        from repro.distributed.collectives import quantized_psum

        qbits = cfg.hist_quant_bits
        reduce_fn = lambda x: quantized_psum(x, axis_name, bits=qbits)
        # sibling subtraction would derive right children from histograms that
        # were quantized once per level, compounding quantization error along
        # right-descending paths (up to max_depth quantization events); with
        # lossy collectives, quantize each level's full histogram exactly once.
        cfg = dataclasses.replace(cfg, hist_subtract=False)
    else:
        reduce_fn = lambda x: jax.lax.psum(x, axis_name)

    # bins keep their storage dtype (int8 preferred); casts fuse at use
    y = y.astype(jnp.float32)
    s, cnt = loss.base_stats(y)
    if axis_name is not None:
        s = jax.lax.psum(s, axis_name)
        cnt = jax.lax.psum(cnt, axis_name)
    base = loss.base_from_stats(s, cnt).astype(jnp.float32)

    state0 = dict(
        feature=jnp.zeros((T, I), jnp.int32),
        thr_bin=jnp.zeros((T, I), jnp.int32),
        is_split=jnp.zeros((T, I), bool),
        leaf_ref=jnp.zeros((T, L), jnp.int32),
        node_gain=jnp.zeros((T, I), jnp.float32),
        leaf_cnt=jnp.zeros((T, L), jnp.float32),
        leaf_values=jnp.zeros((cfg.leaf_capacity,), jnp.float32),
        n_leaf=jnp.zeros((), jnp.int32),
        used_feat=jnp.zeros((d,), bool),
        used_thr=jnp.zeros((d, E), bool),
        preds=jnp.broadcast_to(base[None, :], (n, C)).astype(jnp.float32),
        n_splits=jnp.zeros((), jnp.int32),
        n_trees=jnp.zeros((), jnp.int32),
        stopped=jnp.zeros((), bool),
    )

    def round_body(state, r):
        with jax.named_scope(GRAD):
            g_all, h_all = loss.grad_hess(y, state.get("preds"))
        tree_state = (
            state["used_feat"],
            state["used_thr"],
            state["leaf_values"],
            state["n_leaf"],
            pen_f,
            pen_t,
        )
        new = dict(state)
        contribs = []
        round_splits = jnp.zeros((), jnp.int32)
        for c in range(C):
            tree, contrib, n_sp, tree_state = _grow_tree(
                cfg, bins, g_all[:, c], h_all[:, c], edges, tree_state, reduce_fn
            )
            with jax.named_scope(UPDATE):
                t_idx = r * C + c
                t_feat, t_thr, t_split, lref, t_gain, c_leaf = tree
                new["feature"] = jax.lax.dynamic_update_slice_in_dim(
                    new["feature"], t_feat[None], t_idx, axis=0
                )
                new["thr_bin"] = jax.lax.dynamic_update_slice_in_dim(
                    new["thr_bin"], t_thr[None], t_idx, axis=0
                )
                new["is_split"] = jax.lax.dynamic_update_slice_in_dim(
                    new["is_split"], t_split[None], t_idx, axis=0
                )
                new["leaf_ref"] = jax.lax.dynamic_update_slice_in_dim(
                    new["leaf_ref"], lref[None], t_idx, axis=0
                )
                new["node_gain"] = jax.lax.dynamic_update_slice_in_dim(
                    new["node_gain"], t_gain[None], t_idx, axis=0
                )
                new["leaf_cnt"] = jax.lax.dynamic_update_slice_in_dim(
                    new["leaf_cnt"], c_leaf[None], t_idx, axis=0
                )
                contribs.append(contrib)
                round_splits = round_splits + n_sp
        with jax.named_scope(UPDATE):
            (
                new["used_feat"],
                new["used_thr"],
                new["leaf_values"],
                new["n_leaf"],
                _,
                _,
            ) = tree_state
            new["preds"] = state["preds"] + jnp.stack(contribs, axis=1)
            new["n_splits"] = state["n_splits"] + round_splits
            new["n_trees"] = state["n_trees"] + C

            bits = toad_bits(
                new["used_feat"],
                new["used_thr"],
                new["n_leaf"],
                new["n_trees"],
                new["n_splits"],
                edges,
                D,
                C,
            )
            mem_ok = (budget <= 0) | (bits.astype(jnp.float32) <= budget * 8.0)
            accept = (~state["stopped"]) & (round_splits > 0) & mem_ok
            merged = jax.tree.map(
                lambda a, b: jnp.where(accept, a, b), new, state
            )
            merged["stopped"] = state["stopped"] | ~accept
            hist_out = dict(
                bytes=bits.astype(jnp.float32) / 8.0,
                accepted=accept,
                n_fu=jnp.sum(merged["used_feat"].astype(jnp.int32)),
                n_thr=jnp.sum(merged["used_thr"].astype(jnp.int32)),
                n_leaf=merged["n_leaf"],
                n_splits=merged["n_splits"],
            )
        return merged, hist_out

    final, history = jax.lax.scan(round_body, state0, jnp.arange(M, dtype=jnp.int32))

    forest = Forest(
        feature=final["feature"],
        thr_bin=final["thr_bin"],
        is_split=final["is_split"],
        leaf_ref=final["leaf_ref"],
        leaf_values=final["leaf_values"],
        n_leaf_values=final["n_leaf"],
        n_trees=final["n_trees"],
        edges=edges,
        base_score=base,
        n_ensembles=C,
    )
    aux = dict(
        used_feat=final["used_feat"],
        used_thr=final["used_thr"],
        preds=final["preds"],
        node_gain=final["node_gain"],
        leaf_cnt=final["leaf_cnt"],
        toad_bytes=toad_bits(
            final["used_feat"],
            final["used_thr"],
            final["n_leaf"],
            final["n_trees"],
            final["n_splits"],
            edges,
            D,
            C,
        ).astype(jnp.float32)
        / 8.0,
    )
    return forest, history, aux


train_jit = jax.jit(train, static_argnums=0)


@partial(jax.jit, static_argnums=0)
def train_grid(cfg: GBDTConfig, bins, y, edges, pen_f_grid, pen_t_grid, forestsize_grid):
    """The paper's penalty grid searches as a single vmapped jit call.

    pen_*_grid / forestsize_grid: (G,) arrays — one trained model per entry.
    """
    fn = lambda pf, pt, fs: train(cfg, bins, y, edges, pf, pt, fs)
    return jax.vmap(fn)(pen_f_grid, pen_t_grid, forestsize_grid)
