"""Plain reference for the training cell: replays the first trees in float64.

It imports nothing of the program.  Given the binned data the benchmark
made (``bins``, ``y``, ``edges``), the semantics the configuration states
(logistic loss, base score ``logit(mean y)``, complete trees of
``max_depth`` grown level by level, gain
``½(G_L²/(H_L+λ) + G_R²/(H_R+λ) − G²/(H+λ)) − γ``, a split valid only where
each child holds ``min_child_samples`` rows and ``min_child_weight``
hessian, the ToaD penalties ι for a feature and ξ for a (feature,
threshold) not used before, paid once and then free for every later node,
nodes committed one after another in index order, leaf value
``−lr·G/(H+λ)``), and the program's trees, it checks each of the first
``n_trees`` trees:

- ``leaf_count``: the rows that reach each leaf when routed through the
  program's tree (bin ``<= thr_bin`` goes left; an unsplit node sends all
  left) against the program's own leaf counts.  Exact: the largest
  difference.
- ``gain_gap``: the program's recorded gain of each split it made against
  the gain of that split from float64 sums of the rows that reach it.  The
  largest gap, against the node's reference gain or the median split's,
  whichever is larger.  This reads the histogram and the gain arithmetic.
- ``top_gain_gap``: the same over the nodes of the first ``top_levels``
  levels only, whose sums hold the most rows, so fp32 rounding of long
  sums (which sibling subtraction carries into the small bins of deep
  right children) stays far below what rounding the gradients to bfloat16
  does.
- ``split_gap``: at every live node of every level, how far the program's
  chosen split, with its penalty, lies below the best that a float64
  histogram of every (feature, threshold) finds, against that best or the
  median node's, whichever is larger.  This reads the split choice under
  the penalties, which decide it at the deep levels, where gains are small.
- ``leaf_gap``: the program's leaf value (through its shared table) against
  the reference's from float64 sums, against the leaf's reference value or
  the median leaf's, whichever is larger.

Round ``r`` takes its gradients from the reference's own float64 scores
after the program's first ``r`` trees with the reference's leaf values, so
a program whose scores did not move reads far off at round 1.

``control="bfloat16"`` puts the reference in the program's place,
computed in the precision below the configuration's: the gains and leaf
values compared are the reference's own from gradients and hessians
rounded to bfloat16 (sums in float64), on the program's trees.

The histograms are summed one feature to a thread, and rows are routed in
chunks on the same threads; every sum is float64.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: threads that sum the per-feature histograms (numpy releases the GIL)
THREADS = min(8, os.cpu_count() or 1)


def _gain(G, H, GL, HL, lam, gamma):
    GR, HR = G - GL, H - HL
    return 0.5 * (GL**2 / (HL + lam) + GR**2 / (HR + lam) - G**2 / (H + lam)) - gamma


def _split_gain(sums, j, lam, gamma):
    """Gain of node ``j``'s split from its children's (G, H) sums."""
    G = sums[0][2 * j] + sums[0][2 * j + 1]
    H = sums[1][2 * j] + sums[1][2 * j + 1]
    return _gain(G, H, sums[0][2 * j], sums[1][2 * j], lam, gamma)


def _scaled(gaps, refs):
    """Largest |gap| against max(|ref|, median |ref|)."""
    gaps, refs = np.abs(np.asarray(gaps, np.float64)), np.abs(np.asarray(refs, np.float64))
    if gaps.size == 0:
        return 0.0
    scale = np.maximum(refs, np.median(refs))
    return float(np.max(gaps / np.where(scale > 0, scale, 1.0)))


def _by_level(gaps, refs, levels, depth):
    """The worst scaled gap on each level (scaled by the median over all)."""
    gaps, refs = np.abs(np.asarray(gaps, np.float64)), np.abs(np.asarray(refs, np.float64))
    levels = np.asarray(levels)
    med = np.median(refs) if refs.size else 0.0
    scale = np.maximum(refs, med)
    scaled = gaps / np.where(scale > 0, scale, 1.0)
    return [float(np.max(scaled[levels == k], initial=0.0)) for k in range(depth)]


def level_histogram(bins_t, local, nodes, n_bins, weights, pool, parent=None):
    """float64 sums of each weight (``None``: a count) per (node, feature, bin).

    With the level above's histogram ``parent``, only the rows of left
    children are summed; each right child is its parent less its left
    sibling (in float64 the difference loses nothing that matters here).
    """
    d = bins_t.shape[0]
    if parent is None:
        sel, slot, m = None, local, nodes
    else:
        sel = np.flatnonzero(local % 2 == 0)
        slot, m = local[sel] // 2, nodes // 2
        weights = [None if w is None else w[sel] for w in weights]
    base = slot.astype(np.int64) * n_bins
    part = np.empty((len(weights), m, d, n_bins))

    def one(f):
        col = bins_t[f] if sel is None else bins_t[f][sel]
        idx = col.astype(np.int64)
        idx += base
        for i, w in enumerate(weights):
            part[i, :, f] = np.bincount(idx, w, m * n_bins).reshape(m, n_bins)

    list(pool.map(one, range(d)))
    if parent is None:
        return part
    out = np.empty((len(weights), nodes, d, n_bins))
    out[:, 0::2] = part
    out[:, 1::2] = parent - part
    return out


def route(bins_t, pos, feat, thr, split, pool, chunk=1 << 20):
    """Each row's child under the program's tree: bin ``<= thr`` goes left,
    an unsplit node sends all left.  Returns (child, went_right)."""
    n = pos.shape[0]
    child = np.empty(n, np.int64)
    right = np.empty(n, bool)

    def one(a):
        b = min(a + chunk, n)
        p = pos[a:b]
        xb = bins_t[feat[p], np.arange(a, b)]
        r = split[p] & (xb > thr[p])
        right[a:b] = r
        child[a:b] = 2 * p + 1 + r

    list(pool.map(one, range(0, n, chunk)))
    return child, right


def check(bins_t, y, edges, forest: dict, cfg: dict, n_trees: int,
          top_levels: int, control: str | None = None) -> dict:
    """The numbers over the first ``n_trees`` trees.

    bins_t: (d, n) int bins, feature-major; y: (n,) labels in {0, 1};
    forest: the program's ``feature``, ``thr_bin``, ``is_split`` (T, I),
    ``leaf_ref``, ``leaf_cnt`` (T, L), ``leaf_values`` (V,), ``node_gain``
    (T, I), as numpy; cfg: ``max_depth``, ``learning_rate``, ``reg_lambda``,
    ``gamma``, ``min_child_weight``, ``min_child_samples``,
    ``toad_penalty_feature``, ``toad_penalty_threshold``.
    """
    # a masked candidate or an empty leaf may divide by a hessian of 0
    with ThreadPoolExecutor(THREADS) as pool, np.errstate(divide="ignore", invalid="ignore"):
        return _check(bins_t, y, edges, forest, cfg, n_trees, top_levels,
                      control, pool)


def _check(bins_t, y, edges, forest, cfg, n_trees, top_levels, control, pool):
    d, n = bins_t.shape
    E = edges.shape[1]
    B = E + 1
    D = int(cfg["max_depth"])
    I = 2**D - 1
    lam, gamma = float(cfg["reg_lambda"]), float(cfg["gamma"])
    mcw, mcs = float(cfg["min_child_weight"]), float(cfg["min_child_samples"])
    pen_f = float(cfg["toad_penalty_feature"])
    pen_t = float(cfg["toad_penalty_threshold"])
    lr = float(cfg["learning_rate"])
    valid_edge = np.isfinite(edges)
    y = y.astype(np.float64)
    p0 = np.clip(y.mean(), 1e-6, 1 - 1e-6)
    F = np.full(n, np.log(p0 / (1 - p0)))
    used_feat = np.zeros(d, bool)
    used_thr = np.zeros((d, E), bool)

    count_gap, gain_gaps, gain_refs, levels = 0.0, [], [], []
    split_gaps, split_refs, split_lv, split_pen = [], [], [], []
    leaf_gaps, leaf_refs = [], []
    for t in range(n_trees):
        feat, thr = forest["feature"][t], forest["thr_bin"][t]
        split = forest["is_split"][t]
        s = 1.0 / (1.0 + np.exp(-F))
        g, h = s - y, s * (1.0 - s)
        if control is not None:
            import ml_dtypes

            low = getattr(ml_dtypes, control)
            gq, hq = (a.astype(low).astype(np.float64) for a in (g, h))
        # a count of 0 rows always passes a min_child_samples of 0
        weights = (g, h) + ((None,) if mcs > 0 else ())
        pos = np.zeros(n, np.int64)
        dead = np.zeros(1, bool)
        tree_gain_gaps, tree_gain_refs, tree_levels = [], [], []
        for level in range(D):
            nodes = 2**level
            base = nodes - 1
            local = pos - base
            hist = level_histogram(bins_t, local, nodes, B, weights, pool,
                                   None if level == 0 else hist)
            cum = np.cumsum(hist, axis=-1)[..., :E]   # (k, nodes, d, E)
            tot = hist[:, :, 0, :].sum(-1)             # (k, nodes)
            GL, HL = cum[0], cum[1]
            G, H = tot[0][:, None, None], tot[1][:, None, None]
            gain = _gain(G, H, GL, HL, lam, gamma)
            valid = (HL >= mcw) & (H - HL >= mcw) & valid_edge[None]
            if mcs > 0:
                CL, Cn = cum[2], tot[2][:, None, None]
                valid &= (CL >= mcs) & (Cn - CL >= mcs)
            for j in range(nodes):
                node = base + j
                f, e, ok = int(feat[node]), int(thr[node]), bool(split[node])
                if not dead[j]:
                    pen = pen_f * (~used_feat[:, None]) + pen_t * (~used_thr)
                    eff = np.where(valid[j], gain[j] - pen, -np.inf)
                    best = max(float(eff.max()), 0.0)
                    chosen = float(eff[f, e]) if ok else 0.0
                    split_gaps.append(best - chosen)
                    split_refs.append(best)
                    split_lv.append(level)
                    split_pen.append(float(pen[f, e]) if ok else 0.0)
                if ok:
                    used_feat[f] = True
                    used_thr[f, e] = True
            child, right = route(bins_t, pos, feat, thr, split, pool)
            side = local * 2 + right
            sums = [np.bincount(side, w, 2 * nodes) for w in (g, h)]
            if control is not None:
                sums_q = [np.bincount(side, w, 2 * nodes) for w in (gq, hq)]
            for j in range(nodes):
                node = base + j
                if split[node]:
                    ref = _split_gain(sums, j, lam, gamma)
                    got = (float(forest["node_gain"][t, node]) if control is None
                           else _split_gain(sums_q, j, lam, gamma))
                    tree_gain_gaps.append(got - ref)
                    tree_gain_refs.append(ref)
                    tree_levels.append(level)
            split_lvl = split[base:base + nodes]
            dead = np.stack([dead, dead | ~split_lvl], axis=1).reshape(-1)
            pos = child
        leaf = pos - I
        L = I + 1
        cnt = np.bincount(leaf, None, L)
        count_gap = max(count_gap, float(np.max(np.abs(cnt - forest["leaf_cnt"][t]))))
        G = np.bincount(leaf, g, L)
        H = np.bincount(leaf, h, L)
        v = np.where(cnt > 0, -lr * G / (H + lam), 0.0)
        if control is None:
            prog = forest["leaf_values"][forest["leaf_ref"][t]].astype(np.float64)
        else:
            prog = -lr * np.bincount(leaf, gq, L) / (np.bincount(leaf, hq, L) + lam)
        reached = cnt > 0
        leaf_gaps.append(prog[reached] - v[reached])
        leaf_refs.append(v[reached])
        gain_gaps.append(np.asarray(tree_gain_gaps))
        gain_refs.append(np.asarray(tree_gain_refs))
        levels.append(np.asarray(tree_levels))
        F = F + v[leaf]

    per_tree = lambda gaps, refs: max(
        (_scaled(a, b) for a, b in zip(gaps, refs)), default=0.0)
    top = [lv < top_levels for lv in levels]
    by_level = [
        max((float(np.max(np.abs(a[lv == k]) / np.maximum(
            np.abs(b[lv == k]), np.median(np.abs(b))), initial=0.0))
            for a, b, lv in zip(gain_gaps, gain_refs, levels)), default=0.0)
        for k in range(D)]
    return {
        "leaf_count": count_gap,
        "gain_gap": per_tree(gain_gaps, gain_refs),
        "top_gain_gap": per_tree([a[m] for a, m in zip(gain_gaps, top)],
                                 [b[m] for b, m in zip(gain_refs, top)]),
        "split_gap": _scaled(split_gaps, split_refs),
        "leaf_gap": per_tree(leaf_gaps, leaf_refs),
        # the looks behind gain_gap and split_gap: their worst node on each
        # level (not compared)
        "gain_gap_by_level": by_level,
        "split_gap_by_level": _by_level(split_gaps, split_refs, split_lv, D),
        # every live node: level, best, best less chosen, penalty paid
        "split_nodes": np.column_stack([split_lv, split_refs, split_gaps, split_pen]),
    }
