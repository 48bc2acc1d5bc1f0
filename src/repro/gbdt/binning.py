"""Quantile feature binning (LightGBM-style histogram preprocessing).

Candidate split thresholds are the bin *edges*; training operates purely on
integer bin ids.  The binned test ``bin <= e`` is exactly the raw test
``x <= edges[e]`` because ``bin(x) = #{j : edges_j < x}``.
"""

from __future__ import annotations

import concurrent.futures

import jax
import jax.numpy as jnp
import numpy as np


def fit_bins(x: np.ndarray, n_bins: int = 256) -> np.ndarray:
    """Quantile bin edges per feature.

    Args:
      x: (n, d) training features (host numpy).
      n_bins: number of bins; produces n_bins - 1 candidate edges.

    Returns:
      (d, n_bins - 1) float32 edges, non-decreasing per feature.  Duplicate
      quantiles (low-cardinality features) are replaced by +inf so they are
      never selected as split candidates.
    """
    x = np.asarray(x)
    n, d = x.shape
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]

    def column(f):  # float64 quantiles, one contiguous column at a time
        return np.quantile(x[:, f].astype(np.float64), qs)

    # columns are independent and numpy's selection runs without the GIL
    with concurrent.futures.ThreadPoolExecutor() as pool:
        edges = np.array(list(pool.map(column, range(d))), np.float64)
    edges = edges.reshape(d, len(qs))  # (d, n_bins - 1)
    out = np.full_like(edges, np.inf)
    for f in range(d):
        e = edges[f]
        keep = np.concatenate([[True], e[1:] > e[:-1]])
        # de-duplicated edges, left-packed; the rest stay +inf
        kept = e[keep]
        out[f, : len(kept)] = kept
    return out.astype(np.float32)


#: rows binned per step of :func:`apply_bins` (bounds its temporaries)
BIN_ROWS = 1 << 16


@jax.jit
def apply_bins(x: jax.Array, edges: jax.Array) -> jax.Array:
    """(n, d) raw floats -> (n, d) int32 bin ids, bin = #{edges < x}.

    Rows are binned ``BIN_ROWS`` at a time, so the search's temporaries
    stay one block's size: binning all rows of a chip-sized dataset at once
    needs several times the dataset in device memory.
    """

    def rows(xb):
        one = lambda col, e: jnp.searchsorted(e, col, side="left")
        return jax.vmap(one, in_axes=(-1, 0), out_axes=-1)(xb, edges)

    if x.shape[0] <= BIN_ROWS:
        return rows(x).astype(jnp.int32)
    return jax.lax.map(rows, x, batch_size=BIN_ROWS).astype(jnp.int32)
