"""Work of the training histogram, counted from the algorithm's shapes.

One call builds ``(n_nodes, d, n_bins, CH)`` sums from ``n`` rows: it reads
each row's ``d`` bins once (int32, as the trainer holds them), its ``CH``
channels (g, h, count; fp32) and its node id once, writes the histogram
once, and makes ``n·d·CH`` accumulations.  What an implementation does
beyond that (one-hot products on the MXU, transposes, padding) is not
counted, so the count stays the same whatever implements it.  At about
one operation per 4 bytes it is bound by memory bandwidth.

Per round the trainer builds level 0 whole and, at each level ``l >= 1``,
only the left children: ``2**(l-1)`` nodes (sibling subtraction).
"""

CH = 3
BIN_BYTES = 4


def call(n: int, d: int, n_bins: int, n_nodes: int) -> tuple[float, float]:
    """(operations, bytes) of one histogram call."""
    ops = n * d * CH
    nbytes = n * d * BIN_BYTES + n * CH * 4 + n * 4 + n_nodes * d * n_bins * CH * 4
    return float(ops), float(nbytes)


def nodes_per_level(max_depth: int) -> list[int]:
    return [1] + [2 ** (level - 1) for level in range(1, max_depth)]


def round_(n: int, d: int, n_bins: int, max_depth: int) -> tuple[float, float]:
    """(operations, bytes) of one round's histogram calls."""
    parts = [call(n, d, n_bins, k) for k in nodes_per_level(max_depth)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
