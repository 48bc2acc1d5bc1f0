"""Work of the split search, counted from shapes.

At each level every node scores every (feature, edge) candidate: prefix
sums of G, H and count (3 adds), the right side (3 subtractions), the two
children's and the parent's terms (3 squares, 3 additions of λ, 3
divisions), their sum and halving (3), and the penalty (2): 20 operations
per candidate.
"""

OPS_PER_CANDIDATE = 20


def round_(d: int, n_bins: int, max_depth: int) -> float:
    """Operations of one round's split search (all nodes of every level)."""
    nodes = 2**max_depth - 1
    return float(nodes * d * (n_bins - 1) * OPS_PER_CANDIDATE)
