"""Work of packed inference, counted from shapes.

One call scores ``rows`` rows of ``d`` fp32 features with ``trees``
complete trees of ``max_depth``: it reads the rows once, the model's tables
once (per tree a feature and a threshold for each of the ``2**D - 1``
internal nodes and a value for each of the ``2**D`` leaves, 4 bytes each),
writes ``rows × C`` fp32 scores once, and makes ``rows·trees·max_depth``
node tests.  Bound by memory bandwidth at these shapes.
"""


def call(rows: int, d: int, trees: int, max_depth: int, n_classes: int):
    """(operations, bytes) of one predict call."""
    internal, leaves = 2**max_depth - 1, 2**max_depth
    ops = rows * trees * max_depth
    nbytes = rows * d * 4 + trees * (2 * internal + leaves) * 4 + rows * n_classes * 4
    return float(ops), float(nbytes)
