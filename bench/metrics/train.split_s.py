"""Device seconds per round in the trainer's ``toad.split`` scope (gbdt/trainer.py).

Split search: cumulative sums, node totals, gains, validity. Summed over the
traced window's instructions that the compiled trainer's metadata puts in
the scope (``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "split")
