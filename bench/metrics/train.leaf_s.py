"""Device seconds per round in the trainer's ``toad.leaf`` scope (gbdt/trainer.py).

Leaf statistics (segment sum), leaf values, the shared-table insert loop,
the per-row contribution gather. Summed over the traced window's
instructions that the compiled trainer's metadata puts in the scope
(``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "leaf")
