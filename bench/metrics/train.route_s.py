"""Device seconds per round in the trainer's ``toad.route`` scope (gbdt/trainer.py).

Routing every row to its child: the gathers of node and bin, new positions.
Summed over the traced window's instructions that the compiled trainer's
metadata puts in the scope (``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "route")
