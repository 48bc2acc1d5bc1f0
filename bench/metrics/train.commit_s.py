"""Device seconds per round in the trainer's ``toad.commit`` scope (gbdt/trainer.py).

The sequential per-node commit loop under the ToaD penalties. Summed over
the traced window's instructions that the compiled trainer's metadata puts
in the scope (``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "commit")
