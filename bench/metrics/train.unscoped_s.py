"""Device seconds per round that no phase scope names (layer: gbdt/trainer.py).

The traced window's device operation seconds, less the histogram kernel's,
less the seven ``toad.*`` phases (``bench/scopes.py``): copies XLA inserts
without metadata, and operations of a scope a refactor dropped.
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "unscoped")
