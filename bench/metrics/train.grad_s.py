"""Device seconds per round in the trainer's ``toad.grad`` scope (gbdt/trainer.py).

Gradients and hessians of the loss, and the (n, 3) histogram channels.
Summed over the traced window's instructions that the compiled trainer's
metadata puts in the scope (``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "grad")
