"""Device seconds per round in the trainer's ``toad.update`` scope (gbdt/trainer.py).

Tree writes, the prediction update, the ToaD size, acceptance, the merge of
the whole state. Summed over the traced window's instructions that the
compiled trainer's metadata puts in the scope (``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "update")
