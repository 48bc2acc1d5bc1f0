"""Device seconds per round in the trainer's ``toad.hist`` scope (kernels/histogram.py).

The histogram's work around the Pallas call: pads and transposes of the
bins, part sums, sibling subtraction. The kernel itself (``histogram``) is
left out; ``train.hist_roofline`` reads it. Summed over the traced window's
instructions that the compiled trainer's metadata puts in the scope
(``bench/scopes.py``).
"""

from bench import scopes


def read(run, peaks):
    return scopes.phase_s(run, "hist")
