"""Histogram kernel's share of its roofline, in % (layer: kernels/histogram.py).

Device time: the traced window's operations named ``histogram`` (the Pallas
call, matched by its name without XLA's numeric suffix).  Least time: the
larger of the rounds' histogram operations over the bf16 peak and their
bytes over HBM bandwidth (``bench/work/histogram.py``); the bytes bound it.
"""

from bench import tracing
from bench.harness import load_piece

PATTERN = r"^histogram$"


def read(run, peaks):
    s = run.trace_summary and tracing.kernel_s(run.trace_summary, PATTERN)
    if not s:
        return None
    c = run.counters
    ops, nbytes = load_piece("work", "histogram").round_(
        c["rows"], c["features"], c["n_bins"], c["max_depth"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * c["rounds"] / s
