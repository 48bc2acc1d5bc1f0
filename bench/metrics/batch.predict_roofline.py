"""Predict kernel's share of its roofline, in % (layer: kernels/predict.py).

Device time: the traced window's operations named ``packed_predict`` (the
Pallas call).  Least time: the larger of the passes' node tests over the
bf16 peak and their bytes over HBM bandwidth (``bench/work/predict.py``);
the bytes bound it.
"""

from bench import tracing
from bench.harness import load_piece

PATTERN = r"^packed_predict$"


def read(run, peaks):
    s = run.trace_summary and tracing.kernel_s(run.trace_summary, PATTERN)
    if not s:
        return None
    c = run.counters
    ops, nbytes = load_piece("work", "predict").call(
        c["rows"], c["features"], c["trees"], c["max_depth"], c["n_classes"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * c["passes"] / s
