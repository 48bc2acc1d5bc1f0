"""A round's algorithmic operations per second over the bf16 peak, in %.

Operations: the histogram's accumulations (``bench/work/histogram.py``)
and the split search (``bench/work/split_gain.py``) of every round in the
traced window, over the window's length.
"""

from bench.harness import load_piece


def read(run, peaks):
    t, c = run.trace_summary, run.counters
    if not t or not c.get("rounds"):
        return None
    hist, _ = load_piece("work", "histogram").round_(
        c["rows"], c["features"], c["n_bins"], c["max_depth"])
    split = load_piece("work", "split_gain").round_(
        c["features"], c["n_bins"], c["max_depth"])
    return 100.0 * (hist + split) * c["rounds"] / t["window_s"] / peaks["flops_bf16"]
