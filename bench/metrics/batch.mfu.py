"""A pass's node tests per second of wall time over the bf16 peak, in %."""

from bench.harness import load_piece


def read(run, peaks):
    t, c = run.trace_summary, run.counters
    if not t or not c.get("passes"):
        return None
    ops, _ = load_piece("work", "predict").call(
        c["rows"], c["features"], c["trees"], c["max_depth"], c["n_classes"])
    return 100.0 * ops * c["passes"] / t["window_s"] / peaks["flops_bf16"]
