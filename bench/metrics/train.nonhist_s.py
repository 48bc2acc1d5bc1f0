"""Device seconds per round outside the histogram kernel (layer: gbdt/trainer.py).

Busy device time of the traced window (union of operation intervals) less
the histogram kernel's, over the rounds: split search, commit loop, routing
gathers, leaf scatter and the histogram's own transposes.
"""

from bench import tracing

PATTERN = r"^histogram$"


def read(run, peaks):
    t = run.trace_summary
    if not t or not run.counters.get("rounds"):
        return None
    return (t["busy_s"] - tracing.kernel_s(t, PATTERN)) / run.counters["rounds"]
