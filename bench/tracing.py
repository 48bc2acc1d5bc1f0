"""Reduction of a profiler trace of the window to device metrics.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  Of it
the reduction keeps two kinds of events, as ``(plane, line, name, start_ns,
dur_ns)``:

- the operations each TPU ran: line ``XLA Ops`` of the planes named
  ``/device:TPU:<n>``, less the control-flow operations (``while``) whose
  events span the operations inside them;
- the benchmark's own host spans, named ``bench.*``
  (``jax.profiler.TraceAnnotation``); ``bench.window`` bounds the window.

From them: busy time (the union of the operation intervals inside the
window, averaged over the chips), the window's length, device time by
operation name (numeric suffixes that XLA adds, ``.73``, are dropped, so
names compare across compiles), and the longest idle gaps, each named by
the innermost benchmark span the host was in at the gap's middle.
"""

from __future__ import annotations

import collections
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: control-flow operations whose events span the operations they run
CONTAINERS = {"while", "conditional", "call"}
WINDOW = "bench.window"
TOP = 10


def op_name(name: str) -> str:
    """The instruction's name from a trace event, which may carry the whole
    HLO text: ``%histogram.1 = f32[512,128]... custom-call(...)`` ->
    ``histogram.1``."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def stable_name(name: str) -> str:
    """``histogram.73`` -> ``histogram``; ``fusion.446`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def load_events(path) -> list[tuple]:
    """Device operations and ``bench.*`` host spans of one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    out.append((plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(events) -> dict:
    """busy_s, window_s, device_ops, idle_gaps and op_s of the window."""
    spans = [(s, s + d, n) for p, _, n, s, d in events
             if not DEVICE_PLANE.match(p)]
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    by_plane = collections.defaultdict(list)
    op_ns = collections.Counter()
    raw_ns = collections.Counter()
    for p, line, n, s, d in events:
        if (not DEVICE_PLANE.match(p) or line != OPS_LINE
                or stable_name(n) in CONTAINERS):
            continue
        s, e = max(s, w0), min(s + d, w1)
        if e <= s:
            continue
        by_plane[p].append((s, e))
        op_ns[stable_name(n)] += e - s
        raw_ns[op_name(n)] += e - s
    chips = max(1, len(by_plane))
    busy_ns = sum(e - s for iv in by_plane.values() for s, e in merge(iv)) / chips

    # idle gaps of the first chip, named by what the host was doing
    first = sorted(by_plane)[0] if by_plane else None
    merged = merge(by_plane[first]) if first else []
    edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW]

    def host_doing(mid):
        hits = [(e - s, n) for s, e, n in inner if s <= mid <= e]
        return min(hits)[1] if hits else WINDOW

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    idle = [[host_doing((s + e) / 2), (e - s) / 1e9] for s, e in gaps[:TOP]]
    ops = [[n, t / 1e9] for n, t in op_ns.most_common(TOP)]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": ops,
        "idle_gaps": idle,
        "op_s": {n: t / 1e9 for n, t in op_ns.items()},
        "op_raw_s": {n: t / 1e9 for n, t in raw_ns.items()},
        "chips": chips,
    }


def summarize_dir(tdir) -> dict:
    files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    events = []
    for f in files:
        events.extend(load_events(f))
    return summarize(events)


def kernel_s(summary: dict, pattern: str) -> float:
    """Device seconds of the operations whose stable name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(t for n, t in summary["op_s"].items() if rx.search(n))
