"""The general harness: finds a cell's pieces by name and runs it once.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
configuration's file (``bench/configs/<config>.json``) says which generator
under ``bench/data/`` makes its data or model; the traffic file
(``bench/traffic/<traffic>.json``) says which driver under ``bench/cells/``
runs it (its ``kind``) and with what parameters.  Each per-layer metric is
read by ``bench/metrics/<metric>.py``.  So a later cell, mix or metric adds
files and entries and edits none.

One run: set-up (data, model, compilation, warm-up), then the measured
window of ``--seconds``, then the reading of memory, the freeing of the
program's state, and the comparison with the plain reference that decides
``correct``.  End-to-end metrics come from ``--trace 0`` runs, per-layer
metrics from ``--trace 1`` runs, whose window is traced by the profiler.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: exit code of a run that found no chip, or too few
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_piece(kind: str, name: str, bench: pathlib.Path = BENCH):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} piece named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, name: str, root: pathlib.Path = ROOT):
    """(workload, configuration, traffic) dicts of the cell ``name``."""
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    return wl, config, traffic


def require_chips(n: int):
    """The first ``n`` TPU devices; :class:`NoChip` when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def enable_compile_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compilations while armed (a cache miss in the window)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, key, _secs, **_kw):
        if self.armed and key in self.EVENTS:
            self.count += 1


@dataclasses.dataclass
class Run:
    """What one run of a cell knows and records; handed to its driver."""

    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    devices: list
    bench: pathlib.Path = BENCH
    #: host clock at the first timed call
    t_window: float | None = None
    attempted: int = 0
    failed: int = 0
    #: end-to-end metrics the driver measured, by name
    e2e: dict = dataclasses.field(default_factory=dict)
    #: counts made in the window (rows, rounds, passes, engine stats)
    counters: dict = dataclasses.field(default_factory=dict)
    #: (name, value, limit) of every number the reference compared
    checks: list = dataclasses.field(default_factory=list)
    memory_peak_bytes: int | None = None
    compiles_in_window: int = 0
    #: reduced profiler trace of the window (``--trace 1`` only)
    trace_summary: dict | None = None
    _counter: CompileCounter | None = None

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process

    def piece(self, kind: str, name: str):
        return load_piece(kind, name, self.bench)

    @contextlib.contextmanager
    def window(self):
        """The measured window: compilations counted, traced with --trace 1."""
        import jax

        if self._counter is None:
            self._counter = CompileCounter()
        tdir = None
        if self.trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
        self._counter.armed = True
        self.t_window = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield self
        finally:
            self._counter.armed = False
            self.compiles_in_window = self._counter.count
            if tdir is not None:
                jax.profiler.stop_trace()
                from bench import tracing

                try:
                    self.trace_summary = tracing.summarize_dir(tdir)
                finally:
                    shutil.rmtree(tdir, ignore_errors=True)

    def read_memory(self) -> None:
        """Peak device memory on the fullest chip, read as the window closes."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        ok = all(math.isfinite(v) and v <= lim for _, v, lim in self.checks)
        return bool(self.checks) and ok and self.attempted > 0 and self.failed == 0


def cell_metrics(spec: dict, wl_name: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: end-to-end or per-layer."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if "workloads" not in m or wl_name in m["workloads"]]


def result_line(spec: dict, run: Run, peaks: dict | None) -> dict:
    wl_name = run.workload["name"]
    metrics = {}
    for m in cell_metrics(spec, wl_name, run.trace):
        if run.trace:
            value = run.piece("metrics", m["name"]).read(run, peaks)
        elif m["name"] == "setup_s":
            value = run.setup_s
        else:
            value = run.e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = run.devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    out = {
        "correct": run.correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        out["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"],
        }
    # the compared numbers come last, each beside its limit
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in run.checks}
    return out


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def execute(run: Run, program=None) -> None:
    """Drive the cell: the driver named by the traffic's ``kind``."""
    driver = run.piece("cells", run.traffic["kind"])
    driver.run(run, program)


def main(argv, t_process: float) -> int:
    args = parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl, config, traffic = find_cell(spec, args.workload)
    try:
        devices = require_chips(int(wl["chips"]))
    except NoChip as e:
        log(f"refused: {e}")
        return EXIT_NO_CHIP
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    log(f"compile cache: {enable_compile_cache()}")
    run = Run(workload=wl, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              t_process=t_process, devices=devices)
    execute(run)
    out = result_line(spec, run, peaks[kind])
    log(f"setup_s {run.setup_s:.4f}; compiles in window {run.compiles_in_window}; "
        f"memory_peak_bytes {run.memory_peak_bytes}")
    log("counters " + json.dumps(run.counters, default=str))
    for name, value, limit in run.checks:
        log(f"check {name} {value!r} limit {limit!r}")
    print(json.dumps(out), flush=True)
    return 0
