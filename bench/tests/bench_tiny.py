"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds.

Only the sizes change; every driver, reference and comparison is the one a
chip run uses.  The harness's look for a chip is skipped: the run gets the
CPU's devices.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_CONFIG = {
    "higgs": dict(rows=6000, edge_sample=2048, n_bins=32, max_depth=4, n_rounds=2),
    "covtype": dict(n_rounds=3, max_depth=3, edge_rows=2000),
}
TINY_TRAFFIC = {
    "train": dict(check_trees=2, top_levels=2),
    "batch": dict(rows=600, check_rows=200),
}


def tiny_run(workload: str, seed: int = 2**31 + 11, seconds: float = 0.5,
             program=None, **traffic_overrides) -> harness.Run:
    """Run ``workload`` once at its tiny size; returns the finished Run."""
    import jax

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl, config, traffic = harness.find_cell(spec, workload)
    config.update(TINY_CONFIG[wl["config"]])
    traffic.update(TINY_TRAFFIC[wl["traffic"]], **traffic_overrides)
    run = harness.Run(workload=wl, config=config, traffic=traffic, seed=seed,
                      seconds=seconds, trace=False, t_process=time.perf_counter(),
                      devices=jax.devices())
    harness.execute(run, program)
    return run
