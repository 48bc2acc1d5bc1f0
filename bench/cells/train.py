"""Driver of training traffic: whole training jobs, back to back.

Set-up makes the configuration's binned dataset on the device, puts its
rows in the order ``--seed`` draws, and compiles the trainer for these
shapes (from the persistent cache after the first run) without running
it.  The window then runs ``ToadModel.fit_binned`` jobs of the
configuration's ``n_rounds`` rounds, each ended by ``block_until_ready``,
and starts no job after ``--seconds``.  ``round_s`` is the window's wall
time over all its rounds.

After the window the first job's trees are checked against the float64
reference (``bench/reference/train_check.py``).

Traffic parameters: ``check_trees`` (trees the reference replays) and
``top_levels`` (the levels ``top_gain_gap`` reads).
"""

from __future__ import annotations

import time

import numpy as np

#: the program's training configuration, from the configuration file
GBDT_KEYS = ("task", "n_rounds", "max_depth", "learning_rate", "reg_lambda",
             "gamma", "min_child_weight", "min_child_samples",
             "toad_penalty_feature", "toad_penalty_threshold", "leaf_capacity")


def program_config(config: dict, **overrides):
    from repro.gbdt import GBDTConfig

    kw = {k: config[k] for k in GBDT_KEYS if k in config}
    kw.update(overrides)
    return GBDTConfig(**kw)


def run(r, program=None) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import ToadModel
    from repro.gbdt import train_jit

    cfg, tr = r.config, r.traffic
    data = r.piece("data", cfg["generator"])
    t0 = time.perf_counter()
    bins, y, edges = data.make(cfg)
    bins, y = data.permute(bins, y, r.seed)
    jax.block_until_ready((bins, y, edges))
    t_data = time.perf_counter() - t0

    gcfg = program_config(cfg, **(program or {}))
    model = ToadModel(config=gcfg, n_bins=int(cfg["n_bins"]))
    # compile what fit_binned will call, with the same arguments, unrun
    t0 = time.perf_counter()
    train_jit.trace(gcfg, jnp.asarray(bins), jnp.asarray(np.asarray(y, np.float32)),
                    jnp.asarray(edges)).lower().compile()
    t_compile = time.perf_counter() - t0

    jobs, first = [], None
    with r.window():
        t_start = time.perf_counter()
        while not jobs or time.perf_counter() - t_start < r.seconds:
            tj = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.train_job"):
                model.fit_binned(bins, y, edges)
                jax.block_until_ready((model.forest, model.aux))
            jobs.append(time.perf_counter() - tj)
            if first is None:
                first = (model.forest, model.aux["node_gain"], model.aux["leaf_cnt"])
        t_end = time.perf_counter()
    r.read_memory()

    rounds = len(jobs) * gcfg.n_rounds
    r.attempted, r.failed = len(jobs), 0
    r.e2e["round_s"] = (t_end - t_start) / rounds
    r.counters.update(
        jobs=len(jobs), rounds=rounds, job_s=jobs, data_s=t_data,
        compile_s=t_compile, rows=int(bins.shape[0]), features=int(bins.shape[1]),
        n_bins=int(cfg["n_bins"]), max_depth=gcfg.max_depth,
        rounds_per_job=gcfg.n_rounds,
        trees_in_first_job=int(first[0].n_trees),
    )

    forest, gains, counts = first
    prog = {
        "feature": np.asarray(forest.feature), "thr_bin": np.asarray(forest.thr_bin),
        "is_split": np.asarray(forest.is_split), "leaf_ref": np.asarray(forest.leaf_ref),
        "leaf_values": np.asarray(forest.leaf_values),
        "node_gain": np.asarray(gains), "leaf_cnt": np.asarray(counts),
    }
    bins_t = np.ascontiguousarray(np.asarray(bins).T)
    y_h, edges_h = np.asarray(y), np.asarray(edges)
    del model, first, forest, gains, counts, bins, y, edges
    check(r, bins_t, y_h, edges_h, prog, cfg, tr)


def check(r, bins_t, y, edges, prog, cfg, tr) -> None:
    """Compare the first job's trees with the float64 reference."""
    ref = r.piece("reference", "train_check")
    t0 = time.perf_counter()
    n_trees = min(int(tr["check_trees"]), int(cfg["n_rounds"]))
    got = ref.check(bins_t, y, edges, prog, cfg, n_trees=n_trees,
                    top_levels=int(tr["top_levels"]))
    r.counters["reference_s"] = time.perf_counter() - t0
    for name, limit in tr["limits"].items():
        r.check(name, got[name], limit)
