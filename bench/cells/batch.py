"""Driver of batch scoring: whole passes over one device-resident table.

Set-up builds the configuration's fixed model (``bench/data/forest_pool``),
wraps it as the program's ``ToadModel`` and compresses it with the exact
spec, draws the table's rows from ``--seed`` and puts them on the device,
and compiles the Pallas predictor for the table's shape without running
it.  The window runs passes of ``ToadModel.predictor("pallas")`` over the
table, keeping ``in_flight`` passes queued on the device so that a stall of
the host thread does not idle the chip, and starts no pass after
``--seconds``; every pass it started has ended when the window closes.
``rows_per_s`` is the rows of all passes over the window's wall time.

After the window a sample of the last pass's rows, drawn from the seed, is
scored by the float64 reference (``bench/reference/forest.py``).

Traffic parameters: ``rows`` (table size), ``in_flight``, ``check_rows``,
``limits``.
"""

from __future__ import annotations

import time

import numpy as np


def build_model(r, program=None):
    """(description, compressed ToadModel) of the configuration's model."""
    import jax.numpy as jnp

    from repro.api import ToadModel
    from repro.gbdt import GBDTConfig, Forest

    cfg = r.config
    desc = r.piece("data", cfg["generator"]).make(cfg)
    T = desc["feature"].shape[0]
    V = desc["leaf_values"].size
    forest = Forest(
        feature=jnp.asarray(desc["feature"]), thr_bin=jnp.asarray(desc["thr_bin"]),
        is_split=jnp.asarray(desc["is_split"]), leaf_ref=jnp.asarray(desc["leaf_ref"]),
        leaf_values=jnp.asarray(desc["leaf_values"]),
        n_leaf_values=jnp.asarray(V, jnp.int32), n_trees=jnp.asarray(T, jnp.int32),
        edges=jnp.asarray(desc["edges"]), base_score=jnp.asarray(desc["base_score"]),
        n_ensembles=desc["n_classes"],
    )
    gcfg = GBDTConfig(task="multiclass", n_classes=desc["n_classes"],
                      n_rounds=int(cfg["n_rounds"]), max_depth=desc["max_depth"],
                      leaf_capacity=V)
    model = ToadModel.from_forest(forest, config=gcfg, n_bins=int(cfg["n_bins"]))
    model.compress()
    if program is not None:
        program(model)
    return desc, model


def warm_predict(model, x: np.ndarray) -> None:
    """Compile the Pallas predictor for ``x``'s shape, as ``predict`` calls it."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.predict import packed_predict

    p = model.packed
    args = [jnp.asarray(x)] + [jnp.asarray(a) for a in (
        p.words, p.leaf_ref, p.leaf_values, p.thr_table, p.thr_offsets,
        p.used_features, p.base_score)]
    packed_predict.trace(
        *args, max_depth=p.max_depth, tidx_bits=p.tidx_bits,
        n_ensembles=p.n_ensembles, interpret=ops._interp()).lower().compile()
    model.predictor("pallas")


def sample(seed: int, n: int, k: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed) % (1 << 63), 1])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def run(r, program=None) -> None:
    import collections

    import jax
    import jax.numpy as jnp

    tr = r.traffic
    t0 = time.perf_counter()
    desc, model = build_model(r, program)
    t_model = time.perf_counter() - t0
    t0 = time.perf_counter()
    X = r.piece("data", r.config["rows_generator"]).rows(int(tr["rows"]), r.seed)
    x = jax.block_until_ready(jnp.asarray(X))
    t_rows = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm_predict(model, X)
    predict = model.predictor("pallas")
    t_compile = time.perf_counter() - t0

    depth = int(tr["in_flight"])
    queued, ends, out = collections.deque(), [], None
    with r.window():
        t_start = time.perf_counter()
        while True:
            while len(queued) < depth and (
                    not (ends or queued) or time.perf_counter() - t_start < r.seconds):
                with jax.profiler.TraceAnnotation("bench.batch_dispatch"):
                    queued.append(predict(x))
            if not queued:
                break
            out = queued.popleft()
            with jax.profiler.TraceAnnotation("bench.batch_wait"):
                out.block_until_ready()
            ends.append(time.perf_counter())
        t_end = time.perf_counter()
    r.read_memory()

    n = X.shape[0]
    r.attempted, r.failed = len(ends), 0
    r.e2e["rows_per_s"] = n * len(ends) / (t_end - t_start)
    p = model.packed
    r.counters.update(
        passes=len(ends), pass_s=list(np.diff([t_start] + ends)), rows=n,
        features=X.shape[1], trees=int(p.words.shape[0]), max_depth=int(p.max_depth),
        n_classes=int(p.n_ensembles), in_flight=depth, model_s=t_model, rows_s=t_rows,
        compile_s=t_compile, window_s=t_end - t_start,
    )
    out = np.asarray(out)
    del model, predict, x
    idx = sample(r.seed, n, int(tr["check_rows"]))
    t0 = time.perf_counter()
    ref = r.piece("reference", "forest").score(desc, X[idx])
    r.counters["reference_s"] = time.perf_counter() - t0
    gap = float(np.max(np.abs(out[idx].astype(np.float64) - ref)))
    r.check("score_gap", gap, tr["limits"]["score_gap"])
