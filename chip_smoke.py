#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: train -> compress -> serve.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # data-parallel training, 2x2 host

One process, data made on the device from a seed, no network.  The phases
of a one-chip run, in order:

  device   JAX must report a TPU (the script never sets JAX_PLATFORMS).
  train    ``ToadModel(config=...).fit`` at the widths of
           ``configs/toad_gbdt.py`` (256 features, 256 bins, depth 8, the
           config's penalties, 8 rounds) on 2^22 rows — one chip's share of
           the config's 2^24 over four — through the compiled Pallas
           histogram.  Then the Pallas and the "fused" histogram paths
           train on 2^21 rows and must grow the same trees (the fused path
           needs more device memory than one chip has at 2^22).
  serve    ``model.compress()``, then ``GBDTEngine(backend="pallas")`` with
           no resilience policy: 512 requests from 4 client threads and one
           65,536-row batch, each within 1e-5 of the reference backend, no
           fallback batch and no degraded start; then early exit
           (epsilon=0, the kernel mode) with exact labels.
  kernels  the training and serving programs that ran lower to
           ``tpu_custom_call``: compiled Mosaic kernels, not the interpreter.

``--four-chips`` runs only the data-parallel path of the config and what it
is compared with: ``train_data_parallel`` over a 4-device mesh vs one-device
training on the same 2^22 rows (same trees), a check that every device
holds its own row shard, then the config's full 2^24 rows over four chips
(one round) with the program's bytes per device and each device's peak.

Split parity compares the first tree of a problem with exactly balanced
labels.  Its base score is then 0, every gradient is +-1/2 and every
hessian 1/4, so every histogram sum is exact in fp32 and two correct
histogram paths (or shardings) must grow bitwise the same tree.  Later
rounds sum arbitrary fp32 values in different orders: at 2^21 rows those
sums differ by up to ~1e-4 (long sums of near-equal values round with a
bias), sibling subtraction carries that absolute error into the small bins
of deep right children, and the trees legitimately part.  The real-width
histogram check covers such values with a tolerance instead.

Timings and counts go to earlier lines.  The last line is one JSON object,
printed only when every phase passed; any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import threading
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
FULL_ROWS = 1 << 22      # one chip's share of the config's 2^24 rows
PARITY_ROWS = 1 << 21    # the fused reference does not fit one chip at 2^22
EDGE_SAMPLE = 1 << 18    # rows that bin edges are fitted on in parity runs
FULL_ROUNDS = 1          # rounds of the four-chip run at the config's rows
N_REQUESTS, N_CLIENTS, BATCH_ROWS, EE_REQUESTS = 512, 4, 65536, 256
TOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    def __init__(self, what: str):
        self.what = what

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"  {self.what}: {self.s:.2f} s")


def make_data(rows: int, d: int, seed: int, sharding=None,
              balanced: bool = False):
    """(rows, d) standard-normal features and a binary label that depends
    on five of them plus noise, made on the device.  ``balanced`` labels
    exactly half the rows 1 (those with the larger signal)."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        kx, kn = jax.random.split(key)
        X = jax.random.normal(kx, (rows, d), jnp.float32)
        noise = jax.random.normal(kn, (rows,), jnp.float32)
        z = X[:, 0] - X[:, 1] + 0.3 * X[:, 2] ** 2 + 0.5 * X[:, 3] * X[:, 4]
        z = z + 0.5 * noise
        if balanced:
            top = jnp.argsort(z)[rows // 2:]
            return X, jnp.zeros(rows, jnp.float32).at[top].set(1.0)
        return X, (z > 0).astype(jnp.float32)

    out = None if sharding is None else (sharding, sharding)
    return jax.jit(gen, out_shardings=out)(jax.random.key(seed))


def parity_problem(rows: int, wl, seed: int):
    """Balanced-label data for a split-parity run: (X, y, edges)."""
    from repro.gbdt import fit_bins

    X, y = make_data(rows, wl.n_features, seed, balanced=True)
    edges = fit_bins(np.asarray(X[:EDGE_SAMPLE]), wl.n_bins)
    return X, y, edges


def same_trees(f1, f2, what: str, gains=None) -> float:
    """Split-exact parity of two forests; returns max |Δ leaf value|.

    ``gains``, the two runs' ``aux["node_gain"]``, lets a failure name the
    first node (tree-major, level order) where the runs chose differently.
    """
    split = lambda f: np.stack([np.asarray(f.feature), np.asarray(f.thr_bin),
                                np.asarray(f.is_split)], axis=-1)
    s1, s2 = split(f1), split(f2)
    if not np.array_equal(s1, s2):
        t, i = np.argwhere(np.any(s1 != s2, axis=-1))[0]
        g = "" if gains is None else " gains " + " vs ".join(
            f"{float(np.asarray(x)[t, i]):.9g}" for x in gains)
        raise SmokeFailure(
            f"{what}: {int(np.sum(np.any(s1 != s2, axis=-1)))} nodes differ; "
            f"first tree {t} node {i}: (feature, thr_bin, split) "
            f"{tuple(s1[t, i])} vs {tuple(s2[t, i])}{g}")
    for field in ("leaf_ref", "n_trees"):
        check(np.array_equal(np.asarray(getattr(f1, field)),
                             np.asarray(getattr(f2, field))),
              f"{what}: {field} differs")
    dv = float(np.max(np.abs(np.asarray(f1.leaf_values)
                             - np.asarray(f2.leaf_values))))
    check(dv <= TOL, f"{what}: leaf values differ by {dv:.3e} > {TOL}")
    return dv


def lowers_to_kernel(jitted, *args, **kwargs) -> bool:
    """Whether a jitted program, lowered as it ran, holds a Mosaic kernel."""
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).as_text()


# ---------------------------------------------------------------- one chip
def phase_train(wl, rows: int, parity_rows: int):
    import jax.numpy as jnp

    from repro.api import ToadModel
    from repro.gbdt import apply_bins, train_jit

    cfg = dataclasses.replace(wl.gbdt, hist_method="pallas")
    log(f"[train] rows={rows} d={wl.n_features} bins={wl.n_bins} "
        f"depth={cfg.max_depth} rounds={cfg.n_rounds} "
        f"penalties=({cfg.toad_penalty_feature}, "
        f"{cfg.toad_penalty_threshold}) hist=pallas")
    with Timer("data on device"):
        X, y = make_data(rows, wl.n_features, SEED)
        X, y = np.asarray(X), np.asarray(y)
    with Timer("ToadModel.fit (binning + compile + train)"):
        model = ToadModel(config=cfg, n_bins=wl.n_bins).fit(X, y)
        n_trees = int(model.forest.n_trees)
    acc = float(np.mean((model.predict(X[:BATCH_ROWS])[:, 0] > 0)
                        == (y[:BATCH_ROWS] > 0)))
    log(f"  trees={n_trees} accepted_rounds="
        f"{int(np.sum(np.asarray(model.history['accepted'])))} "
        f"train_accuracy@{BATCH_ROWS}={acc:.4f}")
    check(n_trees == cfg.n_rounds, f"{n_trees} trees, want {cfg.n_rounds}")

    hist_parity(bins_rows=parity_rows, wl=wl)
    log(f"[train parity] pallas vs fused histograms on {parity_rows} rows "
        f"(cut from {rows}: the fused path does not fit one chip's HBM "
        f"there), first tree, balanced labels")
    X_p, y_p, edges = parity_problem(parity_rows, wl, SEED + 2)
    bins = apply_bins(X_p, jnp.asarray(edges))
    del X_p
    models = {}
    for method in ("pallas", "fused"):
        with Timer(f"train hist={method}"):
            mcfg = dataclasses.replace(cfg, hist_method=method, n_rounds=1)
            models[method] = ToadModel(config=mcfg, n_bins=wl.n_bins) \
                .fit_binned(bins, y_p, edges)
            int(models[method].forest.n_trees)
    dv = same_trees(models["pallas"].forest, models["fused"].forest,
                    "pallas vs fused",
                    gains=[m.aux["node_gain"] for m in models.values()])
    log(f"  identical feature/thr_bin/is_split/leaf_ref over "
        f"{int(np.sum(np.asarray(models['pallas'].forest.is_split)))} splits; "
        f"max|Δ leaf value|={dv:.3e}")
    kernel = lowers_to_kernel(train_jit, cfg, bins, y_p, jnp.asarray(edges))
    return model, X, y, kernel


def hist_parity(bins_rows: int, wl, n_nodes: int = 8) -> None:
    """One level's histograms, Pallas vs fused, at real widths: within fp32
    accumulation error of each other (scaled by the bin's sum of |g|, |h|)
    and with identical counts."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import build_histogram

    k1, k2, k3, k4 = jax.random.split(jax.random.key(SEED + 3), 4)
    n, d, B = bins_rows, wl.n_features, wl.n_bins
    bins = jax.random.randint(k1, (n, d), 0, B, jnp.int32)
    gh = jnp.stack([jax.random.normal(k2, (n,)),
                    jax.random.uniform(k3, (n,), minval=0.1), jnp.ones(n)], -1)
    pos = jax.random.randint(k4, (n,), 0, n_nodes, jnp.int32)
    hist = {m: np.asarray(build_histogram(bins, gh, pos, n_nodes=n_nodes,
                                          n_bins=B, method=m))
            for m in ("pallas", "fused")}
    scale = np.asarray(build_histogram(bins, jnp.abs(gh), pos, n_nodes=n_nodes,
                                       n_bins=B, method="fused"))
    err = np.abs(hist["pallas"] - hist["fused"])
    log(f"[histogram parity] {n} rows, d={d}, {B} bins, {n_nodes} nodes: "
        f"max|Δ|={err.max():.3e}, "
        f"max|Δ|/Σ|gh|={np.max(err / np.maximum(scale, 1e-30)):.3e}")
    check(np.array_equal(hist["pallas"][..., 2], hist["fused"][..., 2]),
          "histogram counts differ")
    check(np.all(err <= TOL * scale), "histograms differ beyond fp32 error")


def phase_serve(model, X):
    from repro.api import EarlyExitPolicy, GBDTEngine
    from repro.gbdt.early_exit import predict_label_from_scores
    from repro.kernels.ops import _interp
    from repro.kernels.predict import _packed_predict_ee_call, packed_predict

    with Timer("compress"):
        model.compress()
    log(f"[serve] {model.memory_report()['toad_bytes']:.0f} B ToaD stream, "
        f"{N_REQUESTS} requests from {N_CLIENTS} clients + one "
        f"{BATCH_ROWS}-row batch, backend=pallas, no resilience policy")
    rng = np.random.default_rng(SEED)
    queries = X[rng.integers(0, X.shape[0], N_REQUESTS)]
    ref = model.predict(queries, backend="reference")
    got = np.zeros_like(ref)
    errors = []

    def client(lo, hi):
        try:
            futs = [engine.submit(queries[i]) for i in range(lo, hi)]
            for i, f in zip(range(lo, hi), futs):
                got[i] = f.result(timeout=600)
        except Exception as exc:  # surfaced by the main thread
            errors.append(exc)

    engine = GBDTEngine(model, backend="pallas")
    with Timer("engine start (warms every batch bucket)"):
        engine.start()
    try:
        with Timer(f"{N_REQUESTS} requests"):
            step = N_REQUESTS // N_CLIENTS
            threads = [threading.Thread(target=client, args=(c, c + step))
                       for c in range(0, N_REQUESTS, step)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        check(not errors, f"client error: {errors[:1]!r}")
        batch = X[:BATCH_ROWS]
        with Timer(f"{BATCH_ROWS}-row batch"):
            out = engine.predict(batch)
        s = engine.stats()
    finally:
        engine.stop()
    err_req = float(np.max(np.abs(got - ref)))
    err_batch = float(np.max(np.abs(
        out - model.predict(batch, backend="reference"))))
    log(f"  requests={s.n_requests} batches={s.n_batches} "
        f"mean_batch={s.mean_batch:.1f} active_backend={s.active_backend} "
        f"fallback_batches={s.n_fallback_batches} "
        f"degraded_starts={s.n_degraded_starts}")
    log(f"  max|Δ| vs reference: requests={err_req:.3e} batch={err_batch:.3e}")
    check(s.n_requests == N_REQUESTS, f"served {s.n_requests} requests")
    check(s.active_backend == "pallas", f"active backend {s.active_backend}")
    check(s.n_fallback_batches == 0, f"{s.n_fallback_batches} fallback batches")
    check(s.n_degraded_starts == 0, f"degraded start: {s.primary_start_error}")
    check(max(err_req, err_batch) <= TOL, "score parity above 1e-5")

    log(f"[serve early-exit] {EE_REQUESTS} requests, epsilon=0.0")
    ee = GBDTEngine(model, backend="pallas",
                    early_exit=EarlyExitPolicy(epsilon=0.0))
    check(ee._early_exit.mode == "kernel", f"early-exit mode {ee._early_exit.mode}")
    ee_q = queries[:EE_REQUESTS]
    with Timer("early-exit start + requests"):
        with ee:
            ee_out = np.stack([f.result(timeout=600)
                               for f in [ee.submit(q) for q in ee_q]])
            s_ee = ee.stats()
    task = model.config.task
    mism = int(np.sum(predict_label_from_scores(ee_out, task)
                      != predict_label_from_scores(ref[:EE_REQUESTS], task)))
    log(f"  label mismatches={mism} mean_trees_evaluated="
        f"{s_ee.mean_trees_evaluated:.2f}/{int(model.forest.n_trees)} "
        f"fallback_batches={s_ee.n_fallback_batches}")
    check(mism == 0, f"{mism} early-exit label mismatches")
    check(s_ee.n_fallback_batches == 0 and s_ee.n_degraded_starts == 0,
          "early-exit engine fell back")

    p = model.packed
    arrays = [np.asarray(getattr(p, f)) for f in (
        "words", "leaf_ref", "leaf_values", "thr_table", "thr_offsets",
        "used_features", "base_score")]
    static = dict(max_depth=p.max_depth, tidx_bits=p.tidx_bits,
                  n_ensembles=p.n_ensembles, interpret=_interp())
    predict_kernel = lowers_to_kernel(packed_predict, batch, *arrays, **static)
    C, T = p.n_ensembles, arrays[0].shape[0]
    n_blocks = -(-T // (-(-8 // C) * C))
    ee_kernel = lowers_to_kernel(
        _packed_predict_ee_call, ee_q, *arrays,
        np.zeros((n_blocks, C), np.float32), np.zeros(C, np.float32),
        n_rows=EE_REQUESTS, guard=0.0, **static)
    return predict_kernel and ee_kernel


def one_chip() -> None:
    from repro.configs.toad_gbdt import config

    wl = config()
    model, X, _, train_kernel = phase_train(wl, FULL_ROWS, PARITY_ROWS)
    serve_kernel = phase_serve(model, X)
    log(f"[kernels] tpu_custom_call in training program: {train_kernel}; "
        f"in serving programs: {serve_kernel}")
    check(train_kernel and serve_kernel, "a program ran without its kernel")


# -------------------------------------------------------------- four chips
def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.toad_gbdt import config
    from repro.gbdt import apply_bins, fit_bins, train_jit
    from repro.gbdt.distributed import train_data_parallel

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    rows_sharding = NamedSharding(mesh, P("data"))
    wl = config()
    cfg = dataclasses.replace(wl.gbdt, hist_method="pallas")
    rows = FULL_ROWS
    log(f"[data-parallel] rows={rows} over mesh {dict(mesh.shape)} "
        f"vs one device; d={wl.n_features} bins={wl.n_bins} "
        f"depth={cfg.max_depth} hist=pallas, first tree, balanced labels")
    X, y, edges = parity_problem(rows, wl, SEED + 2)
    edges = jnp.asarray(edges)
    bins = apply_bins(X, edges)
    del X
    pcfg = dataclasses.replace(cfg, n_rounds=1)
    with Timer("one-device train"):
        f1, _, aux1 = train_jit(pcfg, bins, y, edges)
        int(f1.n_trees)
    bins_sh = jax.device_put(bins, rows_sharding)
    y_sh = jax.device_put(y, rows_sharding)
    del bins, y
    with Timer("data-parallel train"):
        f4, _, aux = train_data_parallel(pcfg, bins_sh, y_sh, edges, mesh)
        int(f4.n_trees)
    dv = same_trees(f1, f4, "one device vs data-parallel",
                    gains=[aux1["node_gain"], aux["node_gain"]])
    log(f"  identical feature/thr_bin/is_split/leaf_ref over "
        f"{int(np.sum(np.asarray(f1.is_split)))} splits; "
        f"max|Δ leaf value|={dv:.3e}")
    for name, arr in (("bins", bins_sh), ("preds", aux["preds"])):
        shards = {s.device: s.data.shape for s in arr.addressable_shards}
        log(f"  {name} shards: " + ", ".join(
            f"{d.id}:{shape}" for d, shape in sorted(
                shards.items(), key=lambda kv: kv[0].id)))
        check(len(shards) == 4 and all(
            shape[0] == rows // 4 for shape in shards.values()),
            f"{name} is not row-sharded over 4 devices")
    del bins_sh, y_sh, aux

    full = wl.rows
    fcfg = dataclasses.replace(cfg, n_rounds=FULL_ROUNDS)
    log(f"[data-parallel full] rows={full} (the config's) over 4 chips, "
        f"rounds cut from {cfg.n_rounds} to {FULL_ROUNDS} (chip time; every "
        f"round allocates the same)")
    with Timer("data + binning on 4 chips"):
        X, y = make_data(full, wl.n_features, SEED + 1, sharding=rows_sharding)
        edges = jnp.asarray(fit_bins(np.asarray(X[:EDGE_SAMPLE]), wl.n_bins))
        bins = jax.jit(jax.shard_map(
            lambda x: apply_bins(x, edges), mesh=mesh,
            in_specs=P("data"), out_specs=P("data")))(X)
        del X
    with Timer("compile"):
        train_full = jax.jit(lambda b, t, e: train_data_parallel(
            fcfg, b, t, e, mesh)).lower(bins, y, edges).compile()
    m = train_full.memory_analysis()
    log(f"  per-device program bytes: arguments={m.argument_size_in_bytes} "
        f"outputs={m.output_size_in_bytes} temporaries={m.temp_size_in_bytes}")
    with Timer("data-parallel train"):
        f, _, _ = train_full(bins, y, edges)
        check(int(f.n_trees) == FULL_ROUNDS, "full run grew too few trees")
    # device 0's peak also covers the one-device run above; 1-3 held only
    # their row shards before this run
    for d in devs[:4]:
        stats = d.memory_stats() or {}
        log(f"  device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel path on a 4-chip host")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        print(f"no TPU: JAX found {d0.platform!r} devices only",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[cache] {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[total] {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
