#!/usr/bin/env python3
"""Read the numbers a cell compares under its control and planted faults.

    python bench/control.py --workload higgs.train --variants sound bf16 half frozen altered --seeds 11 12 13
    python bench/control.py --workload covtype.batch --variants bf16 --seeds 11 12 13

Not part of a benchmark run: this is how the limits in the traffic files
were set (lower reading from sound runs, upper from the control and the
faults).  One process reads every (variant, seed) at the cell's own size
and prints one JSON line each.

Training variants, each one job of the configuration's rounds:
  sound    the program as the configuration states it;
  ref_bf16 a sound run, read as it stands and then with the control: the
           gains and leaf values the reference computes from bfloat16
           gradients and hessians put in the program's place;
  bf16     the program's own lower-precision path, ``hist_dtype="bf16"``
           (on the TPU the compiler folds its rounding away, so it reads
           as sound);
  nopen    the ToaD penalties left out (ι = ξ = 0);
  half     half of the rows left out, the statistics taken over the rest;
  frozen   every tree's contribution dropped, so the scores never move;
  altered  one leaf value of the first tree negated where it is produced.
Scoring variant:
  bf16     the control: the reference in bfloat16 in the program's place,
           compared on the cell's own rows and sample.
"""

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def emit(out, variant, seed, got, prog=None, **extra):
    """One JSON line of the compared numbers; with ``out``, the per-node
    split readings and the program's trees go to ``<out>/<variant>_<seed>.npz``."""
    nodes = got.pop("split_nodes", None)
    if out is not None and nodes is not None:
        trees = {k: prog[k] for k in ("feature", "thr_bin", "is_split")} if prog else {}
        np.savez(out / f"{variant}_{seed}.npz", split_nodes=nodes, **trees)
    print(json.dumps({"variant": variant, "seed": seed, **got, **extra}), flush=True)


def training(run, variants, seeds, out=None):
    import jax
    import jax.numpy as jnp

    from repro.api import ToadModel
    from repro.gbdt import trainer

    from bench.cells import train as driver

    cfg, tr = run.config, run.traffic
    data = run.piece("data", cfg["generator"])
    bins0, y0, edges = data.make(cfg)
    edges_h = np.asarray(edges)
    grow = trainer._grow_tree
    for variant in variants:
        jax.clear_caches()
        trainer._grow_tree = grow
        over = {"bf16": {"hist_dtype": "bf16"},
                "nopen": {"toad_penalty_feature": 0.0,
                          "toad_penalty_threshold": 0.0}}.get(variant, {})
        if variant == "frozen":
            def frozen(*a, **k):
                tree, contrib, n_sp, state = grow(*a, **k)
                return tree, jnp.zeros_like(contrib), n_sp, state
            trainer._grow_tree = frozen
        for seed in seeds:
            t0 = time.perf_counter()
            bins, y = data.permute(bins0, y0, seed)
            model = ToadModel(config=driver.program_config(cfg, **over),
                              n_bins=int(cfg["n_bins"]))
            if variant == "half":
                n = bins.shape[0] // 2
                model.fit_binned(bins[:n], y[:n], edges)
            else:
                model.fit_binned(bins, y, edges)
            f = model.forest
            if variant == "altered":
                slot = f.leaf_ref[0, 0]
                f = dataclasses.replace(f, leaf_values=f.leaf_values.at[slot].multiply(-1.0))
            prog = {k: np.asarray(getattr(f, k)) for k in
                    ("feature", "thr_bin", "is_split", "leaf_ref", "leaf_values")}
            prog["node_gain"] = np.asarray(model.aux["node_gain"])
            prog["leaf_cnt"] = np.asarray(model.aux["leaf_cnt"])
            bins_t = np.ascontiguousarray(np.asarray(bins).T)
            y_h = np.asarray(y)
            del model, f, bins, y
            check = run.piece("reference", "train_check").check
            kw = dict(n_trees=min(int(tr["check_trees"]), int(cfg["n_rounds"])),
                      top_levels=int(tr["top_levels"]))
            t1 = time.perf_counter()
            got = check(bins_t, y_h, edges_h, prog, cfg, **kw)
            emit(out, variant, seed, got, prog, s=t1 - t0, reference_s=time.perf_counter() - t1)
            if variant == "ref_bf16":
                got = check(bins_t, y_h, edges_h, prog, cfg, **kw, control="bfloat16")
                emit(out, "ref_bf16.control", seed, got)
    trainer._grow_tree = grow


def scoring(run, variants, seeds):
    from bench.cells.batch import sample

    cfg, tr = run.config, run.traffic
    desc = run.piece("data", cfg["generator"]).make(cfg)
    ref = run.piece("reference", "forest")
    rows = run.piece("data", cfg["rows_generator"]).rows
    n, k = int(tr["rows"]), int(tr["check_rows"])
    for variant in variants:
        if variant != "bf16":
            raise ValueError(f"no scoring variant {variant!r}")
        for seed in seeds:
            x = rows(n, seed)[sample(seed, n, k)]
            gap = np.max(np.abs(ref.score(desc, x, "bfloat16") - ref.score(desc, x)))
            print(json.dumps({"variant": variant, "seed": seed, "score_gap": float(gap)}),
                  flush=True)


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--variants", nargs="+", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--out", help="directory for per-node split readings and trees")
    args = p.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    wl, config, traffic = harness.find_cell(spec, args.workload)
    devices = harness.require_chips(int(wl["chips"]))
    harness.enable_compile_cache()
    run = harness.Run(workload=wl, config=config, traffic=traffic, seed=0,
                      seconds=0.0, trace=False, t_process=time.perf_counter(),
                      devices=devices)
    if traffic["kind"] == "train":
        out = None
        if args.out:
            out = pathlib.Path(args.out)
            out.mkdir(parents=True, exist_ok=True)
        training(run, args.variants, args.seeds, out)
    else:
        scoring(run, args.variants, args.seeds)


if __name__ == "__main__":
    main(sys.argv[1:])
