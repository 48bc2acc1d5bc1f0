"""The control of each cell, at a size a test run holds, comes out not correct.

Training: the reference computed from bfloat16 gradients and hessians, put
in the program's place on the program's own trees.  Scoring: the reference
computed in bfloat16, put in the program's place.
"""

import json

import jax.numpy as jnp
import numpy as np

import bench_tiny
from bench import harness


def test_training_control_bf16_reference():
    from repro.api import ToadModel

    from bench.cells import train

    cfg = json.loads((bench_tiny.ROOT / "bench/configs/higgs.json").read_text())
    cfg.update(bench_tiny.TINY_CONFIG["higgs"])
    tr = json.loads((bench_tiny.ROOT / "bench/traffic/train.json").read_text())
    bins, y, edges = harness.load_piece("data", "higgs").make(cfg)
    model = ToadModel(config=train.program_config(cfg), n_bins=cfg["n_bins"])
    model.fit_binned(bins, y, edges)
    f = model.forest
    prog = {k: np.asarray(getattr(f, k)) for k in
            ("feature", "thr_bin", "is_split", "leaf_ref", "leaf_values")}
    prog["node_gain"] = np.asarray(model.aux["node_gain"])
    prog["leaf_cnt"] = np.asarray(model.aux["leaf_cnt"])
    ref = harness.load_piece("reference", "train_check")
    args = (np.ascontiguousarray(np.asarray(bins).T), np.asarray(y), np.asarray(edges),
            prog, cfg)
    kw = dict(n_trees=2, top_levels=2)
    sound, control = ref.check(*args, **kw), ref.check(*args, **kw, control="bfloat16")
    lim = tr["limits"]
    assert all(sound[k] <= lim[k] for k in lim)
    assert any(control[k] > lim[k] for k in lim)


def test_scoring_control_bf16_reference(monkeypatch):
    from repro.kernels import ops

    cfg = dict(json.loads(
        (bench_tiny.ROOT / "bench/configs/covtype.json").read_text()),
        **bench_tiny.TINY_CONFIG["covtype"])
    desc = harness.load_piece("data", "forest_pool").make(cfg)
    ref = harness.load_piece("reference", "forest")

    def control(packed, x):
        return jnp.asarray(ref.score(desc, np.asarray(x), "bfloat16"), jnp.float32)

    monkeypatch.setattr(ops, "predict_packed_model", control)
    run = bench_tiny.tiny_run("covtype.batch")
    assert not run.correct
