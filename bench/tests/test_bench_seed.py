"""--seed changes only the row order (higgs) or only the rows (covtype)."""

import os
import pathlib
import subprocess
import sys

import numpy as np

import bench_tiny
from bench import harness

CFG = {"data_seed": 5, "rows": 3000, "n_bins": 32, "edge_sample": 1024}


def test_higgs_seed_only_permutes_the_fixed_dataset():
    higgs = harness.load_piece("data", "higgs")
    bins, y, edges = (np.asarray(a) for a in higgs.make(CFG))
    again = [np.asarray(a) for a in higgs.make(CFG)]
    np.testing.assert_array_equal(bins, again[0])
    np.testing.assert_array_equal(edges, again[2])
    rows = lambda b, t: sorted(map(tuple, np.column_stack([b, t]).tolist()))
    for seed in (1, 2**31 + 3):
        pb, py = (np.asarray(a) for a in higgs.permute(bins, y, seed))
        assert not np.array_equal(pb, bins)
        assert rows(pb, py) == rows(bins, y)
    assert bins.shape == (3000, 28) and 0.3 < y.mean() < 0.7


def test_covtype_seed_draws_rows_and_not_the_model():
    pool = harness.load_piece("data", "forest_pool")
    cfg = dict(bench_tiny.TINY_CONFIG["covtype"], model_seed=3, n_classes=7,
               n_bins=256, thr_pool=8, leaf_pool=64, leaf_scale=0.1, p_unsplit=0.1)
    a, b = pool.make(cfg), pool.make(cfg)
    for k in ("feature", "thr_bin", "is_split", "leaf_ref", "leaf_values", "edges"):
        np.testing.assert_array_equal(a[k], b[k])
    cov = harness.load_piece("data", "covtype")
    r1, r2 = cov.rows(500, 1), cov.rows(500, 2**31 + 1)
    assert r1.shape == r2.shape == (500, 54)
    assert not np.array_equal(r1, r2)
    np.testing.assert_array_equal(r1, cov.rows(500, 1))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "covtype.batch", "--seed", "1",
         "--seconds", "1"], cwd=bench_tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode == harness.EXIT_NO_CHIP
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_higgs_binning_in_blocks_counts_the_edges_below():
    import jax.numpy as jnp

    higgs = harness.load_piece("data", "higgs")
    rng = np.random.default_rng(0)
    n = higgs.BIN_ROWS + 1000           # more than one block
    x = rng.normal(size=(n, 3)).astype(np.float32)
    edges = np.sort(rng.normal(size=(3, 15)), axis=1).astype(np.float32)
    edges[2, 10:] = np.inf
    got = np.asarray(higgs.bin_rows(jnp.asarray(x), jnp.asarray(edges)))
    want = (x[:, :, None] > edges[None, :, :]).sum(-1)
    np.testing.assert_array_equal(got, want)
