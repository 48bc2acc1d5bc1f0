"""Distribution: data-parallel GBDT parity, quantized collectives,
checkpoint round-trip + elastic resharding, crash/resume."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.gbdt import GBDTConfig, apply_bins, fit_bins, predict_binned, train_jit
from repro.gbdt.distributed import train_data_parallel
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 host devices (see conftest XLA_FLAGS)"
)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    n, d = 2048, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float32)
    edges = jnp.asarray(fit_bins(X, 32))
    return apply_bins(jnp.asarray(X), edges), jnp.asarray(y), edges


def test_data_parallel_exact_parity(data):
    bins, y, edges = data
    cfg = GBDTConfig(task="binary", n_rounds=10, max_depth=3)
    f1, h1, _ = train_jit(cfg, bins, y, edges)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
    f2, h2, _ = train_data_parallel(cfg, bins, y, edges, mesh)
    assert bool(jnp.all(f1.feature == f2.feature))
    assert bool(jnp.all(f1.thr_bin == f2.thr_bin))
    assert bool(jnp.all(f1.is_split == f2.is_split))
    np.testing.assert_allclose(
        np.asarray(f1.leaf_values), np.asarray(f2.leaf_values), atol=2e-5
    )


def test_quantized_histogram_collective_quality(data):
    bins, y, edges = data
    cfg = GBDTConfig(task="binary", n_rounds=10, max_depth=3)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
    f_exact, _, _ = train_data_parallel(cfg, bins, y, edges, mesh)
    f_q16, _, _ = train_data_parallel(cfg, bins, y, edges, mesh, hist_quant_bits=16)
    acc_e = float(jnp.mean((predict_binned(f_exact, bins)[:, 0] > 0) == y))
    acc_q = float(jnp.mean((predict_binned(f_q16, bins)[:, 0] > 0) == y))
    assert acc_q > acc_e - 0.02  # int16 histograms are quality-neutral


def test_ef_quantized_psum_unbiased_over_steps():
    from functools import partial

    from repro.distributed.collectives import ef_quantized_psum

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(4, 64)).astype(np.float32)

    @partial(
        jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False,
    )
    def step(x, err):
        out, err = ef_quantized_psum(x[0], err[0], "data", bits=8)
        return out[None], err[None]

    err = jnp.zeros((4, 64), jnp.float32)
    true_sum = xs.sum(axis=0)
    acc_q = np.zeros(64)
    acc_t = np.zeros(64)
    for _ in range(30):
        out, err = step(jnp.asarray(xs), err)
        acc_q += np.asarray(out[0])
        acc_t += true_sum
    # error feedback keeps the *accumulated* signal unbiased
    rel = np.abs(acc_q - acc_t).max() / np.abs(acc_t).max()
    assert rel < 0.01


def test_checkpoint_roundtrip_and_reshard(tmp_path):
    from repro.distributed import checkpoint as ckpt

    tree = {
        "w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
        "step": jnp.asarray(7, jnp.int32),
    }
    path = ckpt.save(str(tmp_path), 7, tree)
    assert os.path.basename(path) == "step-7"
    assert ckpt.latest_step(str(tmp_path)) == 7

    restored = ckpt.restore(str(tmp_path), 7, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # elastic: restore onto a 2x2 mesh with a different sharding
    mesh = make_mesh((2, 2), ("data", "model"))
    shardings = {
        "w": NamedSharding(mesh, P("data", "model")),
        "nested": {"b": NamedSharding(mesh, P(None))},
        "step": NamedSharding(mesh, P()),
    }
    resharded = ckpt.restore(str(tmp_path), 7, tree, shardings)
    np.testing.assert_array_equal(np.asarray(resharded["w"]), np.asarray(tree["w"]))
    assert resharded["w"].sharding.spec == P("data", "model")


def test_crash_resume_bit_exact(tmp_path):
    """Simulated node failure: train 6 steps with ckpt every 2, then 'crash'
    and restart from step 4 — final params must match an uninterrupted run."""
    from repro.configs import get_reduced
    from repro.models.registry import get_model
    from repro.train.loop import fit, lm_batch_fn

    cfg = get_reduced("qwen3-4b")
    model = get_model(cfg)
    batch_fn = lm_batch_fn(cfg, n_docs=100, seq=16, batch=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        p_full, losses_full = fit(model, batch_fn, steps=6, ckpt_dir=None)
        d1 = str(tmp_path / "run")
        fit(model, batch_fn, steps=4, ckpt_dir=d1, ckpt_every=2)  # "crashes" at 4
        p_resumed, losses_resumed = fit(model, batch_fn, steps=6, ckpt_dir=d1, ckpt_every=2)
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_resumed)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)
