"""Training launcher.

    # LM path (reduced config on CPU; production config on a real pod):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

    # the paper's workload (ToaD GBDT) end-to-end:
    PYTHONPATH=src python -m repro.launch.train --arch toad_gbdt --dataset covtype_binary

On a real cluster this process is launched once per host with
jax.distributed.initialize(); the mesh comes from launch.mesh and all
shardings are identical to the dry-run's.
"""

from __future__ import annotations

import argparse


def train_lm(args):
    import jax

    from repro.launch.mesh import make_mesh

    from repro.configs import get_config, get_reduced
    from repro.models.registry import get_model
    from repro.train.loop import fit, lm_batch_fn

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = get_model(cfg)
    batch_fn = lm_batch_fn(cfg, n_docs=1000, seq=args.seq, batch=args.batch)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params, losses = fit(
            model, batch_fn, steps=args.steps,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        )
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss must decrease"


def train_gbdt(args):
    import jax.numpy as jnp

    from repro.core import compression_summary, encode, reuse_factor
    from repro.data.pipeline import split_dataset
    from repro.data.synth import load
    from repro.gbdt import GBDTConfig, apply_bins, make_loss, predict_binned, train_jit

    ds = load(args.dataset, seed=1)
    sp = split_dataset(ds, seed=1, n_bins=64)
    cfg = GBDTConfig(
        task=ds.task, n_classes=ds.n_classes, n_rounds=args.steps or 64,
        max_depth=3, learning_rate=0.15,
        toad_penalty_feature=args.penalty_feature,
        toad_penalty_threshold=args.penalty_threshold,
        toad_forestsize=args.forestsize,
    )
    edges = jnp.asarray(sp.edges)
    bins = apply_bins(jnp.asarray(sp.x_train), edges)
    forest, hist, aux = train_jit(cfg, bins, jnp.asarray(sp.y_train), edges)
    loss = make_loss(ds.task, ds.n_classes)
    test_pred = predict_binned(forest, apply_bins(jnp.asarray(sp.x_test), edges))
    metric = float(loss.metric(jnp.asarray(sp.y_test), test_pred))
    summary = compression_summary(forest)
    print(f"dataset={ds.name} metric={metric:.4f}")
    print(f"toad bytes={summary['toad_bytes']:.0f} "
          f"(x{summary['compression_vs_f32']:.1f} vs fp32 pointer)")
    print(f"ReF={reuse_factor(forest):.2f}")
    enc = encode(forest)
    print(f"encoded stream: {enc.n_bytes:.1f} bytes")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--dataset", default="covtype_binary")
    ap.add_argument("--penalty-feature", type=float, default=4.0)
    ap.add_argument("--penalty-threshold", type=float, default=1.0)
    ap.add_argument("--forestsize", type=float, default=0.0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.arch == "toad_gbdt":
        train_gbdt(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
