"""Serving launcher: one engine per model family behind one CLI.

    # LM path — batched prefill + decode loop:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --batch 4 --prompt-len 32 --decode-steps 16

    # GBDT path — the paper's deployed model behind the micro-batching
    # engine, through any predictor backend:
    PYTHONPATH=src python -m repro.launch.serve --arch toad-gbdt \
        --backend packed --requests 2048
    PYTHONPATH=src python -m repro.launch.serve --arch toad-gbdt \
        --backend reference --smoke

    # GBDT path from a prebuilt, versioned .toad artifact (no retraining):
    PYTHONPATH=src python -m repro.launch.serve --arch toad-gbdt \
        --model model.toad --smoke

    # Fleet path — a directory of .toad artifacts behind one router with
    # cross-model codebook dedup and hot-swap (see repro.launch.fleet):
    PYTHONPATH=src python -m repro.launch.serve --arch toad-fleet \
        --models fleet_dir/ --smoke

``--model`` is the deployment path: artifacts are produced offline (e.g.
``examples/train_toad.py --compress-budget B --export-artifact m.toad``,
which walks the budget ladder — exact -> fp16 leaves -> leaf/threshold
codebooks — and keeps the first plan that fits B), structurally verified
(toadcheck) and fingerprint-verified at load, and served through any
predictor backend without retraining.

On production meshes the LM functions lower against the sequence-sharded
cache (see launch/dryrun.py decode cells); here the reduced configs run the
actual loops on CPU to prove both serving paths end to end.
"""

from __future__ import annotations

import argparse
import time


def serve_lm(args) -> None:
    """Batched prefill + decode loop over the LM stack."""
    import jax

    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.configs import get_config, get_reduced
    from repro.models.registry import get_model

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = get_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    B, S = args.batch, args.prompt_len
    max_seq = S + args.decode_steps
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    with jax.set_mesh(mesh):
        if cfg.family == "encdec":
            batch = {
                "frames": jnp.ones((B, S // cfg.frontend_len_div, cfg.d_model), jnp.bfloat16),
                "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
            }
        elif cfg.family == "vlm":
            pe = S // cfg.frontend_len_div
            batch = {
                "embeds": jnp.ones((B, pe, cfg.d_model), jnp.bfloat16),
                "tokens": jax.random.randint(key, (B, S - pe), 0, cfg.vocab),
            }
        else:
            batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}

        logits, cache = jax.jit(lambda p, b: model.prefill(p, b))(params, batch)

        # grow attention caches to max_seq
        def pad_cache(c):
            def pad(x):
                if hasattr(x, "ndim") and x.ndim == 5:  # (L, B, S, KV, dh)
                    return jnp.pad(
                        x, ((0, 0), (0, 0), (0, max_seq - x.shape[2]), (0, 0), (0, 0))
                    )
                return x
            return jax.tree.map(pad, c)

        if cfg.family in ("dense", "moe", "vlm"):
            cache = pad_cache(cache)
        elif cfg.family == "encdec":
            cache = dict(cache)
            for k in ("k", "v"):
                cache[k] = jnp.pad(
                    cache[k], ((0, 0), (0, 0), (0, max_seq - cache[k].shape[2]), (0, 0), (0, 0))
                )

        step = jax.jit(
            lambda p, c, t, pos: model.decode_step(mesh, p, c, t, pos)
        )
        tok = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)
        out_tokens = [tok]
        t0 = time.time()
        for i in range(args.decode_steps):
            logits, cache = step(params, cache, tok, jnp.asarray(S + i, jnp.int32))
            tok = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)
            out_tokens.append(tok)
        dt = time.time() - t0
        toks = jnp.stack(out_tokens, axis=1)
        print(f"decoded {args.decode_steps} steps x batch {B} in {dt:.2f}s "
              f"({args.decode_steps * B / dt:.1f} tok/s on CPU)")
        print("sample:", toks[0].tolist())


def serve_gbdt(args) -> dict:
    """Serve raw-feature requests through the micro-batching engine and the
    chosen predictor backend.  With ``--model path.toad`` a prebuilt
    artifact is loaded (fingerprint-verified) and served directly — no
    in-process training; otherwise a small ToaD model is trained and
    compressed on the spot."""
    import threading

    import numpy as np

    from repro.api import GBDTEngine, ToadModel, available_backends, get_backend
    from repro.api.resilience import DeadlineExceeded, Overloaded, resolve_policy
    from repro.configs import get_gbdt_config

    policy = resolve_policy(args)
    ee_policy = None
    if getattr(args, "early_exit", None) is not None:
        from repro.api import EarlyExitPolicy

        ee_policy = EarlyExitPolicy(epsilon=args.early_exit)

    backend = args.backend or "packed"
    if backend != "auto":
        get_backend(backend)  # fail fast on a typo'd name, before training

    n_requests = 256 if args.smoke else args.requests
    rng = np.random.default_rng(0)
    if getattr(args, "model", None):
        from repro.api.artifact import ArtifactError, load_checked

        print(f"verifying + loading artifact {args.model} ...")
        try:
            # the one shared admission path (toadcheck, then load +
            # fingerprint probe) — same as ToadModel.load and the fleet
            # registry, so serving policy cannot drift
            loaded = load_checked(args.model)
        except ArtifactError as e:
            # a serving host never decodes a structurally invalid bundle
            raise SystemExit(f"refusing to serve: {e}")
        print(f"toadcheck: ok ({len(loaded.warnings)} warning(s))")
        model = loaded.model
        if not model.is_compressed:
            model.compress()
        meta = model.artifact_meta or {}
        manifest = meta.get("manifest", {})
        spec = meta.get("spec") or {}
        print(f"artifact: format v{loaded.format_version}, "
              f"spec {spec.get('name', 'pre-spec')!r}, "
              f"{manifest.get('encoded_stream_bytes', 0):.0f} B encoded, "
              f"{manifest.get('n_trees', int(model.forest.n_trees))} trees")
        # probe with the artifact's own eval-fingerprint probe set (tiled to
        # the request count), so the smoke parity check exercises exactly
        # the inputs the artifact was fingerprinted on at save time
        from repro.core.pipeline import probe_inputs

        fp = meta.get("fingerprint") or {}
        probe = probe_inputs(model.forest, n=int(fp.get("n_probe", 32)),
                             seed=int(fp.get("seed", 7)))
        n_pool = max(n_requests, 256)
        X = np.tile(probe, (-(-n_pool // len(probe)), 1))[:n_pool]
    else:
        # always the reduced workload: the full config is the 16.7M-row
        # dry-run shape, not something to train in-process on a serving host
        wl = get_gbdt_config(args.arch, reduced=True)
        X = rng.normal(size=(wl.rows, wl.n_features)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] ** 2 > 0).astype(np.float32)

        print(f"training toad-gbdt (rows={wl.rows}, d={wl.n_features}, "
              f"rounds={wl.gbdt.n_rounds}, depth={wl.gbdt.max_depth}) ...")
        model = ToadModel(config=wl.gbdt, n_bins=wl.n_bins).fit(X, y).compress()
    report = model.memory_report()
    print(f"model: {int(report['n_trees'])} trees, "
          f"{report['toad_bytes']:.0f} B ToaD stream "
          f"({report['compression_vs_f32']:.1f}x vs fp32 pointers), "
          f"ReF={report['reuse_factor']:.2f}")
    print(f"backend: {backend} (available: {', '.join(available_backends())})")

    engine = GBDTEngine(
        model, backend=None if backend == "auto" else backend,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        policy=policy, early_exit=ee_policy,
    )
    queries = X[rng.integers(0, X.shape[0], size=n_requests)]
    errs = []
    mism = []  # early-exit mode: label mismatches per client

    def client(lo: int, hi: int):
        futs = [engine.submit(queries[i]) for i in range(lo, hi)]
        # under a resilience policy, shed (Overloaded) and expired
        # (DeadlineExceeded) requests are expected typed outcomes, not
        # failures — parity is checked on whatever completed
        out, idx = [], []
        for i, f in zip(range(lo, hi), futs):
            try:
                out.append(f.result())
                idx.append(i)
            except (Overloaded, DeadlineExceeded):
                if policy is None:
                    raise
        if idx:
            ref = model.predict(queries[idx], backend="reference")
            if ee_policy is not None:
                # exited rows carry partial sums, so score parity is the
                # wrong check — the early-exit contract is exact labels
                from repro.gbdt.early_exit import predict_label_from_scores

                task = model.config.task
                got = np.stack(out).reshape(len(idx), -1).astype(np.float64)
                ref2 = np.asarray(ref, np.float64).reshape(len(idx), -1)
                mism.append(int(np.sum(
                    predict_label_from_scores(got, task)
                    != predict_label_from_scores(ref2, task)
                )))
            else:
                errs.append(float(np.abs(np.stack(out) - ref).max()))

    with engine:
        threads = [
            threading.Thread(target=client, args=(c * n_requests // args.clients,
                                                  (c + 1) * n_requests // args.clients))
            for c in range(args.clients)
        ]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0

    s = engine.stats()
    max_err = max(errs) if errs else 0.0
    print(f"served {s.n_requests} requests in {wall:.2f}s — "
          f"{s.n_requests / wall:.1f} req/s, mean batch {s.mean_batch:.1f}, "
          f"p50 {s.latency_p50_ms:.2f} ms, p95 {s.latency_p95_ms:.2f} ms")
    if ee_policy is not None:
        n_mism = sum(mism)
        print(f"early-exit: trees_evaluated mean {s.mean_trees_evaluated:.2f}"
              f" / {int(model.forest.n_trees)} trees "
              f"(exact-label mismatches = {n_mism})")
        assert n_mism == 0, \
            f"{n_mism} early-exited request(s) changed predict_label"
    else:
        print(f"parity vs reference backend: max|Δ| = {max_err:.2e}")
    if policy is not None:
        print(f"resilience: shed={s.n_shed} "
              f"deadline_expired={s.n_deadline_expired} "
              f"worker_restarts={s.n_worker_restarts} "
              f"breaker={s.breaker_state} active={s.active_backend}")
        # every submitted request resolved: with a score, a shed, or an
        # expiry — the zero-stranded-futures contract, end to end
        assert s.n_requests + s.n_shed + s.n_deadline_expired == n_requests
    else:
        assert s.n_requests == n_requests and s.n_requests / wall > 0
    if ee_policy is None:
        assert max_err <= 1e-5
    return {**s.as_dict(), "req_per_s": s.n_requests / wall}


def main():
    from repro.api.resilience import add_resilience_args
    from repro.launch.fleet import add_fleet_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    # fleet engine (--arch toad-fleet): --models dir/, --dry-run, --max-hot,
    # --swap id=path
    add_fleet_args(ap)
    # serving resilience (gbdt + fleet): --deadline-ms, --max-queue,
    # --resilience spec.json
    add_resilience_args(ap)
    # LM engine
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=16)
    # GBDT engine
    ap.add_argument("--backend", default="auto",
                    help="predictor backend: auto|reference|packed|pallas")
    ap.add_argument("--model", default=None,
                    help="path to a prebuilt .toad artifact; serves it "
                         "directly instead of training in-process")
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="short run for CI (256 requests)")
    args = ap.parse_args()

    from repro.configs import is_gbdt_arch
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.arch in ("toad-fleet", "toad_fleet"):
        from repro.launch.fleet import serve_fleet

        if not args.models:
            ap.error("--arch toad-fleet requires --models dir/")
        serve_fleet(args)
    elif is_gbdt_arch(args.arch):
        serve_gbdt(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
