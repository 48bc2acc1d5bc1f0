"""Fleet serving launcher: many ``.toad`` artifacts behind one router.

    # Dry run: toadcheck every artifact, print the planned fleet manifest
    # (model ids, versions, negotiated formats, dedup plan) — no serving:
    PYTHONPATH=src python -m repro.launch.fleet --models fleet_dir/ --dry-run

    # Real serve mode: route client requests across every hosted model,
    # check routed predictions against each model's reference backend:
    PYTHONPATH=src python -m repro.launch.fleet --models fleet_dir/ \
        --requests 2048 --clients 4

    # CI smoke: short run + optional live hot-swap mid-traffic:
    PYTHONPATH=src python -m repro.launch.fleet --models fleet_dir/ \
        --smoke --swap tenant_a=new_model.toad

    # Progressive cold-start over .toadpack streaming containers: each
    # model answers from its first tree block, the rest stream in:
    PYTHONPATH=src python -m repro.launch.fleet --models fleet_dir/ \
        --smoke --streaming

    # Adaptive early exit: stop scoring a row once its label is provably
    # final within the margin bound (exact-label parity, fewer trees/row):
    PYTHONPATH=src python -m repro.launch.fleet --models fleet_dir/ \
        --smoke --early-exit 0.0

Also reachable through the serving CLI's arch dispatch::

    PYTHONPATH=src python -m repro.launch.serve --arch toad-fleet \
        --models fleet_dir/ --smoke

Admission is fail-fast: any artifact in the directory with an
error-severity toadcheck finding aborts the launch with exit status 1
(the registry refuses it), so a malformed bundle can never ride into a
fleet rollout.  Per-model probe queries reuse each artifact's eval
fingerprint probe set, so the parity check exercises the same inputs the
artifact was fingerprinted on.
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np


def _probe_queries(model, n: int) -> np.ndarray:
    """(n, d) queries from the artifact's own eval-fingerprint probe set."""
    fp = (model.artifact_meta or {}).get("fingerprint") or {}
    n_probe, seed = int(fp.get("n_probe", 32)), int(fp.get("seed", 7))
    if hasattr(model, "probe_inputs"):
        # streaming entries synthesize the probe from their header tables
        probe = model.probe_inputs(n=n_probe, seed=seed)
    else:
        from repro.core.pipeline import probe_inputs

        probe = probe_inputs(model.forest, n=n_probe, seed=seed)
    reps = -(-n // len(probe))  # ceil
    return np.tile(probe, (reps, 1))[:n]


def _print_manifest(manifest: dict) -> None:
    print(f"fleet manifest: {manifest['n_models']} model(s)")
    for mid, row in manifest["models"].items():
        enc = row["encoded_stream_bytes"]
        stream = f" stream={enc:.0f} B" if enc is not None else ""
        print(
            f"  {mid:20s} v{row['version']} format-v{row['format_version']} "
            f"spec={row['spec'] or 'pre-spec':16s} "
            f"trees={row['n_trees']:4d}{stream}"
        )
    dd = manifest["dedup"]
    print(
        f"dedup: {dd['n_tables']} table(s), {dd['n_shared_tables']} shared, "
        f"{dd['dedup_saved_bytes']:.0f} B saved"
    )


def serve_fleet(args) -> dict:
    """Load every artifact in ``--models`` into a verified registry and
    either print the planned manifest (``--dry-run``) or serve routed
    traffic with per-model parity checks (and optional live ``--swap``)."""
    from repro.api.artifact import ArtifactError
    from repro.api.resilience import DeadlineExceeded, Overloaded, resolve_policy
    from repro.fleet import FleetEngine, ModelRegistry

    policy = resolve_policy(args)
    streaming = bool(getattr(args, "streaming", False))
    ee_policy = None
    if getattr(args, "early_exit", None) is not None:
        from repro.api import EarlyExitPolicy

        ee_policy = EarlyExitPolicy(epsilon=args.early_exit)
    t0 = time.time()
    try:
        registry = ModelRegistry.from_dir(args.models, streaming=streaming)
    except ArtifactError as e:
        raise SystemExit(f"fleet admission refused: {e}")
    print(f"admitted {len(registry)} model(s) in {time.time() - t0:.2f}s "
          f"(toadcheck-verified{', streaming' if streaming else ''})")
    _print_manifest(registry.manifest())

    if getattr(args, "dry_run", False):
        report = registry.memory_report()
        print(
            f"planned residency: {report['standalone_total_bytes']:.0f} B "
            f"standalone -> {report['fleet_resident_bytes']:.0f} B fleet "
            f"({report['dedup_saved_bytes']:.0f} B deduped)"
        )
        print(json.dumps(report, indent=2, default=float))
        return report

    n_requests = 256 if args.smoke else args.requests
    backend = getattr(args, "backend", None)
    if backend in ("auto", None):
        backend = None
    engine = FleetEngine(
        registry,
        backend=backend,
        max_hot=getattr(args, "max_hot", 8),
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        policy=policy,
        streaming=streaming,
        early_exit=ee_policy,
    )

    ids = registry.ids()
    if streaming:
        # first-wave partial predictions: answer every streaming model from
        # whatever blocks have landed (no parity — scores may be partial),
        # then wait for completion so the traffic run below checks final
        # scores
        for mid in ids:
            entry = registry.get(mid)
            if not entry.is_streaming:
                continue
            q = _probe_queries(entry.model, 1)
            res = entry.model.scorer.predict(q)
            st = entry.model.streaming_stats()
            print(
                f"  first-wave {mid}: blocks {res.blocks_evaluated}/"
                f"{res.n_blocks} final={res.score_is_final} "
                f"ttfp={st['time_to_first_prediction_ms']:.1f} ms"
            )
        if ee_policy is not None:
            # cold-start + early exit: a FRESH scorer over the same
            # container stops pulling blocks once the partial sums are
            # provably decision-final for the probe batch
            from repro.stream.progressive import ProgressiveScorer
            from repro.stream.reader import open_streaming

            for mid in ids:
                entry = registry.get(mid)
                if not entry.is_streaming:
                    continue
                scorer = ProgressiveScorer(open_streaming(entry.path))
                q = _probe_queries(entry.model, 4)
                res = scorer.feed_until_confident(q, ee_policy)
                print(
                    f"  cold early-exit {mid}: trees_evaluated "
                    f"{res.trees_evaluated}, blocks {res.blocks_evaluated}/"
                    f"{res.n_blocks}, reason={res.exit_reason}"
                )
        engine.wait_complete()
        print("all streaming entries complete; scores below are final")
    queries = {
        mid: _probe_queries(registry.get(mid).model, n_requests)
        for mid in ids
    }
    errs: list[float] = []
    mism: list[int] = []  # early-exit mode: label mismatches
    rng = np.random.default_rng(0)
    # each client interleaves model ids, so same-model requests from
    # different clients land in the same batches (cross-tenant batching)
    plans = [
        [ids[int(k)] for k in rng.integers(0, len(ids), size=n_requests // args.clients)]
        for _ in range(args.clients)
    ]

    def client(plan):
        futs = []
        for i, mid in enumerate(plan):
            futs.append((mid, i, engine.submit(mid, queries[mid][i])))
        for mid, i, fut in futs:
            try:
                got = fut.result()
            except (Overloaded, DeadlineExceeded):
                # typed, expected outcomes under a resilience policy —
                # parity is checked on whatever completed
                if policy is None:
                    raise
                continue
            ref = registry.get(mid).model.predict(
                queries[mid][i : i + 1], backend="reference"
            )[0]
            entry = registry.get(mid)
            if ee_policy is not None and not entry.is_streaming:
                # exited rows carry partial sums — the contract is exact
                # labels, not score parity (streaming entries stay on full
                # evaluation, so they keep the strict score check below)
                from repro.gbdt.early_exit import predict_label_from_scores

                task = entry.model.config.task
                g = predict_label_from_scores(
                    np.asarray(got, np.float64).reshape(1, -1), task)
                r = predict_label_from_scores(
                    np.asarray(ref, np.float64).reshape(1, -1), task)
                mism.append(int(g[0] != r[0]))
            else:
                errs.append(float(np.abs(got - ref).max()))

    with engine:
        engine.warm(*ids)
        threads = [
            threading.Thread(target=client, args=(p,)) for p in plans
        ]
        t1 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t1

        swapped = {}
        for spec in getattr(args, "swap", None) or []:
            mid, _, path = spec.partition("=")
            if not path:
                raise SystemExit(f"--swap expects model_id=path, got {spec!r}")
            before = engine.version(mid)
            entry = engine.swap(mid, path)
            X = _probe_queries(entry.model, 64)
            got = np.stack([f.result() for f in
                            [engine.submit(mid, x) for x in X]])
            ref = entry.model.predict(X, backend="reference")
            if ee_policy is not None and not entry.is_streaming:
                from repro.gbdt.early_exit import predict_label_from_scores

                task = entry.model.config.task
                bad = int(np.sum(
                    predict_label_from_scores(
                        np.asarray(got, np.float64).reshape(len(X), -1), task)
                    != predict_label_from_scores(
                        np.asarray(ref, np.float64).reshape(len(X), -1), task)
                ))
                assert bad == 0, f"post-swap early-exit label parity: {bad}"
                parity = f"{bad} label mismatch(es)"
            else:
                err = float(np.abs(got - ref).max())
                assert err <= 1e-5, f"post-swap parity {err:.2e} > 1e-5"
                parity = f"max|Δ| {err:.2e}"
            assert entry.version == before + 1
            swapped[mid] = entry.version
            print(f"hot-swapped {mid!r}: v{before} -> v{entry.version} "
                  f"(post-swap parity {parity})")

        # breaker/active views are per *hot* backend: capture before stop()
        # retires them all
        live = engine.stats()

    stats = engine.stats()
    n_served = stats.fleet.n_requests
    n_checked = len(errs) + len(mism)
    max_err = max(errs) if errs else 0.0
    print(
        f"served {n_checked} routed requests across {len(ids)} models in "
        f"{wall:.2f}s — {n_checked / max(wall, 1e-9):.1f} req/s, "
        f"mean batch {stats.fleet.mean_batch:.1f}, "
        f"p95 {stats.fleet.latency_p95_ms:.2f} ms, "
        f"{stats.n_retired} retired backend(s)"
    )
    if ee_policy is not None:
        n_mism = sum(mism)
        print(f"early-exit: trees_evaluated mean "
              f"{stats.fleet.mean_trees_evaluated:.2f} per row over "
              f"{stats.fleet.n_early_exit_rows} rows "
              f"(exact-label mismatches = {n_mism}/{len(mism)})")
        assert n_mism == 0, \
            f"{n_mism} early-exited request(s) changed predict_label"
    else:
        print(f"parity vs per-model reference: max|Δ| = {max_err:.2e}")
    if policy is not None:
        print(f"resilience: shed={stats.n_shed} "
              f"deadline_expired={stats.n_deadline_expired} "
              f"worker_restarts={stats.n_worker_restarts} "
              f"breaker={live.breaker_state} active={live.active_backend}")
    report = registry.memory_report()
    print(
        f"residency: {report['standalone_total_bytes']:.0f} B standalone -> "
        f"{report['fleet_resident_bytes']:.0f} B fleet "
        f"({report['dedup_saved_bytes']:.0f} B deduped across models)"
    )
    assert max_err <= 1e-5
    assert n_served >= n_checked
    return {
        "stats": stats.as_dict(),
        "memory": report,
        "max_err": max_err,
        "swapped": swapped,
    }


def add_fleet_args(ap: argparse.ArgumentParser) -> None:
    """Fleet flags, shared with the serve CLI's --arch toad-fleet path."""
    ap.add_argument("--models", default=None,
                    help="directory of .toad artifacts; model_id = file stem")
    ap.add_argument("--dry-run", action="store_true",
                    help="verify + print the planned fleet manifest and "
                         "residency report without serving")
    ap.add_argument("--max-hot", type=int, default=8,
                    help="LRU size of warm per-model backends")
    ap.add_argument("--swap", action="append", default=None,
                    metavar="MODEL_ID=PATH",
                    help="after the traffic run, hot-swap MODEL_ID to the "
                         "artifact at PATH and assert the new version serves")
    ap.add_argument("--streaming", action="store_true",
                    help="progressive cold-start: serve .toadpack entries "
                         "from their first tree block while the rest stream "
                         "in (see docs/streaming.md)")
    ap.add_argument("--early-exit", type=float, default=None,
                    metavar="EPSILON",
                    help="adaptive early exit: stop evaluating a row once "
                         "its decision is provably final within EPSILON "
                         "margin slack (see docs/early_exit.md); parity "
                         "switches to exact-label equality")


def main():
    from repro.api.resilience import add_resilience_args

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_fleet_args(ap)
    add_resilience_args(ap)
    ap.add_argument("--backend", default="auto",
                    help="predictor backend: auto|reference|packed|pallas")
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="short run for CI (256 requests)")
    args = ap.parse_args()
    if not args.models:
        ap.error("--models is required")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    serve_fleet(args)


if __name__ == "__main__":
    main()
