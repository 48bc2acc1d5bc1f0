"""Benchmark harness: one runner per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # reduced (CI) sizes
    PYTHONPATH=src python -m benchmarks.run --full    # paper-scale grids

Prints ``name,us_per_call,derived`` CSV summary lines at the end; detailed
artifacts land in results/*.json.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[])
    args = ap.parse_args()

    from benchmarks import appd_random_forest, fig4_quality_memory, fig5_penalty_grid
    from benchmarks import fig6_univariate, fig7_multivariate, table2_latency

    summary = []

    def bench(name, fn):
        if name in args.skip:
            return
        t0 = time.time()
        out = fn()
        dt = time.time() - t0
        summary.append((name, dt, out))
        print(f"[{name}] done in {dt:.1f}s", flush=True)

    if args.full:
        bench("fig4", lambda: fig4_quality_memory.run(verbose=False))
        bench("fig6", lambda: fig6_univariate.run(verbose=False))
        bench("fig7", lambda: fig7_multivariate.run(verbose=False))
    else:
        bench("fig4", lambda: fig4_quality_memory.run(
            datasets=("covtype_binary", "california_housing"),
            n_rounds=96, seeds=(1,), n_cap=6000, verbose=False))
        bench("fig6", lambda: fig6_univariate.run(
            datasets=("covtype_binary", "california_housing"),
            n_rounds=48, n_cap=6000, verbose=False))
        bench("fig7", lambda: fig7_multivariate.run(
            datasets=("california_housing",), n_rounds=48, n_cap=6000, verbose=False))
    bench("fig5", lambda: fig5_penalty_grid.run_fig5(verbose=False))
    bench("appd_rf", lambda: appd_random_forest.run(verbose=False))
    bench("table2", lambda: table2_latency.run(verbose=False))

    def serve_bench():
        # end-to-end GBDT serving through the micro-batching engine
        ns = argparse.Namespace(
            arch="toad-gbdt", backend="packed", requests=1024, clients=4,
            max_batch=256, max_wait_ms=2.0, smoke=not args.full,
        )
        from repro.launch.serve import serve_gbdt

        return serve_gbdt(ns)

    bench("serve_gbdt", serve_bench)

    def coldstart_bench():
        # classic .toad load vs .toadpack progressive cold-start
        import json as _json

        from benchmarks import bench_coldstart

        bench_coldstart.run(smoke=not args.full, check=False, verbose=False)
        with open("BENCH_coldstart.json") as f:
            return _json.load(f)

    bench("coldstart", coldstart_bench)

    def early_exit_bench():
        # margin early exit: trees saved vs label exactness vs latency
        import json as _json

        from benchmarks import bench_early_exit

        bench_early_exit.run(smoke=not args.full, check=False, verbose=False)
        with open("BENCH_early_exit.json") as f:
            return _json.load(f)

    bench("early_exit", early_exit_bench)

    # trend checks + headline numbers
    print("\n=== summary (name,us_per_call,derived) ===")
    for name, dt, out in summary:
        derived = ""
        if name == "fig4" and out:
            s = fig4_quality_memory.summarize(out)
            ratios = [r["lgbm_f32_memory_multiple"] for r in s
                      if r.get("lgbm_f32_memory_multiple")]
            derived = (
                f"median_lgbm_memory_multiple="
                f"{sorted(ratios)[len(ratios)//2] if ratios else 'n/a'}"
            )
        elif name == "fig6" and out:
            derived = str(fig6_univariate.check_paper_trends(out))
        elif name == "fig5" and out:
            rows, best = out
            derived = (f"best@1KB: iota={best['penalty_feature']:.2g} "
                       f"xi={best['penalty_threshold']:.2g} metric={best['metric']:.3f}")
        elif name == "fig7" and out:
            derived = f"dominated_fraction={fig7_multivariate.nondominated_fraction(out)}"
        elif name == "table2" and out:
            derived = f"packed/dense={out[1]['derived']:.2f}x"
        elif name == "serve_gbdt" and out:
            derived = (f"req_per_s={out['req_per_s']:.0f} "
                       f"p95_ms={out['latency_p95_ms']:.2f}")
        elif name == "coldstart" and out:
            derived = (
                f"fleet_streaming_p50={out['fleet']['streaming_p50_ms']:.1f}ms "
                f"speedup={out['fleet']['speedup_classic_over_streaming']:.0f}x")
        elif name == "early_exit" and out:
            h = out["headline"]
            derived = (
                f"mean_trees={h['mean_trees_evaluated']:.1f}"
                f"/{out['shape']['n_trees']} "
                f"mismatches={h['label_mismatches']}")
        print(f"{name},{dt*1e6:.0f},{derived}")


if __name__ == "__main__":
    main()
