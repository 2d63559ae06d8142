"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] \
        [--json BENCH_serving.json]

Emits one ``name,us_per_call,derived`` CSV row per benchmark (benchmarks
also print their human-readable tables above the CSV rows).  ``--json PATH``
additionally writes a machine-readable report: per-suite rows + wall time +
artifact-cache hit/miss deltas, and the serving suite's HTTP latency/
throughput metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _cache_counts():
    from repro.core.artifact import default_cache

    cache = default_cache()
    if cache is None:
        return {"hits": 0, "misses": 0}
    return {"hits": cache.hits, "misses": cache.misses}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="validate at the paper's 10^6 points (slower)")
    p.add_argument("--only", default=None,
                   help="comma-separated subset of: accuracy|fig5|dense"
                        "|fractal|attn|msimplex|serving|cluster|routing"
                        "|evaluate|wire|concurrency|loadgen")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write a machine-readable per-suite report "
                        "(e.g. BENCH_serving.json)")
    args = p.parse_args()
    from repro.core.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    only = set(args.only.split(",")) if args.only else None

    n_val = 1_000_000 if args.full else 100_000
    sample = 200 if args.full else 50

    from benchmarks import (  # noqa: PLC0415
        accuracy_tables, attn_kernel, block_dense, block_fractal, common,
        energy_efficiency, msimplex_scaling, serving,
    )

    t0 = time.time()
    print("name,us_per_call,derived")
    failures = []
    suites = {
        "accuracy": lambda: accuracy_tables.run(n_val, sample),
        "fig5": lambda: energy_efficiency.run(min(n_val, 50_000), sample),
        "dense": block_dense.run,
        "fractal": block_fractal.run,
        "attn": attn_kernel.run,
        "msimplex": msimplex_scaling.run,
        "serving": serving.run,
        "cluster": serving.cluster_suite,
        "routing": serving.routing_suite,
        "evaluate": serving.evaluate_suite,
        "wire": serving.wire_suite,
        "concurrency": serving.concurrency_suite,
        "loadgen": serving.loadgen_suite,
    }
    report: dict = {"suites": {}, "args": {"full": args.full}}
    for name, fn in suites.items():
        if only and name not in only:
            continue
        rows_before = len(common.ROWS)
        cache_before = _cache_counts()
        suite_t0 = time.time()
        try:
            fn()
        except Exception as e:  # pragma: no cover
            import traceback

            traceback.print_exc()
            failures.append((name, repr(e)))
        cache_after = _cache_counts()
        report["suites"][name] = {
            "seconds": time.time() - suite_t0,
            "rows": [{"name": row_name, "us_per_call": us, "derived": derived}
                     for row_name, us, derived in common.ROWS[rows_before:]],
            "cache_hits": cache_after["hits"] - cache_before["hits"],
            "cache_misses": cache_after["misses"] - cache_before["misses"],
            "failed": any(f[0] == name for f in failures),
        }
    if serving.LAST_METRICS and ("serving" in report["suites"]
                                 or "cluster" in report["suites"]
                                 or "routing" in report["suites"]
                                 or "evaluate" in report["suites"]
                                 or "wire" in report["suites"]
                                 or "concurrency" in report["suites"]
                                 or "loadgen" in report["suites"]):
        report["serving"] = serving.LAST_METRICS
        # the serving suite runs against its own private store, invisible to
        # default_cache() — take its hit/miss deltas from the server's own
        # counters instead
        if "serving" in report["suites"] and "server" in serving.LAST_METRICS:
            store = serving.LAST_METRICS["server"].get("store", {})
            report["suites"]["serving"]["cache_hits"] = store.get("hits", 0)
            report["suites"]["serving"]["cache_misses"] = store.get(
                "misses", 0)
    report["wall_seconds"] = time.time() - t0
    report["failures"] = failures

    print(f"\n[benchmarks] done in {time.time() - t0:.1f}s")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=str)
        print(f"[benchmarks] wrote {args.json}")
    if failures:
        print(f"[benchmarks] FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
