"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  bench_filter_micro      paper Fig. 5–7  (filter queries, CSV+Parquet)
                          + fused-vs-eager pipeline comparison (PR 1)
  bench_projection_micro  paper Fig. 8–9  (projection queries)
                          + fused-vs-eager pipeline comparison (PR 1)
  bench_macro_tpcds       paper Fig. 3    (50-query TPC-DS CDF)
  bench_window            paper Fig. 4    (batching-window sweep)
  bench_mckp              paper §6.2      (optimizer overhead < 2 s)
  bench_batch_reuse       beyond-paper    (cold vs warm repeat batch,
                          cross-batch CE retention per policy — PR 2)
  bench_service           beyond-paper    (online QueryService windows:
                          interleaved arrivals + warm residents vs the
                          cold one-shot batch — PR 3)
  bench_canonical         beyond-paper    (mixed-syntax recurring
                          stream: the canonical plan IR folds every
                          author spelling onto one fingerprint, so
                          warm windows keep hitting resident CEs —
                          PR 5)
  bench_partition         beyond-paper    (partition-grained MCKP on
                          the selective dashboard: partial admission
                          under a sub-CE budget, warm partial
                          residency vs cold — PR 4)
  bench_resilience        beyond-paper    (warm-stream throughput at a
                          5% injected transient-fault rate vs the
                          fault-free warm stream: isolation + retry
                          overhead bounded — PR 6)
  bench_window_batch      beyond-paper    (window-batched kernel
                          execution + plan-shape compile cache: warm
                          recurring-template windows vs per-query
                          literal-keyed dispatch — PR 7)
  bench_subsumption       beyond-paper    (semantic subsumption + pid
                          pool: fresh-literal drill-down stream served
                          from a WEAKER resident CE with zero
                          exact-fingerprint hits — PR 8)
  bench_async             beyond-paper    (asyncio serving front:
                          Poisson clients, adaptive vs fixed windows,
                          per-tenant admission — PR 10)
  bench_serving_prefix    beyond-paper    (LLM prefix-cache MQO)
  roofline_report         assignment      (dry-run roofline terms)

Usage:
  python benchmarks/run.py                       # everything
  python benchmarks/run.py bench_filter_micro bench_projection_micro \
      --out BENCH_pr1.json                       # subset, merged JSON
"""
import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODULES = [
    "bench_mckp",
    "bench_filter_micro",
    "bench_projection_micro",
    "bench_window",
    "bench_macro_tpcds",
    "bench_batch_reuse",
    "bench_service",
    "bench_canonical",
    "bench_partition",
    "bench_resilience",
    "bench_window_batch",
    "bench_subsumption",
    "bench_telemetry",
    "bench_async",
    "bench_serving_prefix",
    "roofline_report",
]

# modules that legitimately emit no reports/bench/*.json artifact (the
# roofline report is a stdout-only dry-run summary); every other bench
# MUST save_result or the run fails loudly (PR 9 satellite — a silently
# missing BENCH artifact is how BENCH_pr7.json went uncommitted)
NO_ARTIFACT = frozenset({"roofline_report"})


def _artifacts_written_since(t0: float) -> int:
    """JSON result files common.save_result produced after ``t0``."""
    from common import RESULTS_DIR

    if not os.path.isdir(RESULTS_DIR):
        return 0
    return sum(
        1 for fn in os.listdir(RESULTS_DIR)
        if fn.endswith(".json")
        and os.path.getmtime(os.path.join(RESULTS_DIR, fn)) >= t0)


def _merge_results(out_path: str, since: float) -> None:
    """Collect the per-module JSONs written by common.save_result
    DURING THIS RUN into a single file (the PR-over-PR perf trajectory
    artifact); stale results from earlier runs are left out."""
    from common import RESULTS_DIR

    merged = {}
    if os.path.isdir(RESULTS_DIR):
        for fn in sorted(os.listdir(RESULTS_DIR)):
            path = os.path.join(RESULTS_DIR, fn)
            if fn.endswith(".json") and os.path.getmtime(path) >= since:
                with open(path) as f:
                    merged[fn[:-5]] = json.load(f)
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=1)
    print(f"# merged {len(merged)} result sets -> {out_path}", flush=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("modules", nargs="*",
                        help=f"subset of {MODULES} (default: all)")
    parser.add_argument("--out", default=None,
                        help="merge reports/bench/*.json into this file")
    args = parser.parse_args()
    modules = args.modules or MODULES
    unknown = [m for m in modules if m not in MODULES]
    if unknown:
        parser.error(f"unknown modules: {unknown}")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    t_start = time.time()
    failures = 0
    for mod_name in modules:
        t0 = time.time()
        try:
            mod = __import__(mod_name)
            for line in mod.main():
                print(line, flush=True)
            if (mod_name not in NO_ARTIFACT
                    and _artifacts_written_since(t0) == 0):
                failures += 1
                print(f"# {mod_name} FAILED: completed without writing "
                      f"any reports/bench/*.json artifact — its results "
                      f"would be missing from the --out merge",
                      flush=True)
                continue
            print(f"# {mod_name} done in {time.time() - t0:.1f}s",
                  flush=True)
        except Exception:
            failures += 1
            print(f"# {mod_name} FAILED:", flush=True)
            traceback.print_exc()
    if args.out:
        _merge_results(args.out, since=t_start)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
