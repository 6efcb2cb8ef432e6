"""Benchmark harness entry point — one section per paper table/claim.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract, plus
section headers.  Scales are CPU-budget-reduced (factors printed inline).

  table1   — HNSW width × ef sweep on Fashion-MNIST-like / SIFT-like
             (paper Table I + wide-beam traversal counters); `--out`
             persists the sweep as JSON (``make bench`` writes
             ``BENCH_hnsw.json`` at the repo root), `--min-recall` turns
             the run into a CI gate
  quant    — PQ/BQ compression vs recall vs scan cost (paper §II-B-2)
  kernels  — distance-kernel microbench + TPU roofline (paper §II-B-3)

The report timestamp is *passed in* (``--timestamp``, or computed once here
at the CLI boundary) — the writer itself never samples ambient time, so
re-runs over the same inputs are reproducible.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    choices=["all", "table1", "quant", "kernels"])
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora (CI budget)")
    ap.add_argument("--builder", default=None,
                    choices=["incremental", "bulk", "bulk_ref", "both"],
                    help="HNSW builder for table1; 'both' sweeps "
                         "incremental and bulk side by side (default: "
                         "incremental, bulk under --fast)")
    ap.add_argument("--out", default=None,
                    help="write the table1 sweep as JSON to this path "
                         "(e.g. BENCH_hnsw.json at the repo root)")
    ap.add_argument("--timestamp", type=float, default=None,
                    help="report timestamp (unix seconds); defaults to one "
                         "sample taken here at the CLI boundary")
    ap.add_argument("--min-recall", type=float, default=None,
                    help="fail (exit 1) if any widest-beam table1 row "
                         "falls below this recall@10 floor")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="with --builder both: fail (exit 1) unless the "
                         "bulk build is at least this many times faster "
                         "than incremental at recall within 0.02")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    timestamp = args.timestamp if args.timestamp is not None else time.time()
    failures = []
    t0 = time.perf_counter()
    if args.only in ("all", "table1"):
        from . import bench_hnsw
        if args.fast:
            scale = dict(n_fmnist=1500, n_sift=2000, n_queries=100,
                         builder=args.builder or "bulk")
        else:
            scale = dict(builder=args.builder or "incremental")
        rows = bench_hnsw.main(**scale)
        if args.out:
            bench_hnsw.write_report(rows, args.out, timestamp,
                                    meta={"fast": args.fast, **scale})
            print(f"# wrote {args.out}")
        if args.min_recall is not None:
            failures += bench_hnsw.check_recall_floor(rows, args.min_recall)
        if args.min_speedup is not None:
            failures += bench_hnsw.check_builder_floor(rows, args.min_speedup)
    if args.only in ("all", "quant"):
        from . import bench_quant
        bench_quant.main(n=8_000 if args.fast else 20_000)
    if args.only in ("all", "kernels"):
        from . import bench_kernels
        bench_kernels.main()
    print(f"# benchmarks done in {time.perf_counter() - t0:.1f}s")
    for f in failures:
        print(f"# FAIL: {f}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
