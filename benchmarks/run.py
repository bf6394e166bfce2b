"""Benchmark harness — one module per survey table/figure.

Prints ``name,us_per_call,derived`` CSV.  Module map:
  bench_partitioning  — Tables 1 & 3 (§2.2.2 / §3.2.1)
  bench_sampling      — Table 4  (§3.2.2)
  bench_abstraction   — Table 5  (§3.2.3)
  bench_caching       — Table 6  (§3.2.4)
  bench_distributed   — Tables 2 & 7 (§3.2.5–§3.2.9: parallelism,
                        propagation, sync, coordination; 8-device payload)
  bench_scheduling    — Table 8  (§3.2.8)
  bench_datasets      — Table 9  (§3.2.10)
  bench_performance   — §3.2.12 system-lineage comparison
  bench_roofline      — deliverable (g): roofline terms from the dry-run
  bench_serving       — online inference: cache hierarchy vs no-cache
  bench_async         — §3.2.7 staleness-bounded async full-graph training
                        (writes BENCH_async.json)
  bench_dynamic       — dynamic graphs: incremental delta invalidation vs
                        full-flush rebuild (writes BENCH_dynamic.json)
"""
import sys
import traceback

from benchmarks import (bench_abstraction, bench_async, bench_caching,
                        bench_datasets, bench_distributed, bench_dynamic,
                        bench_partitioning, bench_performance,
                        bench_roofline, bench_sampling, bench_scheduling,
                        bench_serving)

MODULES = [
    ("partitioning", bench_partitioning),
    ("sampling", bench_sampling),
    ("abstraction", bench_abstraction),
    ("caching", bench_caching),
    ("scheduling", bench_scheduling),
    ("datasets", bench_datasets),
    ("performance", bench_performance),
    ("distributed", bench_distributed),
    ("roofline", bench_roofline),
    ("serving", bench_serving),
    ("async", bench_async),
    ("dynamic", bench_dynamic),
]


def main() -> None:
    print("name,us_per_call,derived")
    failed = 0
    only = set(sys.argv[1:])
    for name, mod in MODULES:
        if only and name not in only:
            continue
        try:
            mod.main()
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{name}/BENCH_FAILED,0.0,", flush=True)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
