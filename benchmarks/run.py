"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  See benchmarks/common.py for
the CPU-scale note; roofline/architecture numbers live in EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = [
    "bench_memops",             # Fig. 7  (fast, analytic)
    "bench_engine",             # engine vs host-loop wall time
    "bench_k_sweep",            # Fig. 6
    "bench_eps_sweep",          # Figs. 5/8/9
    "bench_overhead",           # Table 2
    "bench_partition_balance",  # Fig. 10
    "bench_scaling",            # Fig. 11
    "bench_comm",               # Fig. 12
    "bench_dense",              # hybrid tiers: dense-vs-indexed crossover
    "bench_service",            # serving tier: warm QPS vs batch size
    "bench_speedup_summary",    # Table 3
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if only and mod_name not in only:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            mod.run()
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
