"""Benchmark harness — one section per survey table/figure.

  1. table2_filters         — Table 2 (filter catalogue: cost + resilience)
  2. attack_defence_matrix  — convergence under attack (the standard figure)
  3. coding                 — §3.3.3 gradient coding / reactive redundancy
  4. p2p_dgd                — §3.3.5 decentralized fault tolerance
  5. roofline               — §Roofline from the dry-run artifacts
  6. async                  — fault-injection simulator / async training
  7. serving                — continuous-batching replicated-decode scheduler
  8. compression            — compressed robust exchange (sign / int8 / fp8)

Prints ``name,us_per_call,derived`` CSV.  --full for the long versions.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_async, bench_coding, bench_compression,
                            bench_convergence, bench_filters, bench_p2p,
                            bench_roofline, bench_serving)
    benches = {
        "table2_filters": bench_filters.run,
        "attack_defence_matrix": bench_convergence.run,
        "coding": bench_coding.run,
        "p2p_dgd": bench_p2p.run,
        "roofline": bench_roofline.run,
        "async": bench_async.run,
        "serving": bench_serving.run,
        "compression": bench_compression.run,
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for bname, fn in benches.items():
        if only and bname not in only:
            continue
        try:
            rows = fn(quick=quick)
        except Exception as e:              # keep the harness running
            print(f"{bname}/HARNESS_ERROR,-1,{repr(e)[:120]}")
            failures += 1
            continue
        for r in rows:
            derived = str(r["derived"]).replace(",", ";")
            print(f"{r['bench']}/{r['name']},{r['us_per_call']},{derived}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
