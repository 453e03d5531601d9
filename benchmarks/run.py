"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``python -m benchmarks.run``
runs everything; ``--only table2`` filters.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

TABLES = [
    "table1_divergence",
    "table2_ssbfs",
    "table4_ablation",
    "table5_random_order",
    "table6_msbfs",
    "table7_preproc",
    "table8_memory",
    "fig4_window",
    "fig5_switching",
    "fig5_eta_sweep",
    "triangles_bench",
    "closeness_bench",
    "serve_throughput",
    "serve_switching",
    "serve_fused",
    "serve_fairness",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on table module names")
    args = ap.parse_args()
    from repro.launch import compile_cache

    compile_cache.enable()
    print("name,us_per_call,derived")
    failures = []
    for mod_name in TABLES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures.append(mod_name)
            print(f"# {mod_name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
