"""Benchmark harness: one module per paper table/figure + roofline readout.

    PYTHONPATH=src python -m benchmarks.run [--only substring]

Prints ``name,us_per_call,derived`` CSV rows (one per measurement).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


BENCHES = [
    ("table1_overhead", "benchmarks.bench_table1_overhead"),
    ("scaling", "benchmarks.bench_scaling"),
    ("table2_standard", "benchmarks.bench_table2_standard"),
    ("table3_large_batch", "benchmarks.bench_table3_large_batch"),
    ("fig2_similarity", "benchmarks.bench_fig2_similarity"),
    ("fig3_hamming", "benchmarks.bench_fig3_hamming"),
    ("fig6_perfmodel", "benchmarks.bench_fig6_perfmodel"),
    ("rate_sweep", "benchmarks.bench_rate_sweep"),
    ("overlap", "benchmarks.bench_overlap"),
    ("scenarios", "benchmarks.bench_scenarios"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter on bench name")
    args = ap.parse_args()

    import importlib

    print("name,us_per_call,derived")
    failures = []
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(module)
            rows = mod.run()
            for r in rows:
                print(f"{r[0]},{r[1]:.2f},{r[2]}", flush=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
