"""Benchmark entrypoint: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--large]

Prints ``name,us_per_call,derived`` CSV lines (plus per-table detail rows
prefixed with the table id). --large adds the 1M-record scaling point.
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small datasets only (CI)")
    ap.add_argument("--large", action="store_true",
                    help="add the 1M-record scaling point")
    args = ap.parse_args()

    from . import (bench_kernels, bench_lsh_curve, bench_lsh_sweep,
                   bench_pairs, bench_scaling, bench_table2, bench_table3)

    t0 = time.time()
    print("name,us_per_call,derived")
    bench_kernels.run()
    bench_lsh_curve.run()
    if args.fast:
        bench_pairs.run(distributions=("small",), target_slots=100_000)
        bench_table2.run(datasets=("SYN10K",))
        bench_table3.run(datasets=("SYN10K",))
        bench_lsh_sweep.run(settings=((6, 4), (1, 1)))
        bench_scaling.run(datasets=("SYN10K", "SYN30K"))
    else:
        bench_pairs.run()
        bench_table2.run()
        bench_table3.run()
        bench_lsh_sweep.run()
        bench_scaling.run(include_1m=args.large)
    print(f"# total benchmark time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.runtime import enable_compilation_cache
    enable_compilation_cache()
    main()
