"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale 0.5] [--only table3]

Writes JSON per table under results/ and prints CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.common import enable_compile_cache

from benchmarks import (
    cluster2_ablation,
    delta_init,
    kernel_bench,
    table1_graphs,
    table2_stop_variant,
    table3_vs_sssp,
    table4_sigma,
)

TABLES = {
    "table1": lambda scale: table1_graphs.run(scale),
    "table2": lambda scale: table2_stop_variant.run(scale),
    "table3": lambda scale: table3_vs_sssp.run(scale),
    "table4": lambda scale: table4_sigma.run(scale),
    "delta_init": lambda scale: delta_init.run(),
    "kernels": lambda scale: kernel_bench.run(),
    "cluster2": lambda scale: cluster2_ablation.run(),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()

    failures = []
    for name, fn in TABLES.items():
        if args.only and args.only not in name:
            continue
        print(f"### {name} " + "#" * 50, flush=True)
        t0 = time.perf_counter()
        try:
            fn(args.scale)
            print(f"### {name} done in {time.perf_counter() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print("BENCH FAILURES:", failures)
        return 1
    print("all benchmarks complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
