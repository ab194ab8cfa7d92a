"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  * Table I analog  — tightness per bound per window
  * Table II analog — sequential pruning power (paper semantics)
  * Table III analog— NN-DTW classification time with the engine
  * Fig. 1 analog   — tightness vs per-pair time, L=256, W=0.3L
  * kernel micro-benchmarks (pure-jnp refs; interpret kernels are
    semantics-only on CPU)
  * the roofline table from the dry-run artifacts (if present)

Run: ``PYTHONPATH=src python -m benchmarks.run [--fast]``
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the slower table benchmarks")
    ap.add_argument("--skip", default="", help="comma-list of sections")
    args = ap.parse_args()
    skip = set(filter(None, args.skip.split(",")))

    from benchmarks import kernel_bench, paper_tables, roofline_table
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    sections = [
        ("fig1", paper_tables.fig1_tightness_vs_time),
        ("kernels", kernel_bench.kernel_rows),
        ("table1", paper_tables.table1_tightness),
        ("table2", paper_tables.table2_pruning_power),
        ("table3", paper_tables.table3_nn_time),
        ("roofline", roofline_table.roofline_rows),
    ]
    if args.fast:
        sections = [s for s in sections if s[0] in ("fig1", "kernels", "roofline")]

    print("name,us_per_call,derived")
    for name, fn in sections:
        if name in skip:
            continue
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name}_ERROR,0.0,{type(e).__name__}:{e}", file=sys.stderr)
            raise


if __name__ == "__main__":
    main()
