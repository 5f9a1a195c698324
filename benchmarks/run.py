"""Benchmark harness entry point — one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only tableN]
Output: CSV rows ``name,us_per_call,derived`` (+ `#` table headers).
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller datasets (CI-sized)")
    ap.add_argument("--only", default="",
                    help="substring filter: table1|table2|fig8|fig9|table3")
    args = ap.parse_args()
    nbytes = 1 << 19 if args.quick else 1 << 21

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (fig8_ratio, fig9_throughput, table1_ratio,
                            table2_throughput, table3_usecase)

    suites = {
        "table1": lambda: table1_ratio.run(nbytes=nbytes),
        "table2": lambda: table2_throughput.run(nbytes=nbytes),
        "fig8": lambda: fig8_ratio.run_paper_table(nbytes=nbytes),
        "fig9": lambda: fig9_throughput.run(nbytes=min(nbytes, 1 << 20)),
        "table3": lambda: table3_usecase.run(nbytes=nbytes),
    }
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        print(f"## {name}", file=sys.stderr)
        fn()


if __name__ == "__main__":
    main()
