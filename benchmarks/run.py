"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows for the kernel micro-bench
plus per-table summaries, and writes JSON artifacts under
``artifacts/benchmarks/``.

  PYTHONPATH=src python -m benchmarks.run                 # standard profile
  PYTHONPATH=src python -m benchmarks.run --profile quick
  PYTHONPATH=src python -m benchmarks.run --only fig1_time,table6_missed
"""

from __future__ import annotations

import argparse
import sys
import time

from . import (
    fig1_time,
    fig23_tradeoff,
    index_bench,
    kernel_bench,
    table2_noise,
    table3_quality,
    table4_rho,
    table5_scalability,
    table6_missed,
)

TABLES = {
    "kernel_bench": kernel_bench,
    "index_bench": index_bench,
    "table2_noise": table2_noise,
    "table3_quality": table3_quality,
    "fig1_time": fig1_time,
    "table4_rho": table4_rho,
    "table5_scalability": table5_scalability,
    "fig23_tradeoff": fig23_tradeoff,
    "table6_missed": table6_missed,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="standard", choices=["quick", "standard", "large"])
    ap.add_argument("--only", default=None, help="comma-separated table names")
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else list(TABLES)
    for name in names:
        mod = TABLES[name]
        t0 = time.time()
        print(f"\n=== {name} (profile={args.profile}) ===", flush=True)
        if name == "kernel_bench":
            rows = mod.run()
        else:
            rows = mod.run(profile=args.profile)
        print(mod.summarize(rows), flush=True)
        print(f"[{name} done in {time.time() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
