"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:
  PYTHONPATH=src python -m benchmarks.run [--only fig4,table1,...]
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.util import enable_compile_cache

from benchmarks import (bench_akr_scaling, bench_fig10, bench_fig11,
                        bench_fig12, bench_ingestion, bench_kernels,
                        bench_multistream, bench_table1, bench_table2,
                        roofline)

SUITES = {
    "fig4": bench_ingestion.run,       # embedding latency vs FPS
    "table1": bench_table1.run,        # query-irrelevant baselines
    "table2": bench_table2.run,        # query-relevant baselines + latency
    "fig10": bench_fig10.run,          # top-k vs sampling diversity
    "fig11": bench_fig11.run,          # AKR ablation
    "fig12": bench_fig12.run,          # latency breakdown
    "akr_scaling": bench_akr_scaling.run,  # beyond-paper: tau/theta sweep
    "kernels": bench_kernels.run,      # kernel microbench
    "roofline": roofline.run,          # dry-run roofline terms
    "multistream": bench_multistream.run,  # sessions×queries throughput
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    names = [n for n in args.only.split(",") if n] or list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for n in names:
        try:
            SUITES[n]()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed.append(n)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
