#!/usr/bin/env python3
"""Knee sweep of an open-loop query cell: the highest offered rate the
system sustains with neither a growing query backlog nor a growing
ingest lag.

    python3 benchmarks/onchip/sweep.py --workload <cell> --seed <n> \\
        --rates 10,20,40,80 --seconds 20

Builds and warms the cell once, then runs one window per rate on the
cell's own driver (the mix's ``rate_qps`` replaced) and prints one JSON
line per rate: queries due and answered, the latency quartiles, and the
latency of the first and last thirds of the arrivals, and the ingest
ticks run against those due with their lag behind schedule. A rate
whose last third waits much longer than its first has a growing query
backlog; one whose ingest lag climbs through the window, or that runs
fewer ingest ticks than are due, has a growing ingest backlog. Run once
when a cell is defined; the cell then offers a fixed rate. Exits
non-zero, printing nothing, without a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _merge(base: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge(base[k], v)
        else:
            base[k] = v


def sweep(cell, seed: int, rates, seconds: float, emit=print) -> None:
    import numpy as np
    from vbench import build as vbuild
    from vbench.registry import load_module
    from vbench.harness import Ctx
    from vbench.util import Spans
    driver = cell.driver()
    ctx = Ctx(cell, seed, Spans(), cell.base)
    ctx.attach(vbuild.build(cell.config, seed, ctx.spans, cell.traffic))
    driver.prepare(ctx)
    driver.warmup(ctx)
    for rate in rates:
        cell.traffic["rate_qps"] = rate
        ctx.query_ticks = []
        n_ingest = len(ctx.ingest_ticks)
        out = driver.window(ctx, seconds)
        ing = ctx.ingest_ticks[n_ingest:]
        lag = load_module("drivers", cell.traffic["driver"],
                          cell.base).ingest_lags(ctx)
        lat = np.asarray([(q["done"] - q["due"]) * 1e3
                          for q in ctx.queries])
        third = max(1, len(lat) // 3)
        emit(json.dumps({
            "rate_qps": rate, "due": out["attempted"],
            "answered_in_window": int(sum(q["done"] <= ctx.window[1]
                                          for q in ctx.queries)),
            "p50_ms": float(np.median(lat)),
            "p95_ms": float(np.quantile(lat, 0.95)),
            "first_third_p50_ms": float(np.median(lat[:third])),
            "last_third_p50_ms": float(np.median(lat[-third:])),
            "query_ticks": len(ctx.query_ticks),
            "mean_batch": float(np.mean([t["n"] for t in ctx.query_ticks])),
            "ingest_ticks": len(ing),
            "ingest_due": int(np.ceil(seconds * cell.traffic["ingest_hz"])),
            "ingest_lag_first_s": float(lag[0]) if len(lag) else None,
            "ingest_lag_last_s": float(lag[-1]) if len(lag) else None,
            "ingest_lag_max_s": float(lag.max()) if len(lag) else None,
            "ingest_tick_mean_s": float(np.mean([t["t1"] - t["t0"]
                                                 for t in ing])),
        }))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--traffic", default="{}",
                    help="JSON merged into the cell's mix, to explore")
    args = ap.parse_args(argv)
    from repro.util import enable_compile_cache
    enable_compile_cache()
    import jax
    from vbench.registry import Cell
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    cell = Cell(args.workload)
    _merge(cell.traffic, json.loads(args.traffic))
    sweep(cell, args.seed,
          [float(r) for r in args.rates.split(",")], args.seconds,
          emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
