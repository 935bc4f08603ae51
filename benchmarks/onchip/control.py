#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 benchmarks/onchip/control.py --workload <cell> \\
        --seeds 11,12,13 --seconds 8

For each seed, in one process: set the cell up, run a short window at
the cell's own load, read back what the reference compares, free the
program's state, and compute every compared number twice — for the
program's answers, and for the control: the reference put in the
program's place one precision step below the configuration (bfloat16
scores, softmax and CDF; int4 rows for an int8 index; fp8 matmul
operands in MEM). Each side's numbers go through the harness's own
verdict against the cell's limits. One JSON line per seed and side,
with its ``correct``; the last line gives, per number, the largest
program reading and the smallest control reading, and per side whether
every seed came out correct. The benchmark's own runs never run this.
Exits non-zero, printing nothing, without a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seeds, seconds: float, emit=print):
    """Program and control readings per seed; returns the summary."""
    from vbench import build as vbuild
    from vbench.harness import Ctx, verdict
    from vbench.util import Spans
    driver = cell.driver()
    limits = cell.spec["limits"]
    prog, ctrl = {}, {}
    correct = {"program": [], "control": []}
    for seed in seeds:
        ctx = Ctx(cell, seed, Spans(), cell.base)
        ctx.attach(vbuild.build(cell.config, seed, ctx.spans,
                                cell.traffic))
        driver.prepare(ctx)
        driver.warmup(ctx)
        driver.window(ctx, seconds)
        col = driver.collect(ctx)
        ctx.detach()
        gc.collect()
        for side, lowp, acc in (("program", False, prog),
                                ("control", True, ctrl)):
            nums = driver.check(ctx, col, lowp=lowp)
            ok = verdict(nums, limits)
            correct[side].append(ok)
            emit(json.dumps({"seed": seed, "side": side, "correct": ok,
                             **nums}))
            for k, v in nums.items():
                acc.setdefault(k, []).append(v)
        del ctx, col
        gc.collect()
    summary = {k: {"program_max": max(prog[k]), "control_min": min(ctrl[k]),
                   "limit": limits.get(k)} for k in prog}
    emit(json.dumps({"summary": summary, "seeds": list(seeds),
                     "program_correct": correct["program"],
                     "control_correct": correct["control"]}))
    return summary, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    from repro.util import enable_compile_cache
    enable_compile_cache()
    import jax
    from vbench.registry import Cell
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    readings(Cell(args.workload), [int(s) for s in args.seeds.split(",")],
             args.seconds, emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
