#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/onchip/run_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Builds the cell's configuration from ``configs/``, drives its traffic
from ``traffic/`` through its driver in ``drivers/``, measures for
``--seconds``, checks the answers against the plain reference, and
prints one JSON result as the last line of standard output. Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from repro.util import enable_compile_cache
    enable_compile_cache()
    import jax
    from vbench.registry import Cell
    cell = Cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run_cell: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run_cell: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from vbench import harness
    return harness.run(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
