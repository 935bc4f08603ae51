"""One run of one cell, after the entry point has found its chips.

Set-up builds the service, prefills it and lets the cell's driver warm
every shape its window reaches; ``setup_s`` runs from process start to
the window's first second. The window runs on the driver's loop, traced
when asked. Then the peak device memory is read, the driver reads back
what the reference compares, the program's state is freed, and the
references run. The last line of standard output is the result.
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, Optional

from vbench import build as vbuild
from vbench import trace as vtrace
from vbench.registry import CHECKOUT, Cell, benchmark_json
from vbench.registry import cell_metrics, load_module
from vbench.util import CompileClock, Spans, peaks


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Ctx:
    """What a driver sees: the cell, the seed, the built service, the
    spans and a place for its own records."""

    def __init__(self, cell: Cell, seed: int, spans: Spans, base: Path):
        self.cell = cell
        self.seed = seed
        self.spans = spans
        self.base = base
        self.streams = cell.config["streams"]
        self.log = log

    def attach(self, built) -> None:
        self.built = built
        self.mgr, self.svc, self.embedder = built.mgr, built.svc, \
            built.embedder

    def detach(self) -> None:
        self.built = self.mgr = self.svc = self.embedder = None


def verdict(checks: Dict[str, float], limits: Dict[str, float]) -> bool:
    """``correct``: every compared number within its limit, and exactly
    the numbers the cell sets limits for."""
    return set(checks) == set(limits) and \
        all(checks[k] <= limits[k] for k in limits)


class Run:
    """What a per-layer metric's reader gets."""

    def __init__(self, ctx: Ctx, peaks_row: Dict, trace, records: Dict):
        self.ctx = ctx
        self.cell = ctx.cell
        self.config = ctx.cell.config
        self.peaks = peaks_row
        self.trace = trace
        self.records = records


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, checkout: Optional[Path] = None) -> int:
    import jax
    checkout = checkout or CHECKOUT
    dev = jax.devices()
    peaks_row = peaks(dev[0].device_kind, cell.base)
    bench = benchmark_json(checkout)
    wanted = cell_metrics(bench, cell.name, trace)
    clock = CompileClock()
    spans = Spans()
    ctx = Ctx(cell, seed, spans, cell.base)
    driver = cell.driver()
    ctx.attach(vbuild.build(cell.config, seed, spans, cell.traffic))
    with spans.span("setup.prepare"):
        driver.prepare(ctx)
    with spans.span("setup.warmup"):
        driver.warmup(ctx)
        jax.block_until_ready(ctx.mgr.arena.emb)
    setup_s = time.perf_counter() - t_start
    phases = ", ".join(f"{n[6:]} {e - s:.3f} s" for n, s, e in spans.rows
                       if n.startswith("setup."))
    log(f"setup: {setup_s:.3f} s ({phases}); {clock.compiles} compiles "
        f"({clock.seconds:.3f} s), {clock.cache_hits} cache hits")
    c0 = clock.compiles
    tdir = str(checkout / ".bench_trace" / cell.name)
    if trace:
        with vtrace.capture(tdir):
            out = driver.window(ctx, seconds)
    else:
        out = driver.window(ctx, seconds)
    in_window = clock.compiles - c0
    print(f"compiles_in_window={in_window}", flush=True)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in dev[:cell.chips])
    collected = driver.collect(ctx)
    records = {"out": out, "setup_s": setup_s,
               "compiles_in_window": in_window}
    for k in ("query_ticks", "ingest_ticks", "window"):
        if hasattr(ctx, k):
            records[k] = getattr(ctx, k)
    ctx.detach()
    gc.collect()
    t_ref = time.perf_counter()
    checks = driver.check(ctx, collected)
    limits = cell.spec["limits"]
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")

    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    metrics: Dict[str, Dict] = {}
    result = {}
    if trace:
        red = vtrace.reduce(vtrace.xplane_file(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        r = Run(ctx, peaks_row, red, records)
        for m in wanted:
            v = load_module("metrics", m["name"], cell.base).read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        for m in wanted:
            v = setup_s if m["name"] == "setup_s" else out.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = verdict(checks, limits)
    for k in sorted(checks):
        log(f"check {k}: {checks[k]!r} limit {limits.get(k)!r}")
    line = {"correct": bool(correct), "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": device, **result,
            "checks": {k: {"value": checks[k], "limit": limits.get(k)}
                       for k in sorted(checks)}}
    print(json.dumps(line), flush=True)
    return 0
