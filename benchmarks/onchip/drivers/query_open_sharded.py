"""Open-loop fleet queries beside camera ingest, over an arena whose
slots are split across chips.

The loop, the warm-up and the check are ``query_open``'s. Three things
differ where the arena spans several chips:

* Streams are placed on the least-loaded slab as they are created, so a
  stream's arena slot is not its sid: its stored rows are read by its
  own slot (``mgr[s].memory.slot``).
* The streams whose index is compared whole are chosen one per chip, so
  the check reads rows from every slab.
* Each query tick records, next to what ``query_open`` records, the
  seconds its groups spent bringing answers back from the chips
  (``QueryResult.timings["shard_gather"]``, summed over the tick's
  groups) and the epilogue bytes stitched back from the slabs (the
  manager's ``io_stats["shard_gather_bytes"]`` over the tick). A
  program without those readings records nothing there.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, List

import numpy as np

from vbench import build as vbuild
from vbench import mem as vmem
from vbench.registry import load_module

qo = load_module("drivers", "query_open", Path(__file__).resolve().parents[1])

prepare = qo.prepare
warmup = qo.warmup
check = qo.check


def _group_gather_s(plan, res) -> float:
    """Shard-gather seconds of a plan's groups: one result per group
    carries its group's timings."""
    total = 0.0
    for g in plan.groups:
        j = next(iter(g.order.values()))[0]
        total += res[j].timings.get("shard_gather", 0.0)
    return total


def window(ctx, seconds: float) -> Dict:
    mgr = ctx.mgr
    execute = mgr.execute
    ticks: List[dict] = []

    def recorded(plan, **kw):
        b0 = mgr.io_stats.get("shard_gather_bytes")
        res = execute(plan, **kw)
        if b0 is not None:
            ticks.append({"shard_gather_s": _group_gather_s(plan, res),
                          "shard_gather_bytes":
                              mgr.io_stats["shard_gather_bytes"] - b0})
        return res

    mgr.execute = recorded
    try:
        out = qo.window(ctx, seconds)
    finally:
        del mgr.execute
    if ticks:
        assert len(ticks) == len(ctx.query_ticks)
        for rec, tick in zip(ticks, ctx.query_ticks):
            tick.update(rec)
    return out


def whole_streams(mgr, sampled: List[int], n: int) -> List[int]:
    """``n`` streams, one per chip where the arena has that many: on
    each chip the lowest sampled sid it holds, else its lowest sid."""
    a = mgr.arena
    slab = max(1, a.n_sessions // a.n_shards)
    chip = {s: mgr[s].memory.slot // slab for s in sorted(mgr.sessions)}
    out = []
    for k in range(a.n_shards):
        on = [s for s in chip if chip[s] == k]
        pick = [s for s in sampled if s in on] or on
        if pick:
            out.append(min(pick))
    return out[:n]


def collect(ctx) -> Dict:
    """``query_open.collect``'s readings, with the whole indexes read by
    each stream's own slot, one stream per chip."""
    import jax
    mgr = ctx.mgr
    sample = qo._sample(ctx)
    whole = whole_streams(mgr, sorted({q["sid"] for q in sample}),
                          ctx.cell.traffic["check"]["streams_whole"])
    return {
        "sample": sample,
        "embeddings": vmem.query_embeddings(ctx.embedder),
        "windows": vbuild.stream_windows(mgr),
        "partitions": [mgr[s].stats["partitions"]
                       for s in range(ctx.streams)],
        "rows": {s: np.asarray(jax.device_get(
            mgr.arena.emb[mgr[s].memory.slot])) for s in whole},
    }
