"""The whole query step's share of the chip's peak, in %: the least time
the chip needs for the window's text-tower forwards (real tokens only)
and fused scans (needed rows only), over the summed wall time of the
query ticks."""

import numpy as np

from vbench import counting, ref_mem


def read(run):
    ticks = run.records.get("query_ticks", ())
    if not ticks:
        return None
    mem = run.config["mem"]
    tokens = {}
    flops = 0.0
    for q in run.ctx.queries:
        if q["text"] not in tokens:
            tokens[q["text"]] = int(np.count_nonzero(ref_mem.tokenize(
                q["text"], mem["text"]["vocab_size"], mem["text_max_len"])))
        flops += counting.text_flops(mem, tokens[q["text"]])
    least = flops / run.peaks["bf16_flops"] + counting.least_scan_seconds(
        run.config, run.peaks, ticks)
    wall = sum(t["t1"] - t["t0"] for t in ticks)
    return 100.0 * least / wall if wall else None
