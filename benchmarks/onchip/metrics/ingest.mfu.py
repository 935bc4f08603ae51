"""The whole ingest step's share of the chip's peak, in %: MEM image-
tower FLOPs per keyframe times the keyframes the window's ticks
embedded (the program's count, padding excluded), over the window's
seconds times the bf16 peak."""

from vbench import counting


def read(run):
    ticks = run.records.get("ingest_ticks", ())
    lo, hi = run.records.get("window", (None, None))
    if not ticks or lo is None or "embedded" not in ticks[0]:
        return None
    frames = sum(t["embedded"] for t in ticks)
    flops = frames * counting.vision_flops_per_frame(run.config["mem"])
    return 100.0 * flops / ((hi - lo) * run.peaks["bf16_flops"])
