"""The fused retrieval scan's share of its roofline over a sharded
arena, in %: the least chip time the window's scans need
(``counting.least_scan_seconds``: each targeted stream's rows once, at
one chip's peaks) over the device time of the fused-scan kernel's
events summed over every chip's plane. Each chip scans its own slab, so
this is chip-seconds needed over chip-seconds spent, at most 100."""

from vbench import counting

KERNEL = "fused_retrieve_scan_stack"


def read(run):
    dev = run.trace.seconds_matching(KERNEL)
    least = counting.least_scan_seconds(
        run.config, run.peaks, run.records.get("query_ticks", ()))
    if not dev or not least:
        return None
    return 100.0 * least / dev
