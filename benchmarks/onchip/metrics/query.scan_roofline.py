"""The fused retrieval scan's share of its roofline, in %: the least
time the chip needs for the window's scans (``counting.least_scan_
seconds``: needed rows once, not the slots and passes the kernel
streams) over the summed device time of the fused-scan kernel's
events."""

from vbench import counting

KERNEL = "fused_retrieve_scan_stack"


def read(run):
    dev = run.trace.seconds_matching(KERNEL)
    least = counting.least_scan_seconds(
        run.config, run.peaks, run.records.get("query_ticks", ()))
    if not dev or not least:
        return None
    return 100.0 * least / dev
