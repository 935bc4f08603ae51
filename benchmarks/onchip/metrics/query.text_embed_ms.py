"""Device time of MEM's text-tower program per query tick, in ms: the
summed durations of the ``encode_text`` program's events in the traced
window over the query ticks run in it."""

NEEDLE = "encode_text"


def read(run):
    ticks = len(run.records.get("query_ticks", ()))
    sec = run.trace.seconds_matching(NEEDLE, modules=True)
    if not ticks or not sec:
        return None
    return sec / ticks * 1e3
