"""Median latency of the window's queries, in ms, from scheduled arrival
to frame ids on the host: the same sample as ``query_p95_ms``. The
median falls between queries served at once and queries that waited
behind an ingest tick, so it swings from run to run with the host's
timing; it stands here, beside the tail."""


def read(run):
    return run.records.get("out", {}).get("query_p50_ms")
