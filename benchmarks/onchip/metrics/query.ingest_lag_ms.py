"""How far the window's ingest ticks started behind their schedule, in
ms, averaged: the service has no scheduler, so ingest waits behind the
query ticks in the loop, as queries wait behind ingest."""


def read(run):
    lo, hi = run.records.get("window", (None, None))
    ticks = [t for t in run.records.get("ingest_ticks", ())
             if "due" in t and lo is not None and lo <= t["due"] < hi]
    if not ticks:
        return None
    return sum(t["t0"] - t["due"] for t in ticks) / len(ticks) * 1e3
