"""Host time of the archive trim per ingest tick, in ms: the program
span ``venus.ingest.trim`` (``SessionManager._trim_archives``: each
stream's live-frame horizon and ``FrameStore.trim``), which
``ingest_tick`` returns as ``trim``, averaged over the ticks that start
in the window. None where the program returns no ``trim``."""


def read(run):
    lo, _ = run.records.get("window", (None, None))
    ticks = [t for t in run.records.get("ingest_ticks", ())
             if lo is not None and t["t0"] >= lo]
    if not ticks or "trim" not in ticks[0]:
        return None
    return 1e3 * sum(t["trim"] for t in ticks) / len(ticks)
