"""Mean per ingest tick of the program's own ``cluster`` stage time
(``SessionManager.ingest_tick``'s return), in ms, over the window."""

STAGE = "cluster"


def read(run):
    ticks = run.records.get("ingest_ticks", ())
    if not ticks or STAGE not in ticks[0]:
        return None
    return 1e3 * sum(t[STAGE] for t in ticks) / len(ticks)
