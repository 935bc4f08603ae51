"""Host time per query tick spent bringing a sharded arena's answers
back from the chips, in ms: the program's ``execute.shard_gather`` span
(``QueryResult.timings["shard_gather"]``, summed over a tick's groups
by ``drivers/query_open_sharded.py``), averaged over the ticks from the
window's start. Reads nothing from a program without that span."""


def read(run):
    lo, _ = run.records.get("window", (None, None))
    ticks = [t for t in run.records.get("query_ticks", ())
             if "shard_gather_s" in t]
    if lo is None or not ticks:
        return None
    sel = [t["shard_gather_s"] for t in ticks if t["t0"] >= lo]
    return sum(sel) / len(sel) * 1e3 if sel else None
