"""Host time of ``SessionManager.execute`` per query tick, in ms: the
benchmark's span around the call (text embedding, plan groups' fused
scans, expansion, frame ids to the host), averaged over the window."""


def read(run):
    lo, _ = run.records.get("window", (None, None))
    if lo is None:
        return None
    sel = [e - s for n, s, e in run.ctx.spans.rows
           if n == "execute" and s >= lo]
    return sum(sel) / len(sel) * 1e3 if sel else None
