"""Open-loop fleet queries beside camera ingest, one thread in lockstep.

Users' questions arrive on the mix's schedule whether or not earlier
ones are answered. The service has no scheduler, so the loop is the
client: at each turn it takes up to ``max_batch`` due queries as one
service tick — ``VenusService.plan`` then ``SessionManager.execute``,
whose results hold the frame ids on the host — and then, if one is due,
an ingest tick that hands every camera its next ``chunk_frames``
frames, due every 1/``ingest_hz`` seconds. Queries that arrive during
an ingest tick wait behind it. Each query is timed from its scheduled
arrival to the end of the tick that answered it. An ingest tick that
starts late runs as soon as the loop comes round; its lag behind its
schedule is recorded, so a load under which ingest falls further and
further behind shows as growing lag. When the window closes, ingest
stops and the queries already due are answered in further ticks, so
every query due in the window is in the tail.

The cameras are static scenes, each a still frame (the mix's
``noise_bank`` 1): no scene cut and no forced partition falls inside a
run, so segmentation runs every tick and nothing new is embedded; the
arena holds exactly the history prefill throughout, which
the reference rebuilds from the seed.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from vbench import build as vbuild
from vbench import mem as vmem
from vbench import ref_mem, ref_scan
from vbench.registry import load_module
from vbench.util import rng, sub_seed

STOCHASTIC = ("akr", "sampling")


def prepare(ctx) -> None:
    tr = ctx.cell.traffic
    ctx.video = load_module("traffic", tr["video"]["generator"], ctx.base)
    ctx.pools = ctx.video.make_pools(tr["video"], ctx.streams, ctx.seed)
    ctx.chain = np.zeros(ctx.streams, np.int64)
    ctx.frame_ticks = 0
    ctx.query_ticks: List[dict] = []
    ctx.ingest_ticks: List[dict] = []
    ctx.queries: List[dict] = []


def _budget(ctx, kind: Dict) -> int:
    b = kind.get("budget")
    return int(b if b is not None else ctx.cell.config["venus"]["n_max"])


def _assign_chain(ctx, qs: List[dict]) -> None:
    """Which subkey of its session's chain each stochastic query takes:
    groups run in order of first appearance, a session's queries within
    a group in arrival order."""
    order: Dict[tuple, List[dict]] = {}
    for q in qs:
        order.setdefault((q["strategy"], q["budget"]), []).append(q)
    for group in order.values():
        for q in group:
            if q["strategy"] in STOCHASTIC:
                ctx.chain[q["sid"]] += 1
                q["chain"] = int(ctx.chain[q["sid"]])


def _query_tick(ctx, qs: List[dict]) -> None:
    from repro.serving.venus_service import StreamQuery
    _assign_chain(ctx, qs)
    sq = [StreamQuery(rid=q["rid"], sid=q["sid"], text=q["text"],
                      prompt_tokens=np.zeros((0,), np.int32),
                      strategy=q["strategy"], budget=q["budget"])
          for q in qs]
    t0 = time.perf_counter()
    with ctx.spans.span("plan"):
        plan = ctx.svc.plan(sq)
    with ctx.spans.span("execute"):
        res = ctx.mgr.execute(plan)
    t1 = time.perf_counter()
    texts, embs = ctx.embedder.queries[-1]
    assert texts == [q["text"] for q in qs]
    groups = [(g.key.strategy, g.key.budget,
               [(sid, len(ix)) for sid, ix in g.order.items()])
              for g in plan.groups]
    ctx.query_ticks.append({"t0": t0, "t1": t1, "n": len(qs),
                            "groups": groups})
    for q, r, e in zip(qs, res, np.asarray(embs)):
        q.update(done=t1, draws=np.asarray(r.draws), n_drawn=r.n_drawn,
                 frame_ids=np.asarray(r.frame_ids), emb=e)


def _ingest_tick(ctx, due: float = None) -> None:
    n = ctx.cell.traffic["video"]["chunk_frames"]
    chunks = {s: ctx.video.chunk(ctx.pools[s], ctx.frame_ticks, n)
              for s in range(ctx.streams)}
    t0 = time.perf_counter()
    with ctx.spans.span("ingest_tick"):
        out = ctx.svc.ingest_tick(chunks)
    ctx.ingest_ticks.append({"t0": t0, "t1": time.perf_counter(),
                             "due": t0 if due is None else due, **out})
    ctx.frame_ticks += 1


def ingest_lags(ctx) -> np.ndarray:
    """Seconds each ingest tick of the window started behind schedule."""
    lo, hi = ctx.window
    return np.asarray([t["t0"] - t["due"] for t in ctx.ingest_ticks
                       if lo <= t["due"] < hi])


def _make(ctx, rid: int, sid: int, kind: Dict, text: str) -> dict:
    return {"rid": rid, "sid": int(sid), "strategy": kind["strategy"],
            "budget": _budget(ctx, kind), "text": text, "chain": 0}


def warmup(ctx) -> None:
    """Every shape the window reaches: the ingest tick (the first one,
    with no previous frame, and a later one), the text tower at each
    batch size up to max_batch, and each strategy's scan and post-
    processing at each per-stream query count up to max_batch — one
    tick per count holding a group of every strategy, with embeddings
    given so that only the window's text batches are compiled."""
    from repro.serving.venus_service import StreamQuery
    tr = ctx.cell.traffic
    texts = tr["texts"]
    for _ in range(2):
        _ingest_tick(ctx)
    for q in range(1, tr["max_batch"] + 1):
        embs = ctx.embedder.embed_queries(
            [texts[j % len(texts)] for j in range(q)])
        qs = [dict(_make(ctx, -1, 0, kind, texts[j % len(texts)]),
                   emb=embs[j]) for kind in tr["mix"] for j in range(q)]
        _assign_chain(ctx, qs)
        ctx.mgr.execute(ctx.svc.plan([
            StreamQuery(rid=-1, sid=0, text=q["text"], query_emb=q["emb"],
                        prompt_tokens=np.zeros((0,), np.int32),
                        strategy=q["strategy"], budget=q["budget"])
            for q in qs]))


def window(ctx, seconds: float) -> Dict:
    tr = ctx.cell.traffic
    gen = load_module("traffic", tr["generator"], ctx.base)
    sch = gen.schedule(tr, ctx.streams, ctx.seed, seconds)
    qs = [dict(_make(ctx, i, sch["sid"][i], tr["mix"][sch["kind"][i]],
                     tr["texts"][sch["text"][i]]), due=float(sch["t"][i]))
          for i in range(len(sch["t"]))]
    period = 1.0 / tr["ingest_hz"]
    batch = tr["max_batch"]
    t0 = time.perf_counter()
    for q in qs:
        q["due"] += t0
    end = t0 + seconds
    next_ingest, i = t0, 0
    with ctx.spans.span("window"):
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            ran = False
            j = i
            while j < len(qs) and j - i < batch and qs[j]["due"] <= now:
                j += 1
            if j > i:
                _query_tick(ctx, qs[i:j])
                i, ran = j, True
            if time.perf_counter() >= next_ingest:
                _ingest_tick(ctx, next_ingest)
                next_ingest += period
                ran = True
            if not ran:
                nxt = min(qs[i]["due"] if i < len(qs) else end,
                          next_ingest, end)
                with ctx.spans.span("wait"):
                    time.sleep(max(0.0, nxt - time.perf_counter()))
        while i < len(qs) and qs[i]["due"] <= end:          # drain
            j = min(i + batch, len(qs))
            _query_tick(ctx, qs[i:j])
            i = j
    ctx.window = (t0, end)
    ctx.queries = qs[:i]
    lat = np.asarray([(q["done"] - q["due"]) * 1e3 for q in ctx.queries])
    lag = ingest_lags(ctx)
    akr = [q["n_drawn"] for q in ctx.queries if q["strategy"] == "akr"]
    ctx.log(f"queries: {len(lat)} answered of {len(qs)} scheduled in "
            f"{seconds:g} s, {len(ctx.query_ticks)} query ticks; latency "
            f"ms p50 {np.median(lat):.3f} p95 {np.quantile(lat, 0.95):.3f} "
            f"max {lat.max():.3f}")
    due = int(np.ceil(seconds / period))
    ctx.log(f"ingest: {len(lag)} ticks run of {due} due; lag s first "
            f"{lag[0]:.3f} last {lag[-1]:.3f} max {lag.max():.3f}"
            if len(lag) else "ingest: no tick ran")
    ctx.log("akr draws: " + " ".join(
        f"{n}x{c}" for n, c in zip(*np.unique(akr, return_counts=True))))
    return {"attempted": len(qs), "failed": len(qs) - len(ctx.queries),
            "query_p95_ms": float(np.quantile(lat, 0.95)),
            "query_p50_ms": float(np.median(lat))}


# --------------------------------------------------------------- checking

def _sample(ctx) -> List[dict]:
    tr = ctx.cell.traffic
    qs = ctx.queries
    k = min(len(qs), tr["check"]["queries"])
    pick = set(rng(ctx.seed, "check-sample").choice(len(qs), k,
                                                    replace=False).tolist())
    pick.add(int(np.argmax([q["done"] - q["due"] for q in qs])))
    return [qs[i] for i in sorted(pick)]


def collect(ctx) -> Dict:
    """What the reference compares, read back before the program's state
    is freed: the sampled answers, every recorded query embedding, the
    streams' windows and partition counts, and the stored rows of the
    streams whose index is compared whole."""
    mgr = ctx.mgr
    sample = _sample(ctx)
    sids = sorted({q["sid"] for q in sample})
    whole = sids[:ctx.cell.traffic["check"]["streams_whole"]]
    import jax
    return {
        "sample": sample,
        "embeddings": vmem.query_embeddings(ctx.embedder),
        "windows": vbuild.stream_windows(mgr),
        "partitions": [mgr[s].stats["partitions"]
                       for s in range(ctx.streams)],
        "rows": {s: np.asarray(jax.device_get(mgr.arena.emb[s]))
                 for s in whole},
    }


def check(ctx, col: Dict, lowp: bool = False) -> Dict[str, float]:
    cfg = ctx.cell.config
    venus, mem = cfg["venus"], cfg["mem"]
    cap, dim = venus["memory_capacity"], mem["embed_dim"]
    out: Dict[str, float] = {}
    params = vmem.make_params(mem, sub_seed(ctx.seed, "weights"))
    shape = vbuild.history_shape(ctx.seed, params, mem, ctx.cell.traffic,
                                 ctx.streams)

    def history(s):
        return ref_scan.stored_rows(np.asarray(vbuild.history_rows(
            ctx.seed, s, cap, dim, shape=shape)), venus["index_dtype"], lowp)
    # the index holds the history and nothing else
    bad = sum(w != (0, cap) for w in col["windows"])
    bad += sum(col["partitions"])
    for s, got in col["rows"].items():
        bad += int(np.sum(np.any(got.astype(np.float32) != history(s), -1)))
    out["index_mismatch"] = float(bad)
    # text tower
    texts = sorted(col["embeddings"])
    ref = ref_mem.embed_texts(params, mem, texts)
    if lowp:
        ctrl = ref_mem.embed_texts(params, mem, texts, lowp=True)
        gaps = ref_mem.cosine_gap(ctrl, ref)
        qemb = {t: ctrl[i] for i, t in enumerate(texts)}
    else:
        gaps = np.concatenate([ref_mem.cosine_gap(np.stack(
            col["embeddings"][t]), ref[i][None]) for i, t in
            enumerate(texts)])
    out["text_emb_gap"] = float(np.max(gaps))
    del params
    # retrieval over every retained row
    topk, draw, wrong = 0.0, 0.0, 0
    by_sid: Dict[int, List[dict]] = {}
    for q in col["sample"]:
        by_sid.setdefault(q["sid"], []).append(q)
    keys_needed = {s: max(q["chain"] for q in qs)
                   for s, qs in by_sid.items()}
    import jax.numpy as jnp
    for s, qs in by_sid.items():
        rows = jnp.asarray(history(s))
        cnt, first, ifr = vbuild.history_meta(ctx.seed, s, cap)
        subkeys = ref_scan.chain_subkeys(venus["seed"], keys_needed[s])
        embs = np.stack([qemb[q["text"]] if lowp else q["emb"]
                         for q in qs])
        sc = ref_scan.scores(rows, embs)
        sc_ctrl = ref_scan.scores(rows, embs, lowp=True) if lowp else None
        for i, q in enumerate(qs):
            kind = {"strategy": q["strategy"], "budget": q["budget"]}
            s_ref = sc[i]
            targets = None
            if q["strategy"] in STOCHASTIC:
                targets = ref_scan.draw_targets(subkeys[q["chain"] - 1],
                                                q["budget"])
            if lowp:
                a = ref_scan.answer(sc_ctrl[i], kind, targets, venus["tau"],
                                    venus, lowp=True)
                draws = a["draws"]
                n_drawn = a.get("n_drawn", len(draws))
            else:
                draws, n_drawn = q["draws"], q["n_drawn"]
            if q["strategy"] == "topk":
                topk = max(topk, ref_scan.topk_gap(s_ref, draws))
                if not lowp:
                    wrong += int(not np.array_equal(
                        q["frame_ids"], ifr[np.asarray(draws, np.int64)]))
                continue
            p, cdf = ref_scan.softmax_cdf(s_ref, venus["tau"])
            lanes = np.asarray(draws)[:n_drawn]
            draw = max(draw, ref_scan.draw_gap(cdf, lanes,
                                               targets[:n_drawn]))
            if lowp:
                continue
            if q["strategy"] == "akr":
                wrong += int(n_drawn != ref_scan.akr_stop(
                    p, lanes, venus["theta"], venus["beta"], q["budget"]))
            want = ref_scan.expand_members(
                lanes, first, cnt, ref_scan.expand_u(venus["seed"],
                                                     q["budget"]))
            wrong += int(not np.array_equal(np.sort(q["frame_ids"]), want))
    out["topk_gap"] = topk
    out["draw_gap"] = draw
    out["answer_mismatch"] = float(wrong)
    return out
