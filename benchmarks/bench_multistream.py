"""Multi-session Venus: batched multi-stream ingest + batched querying.

The edge box serves N concurrent camera streams with real-time queries
(the ROADMAP's multi-tenant scenario). This bench measures, on CPU:

* **ingest** — N sessions driven tick-by-tick through the
  ``SessionManager`` (ONE batched MEM call per tick across all streams)
  vs N independent single-stream ``VenusSystem`` instances ingested
  sequentially (per-partition embed calls — the seed path).
* **query** — Q queries per session through ``query_batch`` (one
  similarity scan + vmapped AKR) vs Q sequential ``query`` calls.
* **post-ingest query latency** — the device-resident incrementally
  updated index vs the seed behaviour (every insert invalidates the
  device cache, forcing a full ``(capacity, dim)`` host→device
  re-upload before the next scan).
* **cross-session fused query** — one ``query_batch_cross`` scan over
  ALL sessions' stacked indices vs one ``query_batch`` scan per session
  vs fully sequential ``query`` calls, with scans-per-tick and
  host↔device transfer counters from ``io_stats``.
* **arena vs restack** (``--arena``) — interleaved ingest-tick/query
  rounds where every session grows every tick: the grow-in-place
  ``MemoryArena`` (zero restacks, donated appends) vs the PR-2/3
  detached path (device stack rebuilt every round), with restacks/tick
  and append bandwidth from the counters.
* **session-lifecycle churn** (``--churn``) — rounds of create →
  ingest ⇄ query → close → recreate with a small ``memory_capacity``
  and sliding-window eviction: steady-state slot count (no monotonic
  arena growth under churn), slot reuses, evictions/tick, and
  restacks/tick (asserted 0).

* **sharded arena** (``--shards``) — identical tick/query workloads on
  a 1-shard vs K-shard (``model`` axis) arena mesh: scans/tick,
  per-shard fused launches, candidate-gather bytes vs the dense leak
  bound, and the double-buffered ingest/query overlap. The K>1 arms
  need ``XLA_FLAGS=--xla_force_host_platform_device_count``.

* **disk spill tier** (``--spill``) — ``eviction="none"`` sessions
  under a ``host_retain`` budget, ingesting ≥ 4× their budget:
  demotion throughput (host frames → npy segments) and fault-in
  throughput (cold sweep from disk vs LRU-cached re-reads), with the
  bounded-host invariant (``retained ≤ host_retain``), bit-identical
  round-trips, and full demotion/fault accounting asserted in-harness.
  The spill directory is a tmpdir, removed in a ``finally``.

* **hierarchical tier** (``--tiered``) — a session holding 4× its fine
  capacity of consolidated history answers the same top-k plan via the
  flat 1×-capacity scan (``coarse=False``) vs the two-stage
  coarse→fine retrieval: per-plan scanned bytes from the ``kops``
  counters (two-stage asserted below flat), effective capacity,
  restacks (asserted 0), plus the recall-vs-compression-ratio curve
  from ``bench_fig10.recall_vs_compression``.

``--json`` additionally writes every emitted row (plus run metadata) to
``BENCH_multistream.json`` so CI can upload a machine-readable perf
artifact per commit; the ``trajectory`` key accumulates a compact
summary of every past run (the artifact is re-read before rewriting).

Usage:  PYTHONPATH=src python -m benchmarks.run --only multistream
   (or  PYTHONPATH=src python benchmarks/bench_multistream.py)
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from typing import Dict

if __package__ in (None, ""):               # direct-script invocation
    sys.path.insert(0, ".")
    sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit
from repro.core.memory import VenusMemory
from repro.core.pipeline import VenusConfig, VenusSystem
from repro.core.session import SessionManager
from repro.data.video import (OracleEmbedder, PixelEmbedder, VideoWorld,
                              WorldConfig)
from repro.util import enable_compile_cache


def _bench_ingest(n_sessions: int, chunk: int = 64):
    """Batched multi-stream ingest vs sequential single-stream ingest.

    Uses the REAL dual-tower MEM (the paper's ingestion hot spot): the
    win comes from one jit'd MEM call per tick over every stream's
    closed centroids instead of one call per partition per stream."""
    import jax
    from repro.configs.venus_mem import small_config
    from repro.models.mem import MEM

    worlds = [VideoWorld(WorldConfig(n_scenes=4, seed=20 + s))
              for s in range(n_sessions)]
    n_frames = min(w.total_frames for w in worlds)
    cfg = VenusConfig()
    mem_cfg = small_config()
    mem = MEM(mem_cfg)
    from repro.core.pipeline import MEMEmbedder
    embedder = MEMEmbedder(mem, mem.init(jax.random.key(0)))
    dim = mem_cfg.embed_dim

    def run_batched():
        mgr = SessionManager(cfg, embedder, embed_dim=dim)
        sids = [mgr.create_session() for _ in range(n_sessions)]
        agg: Dict[str, float] = {}
        for i in range(0, n_frames, chunk):
            t = mgr.ingest_tick({sid: w.frames[i:i + chunk]
                                 for sid, w in zip(sids, worlds)})
            for k, v in t.items():
                agg[k] = agg.get(k, 0.0) + v
        mgr.flush()
        return agg

    def run_sequential():
        systems = [VenusSystem(cfg, embedder, embed_dim=dim)
                   for _ in range(n_sessions)]
        for sys_, w in zip(systems, worlds):
            for i in range(0, n_frames, chunk):
                sys_.ingest(w.frames[i:i + chunk])
            sys_.flush()

    run_batched()           # warm the jit caches (scene/cluster/embed)
    run_sequential()        # the seed path shares most of them
    t0 = time.perf_counter()
    agg = run_batched()
    batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_sequential()
    sequential_s = time.perf_counter() - t0

    total = n_frames * n_sessions
    emit("multistream/ingest_batched", batched_s,
         {"sessions": n_sessions, "frames": total,
          "fps": f"{total / batched_s:.0f}",
          "segment_s": f"{agg.get('segment', 0):.3f}",
          "cluster_s": f"{agg.get('cluster', 0):.3f}",
          "embed_insert_s": f"{agg.get('embed_insert', 0):.3f}"})
    emit("multistream/ingest_sequential", sequential_s,
         {"sessions": n_sessions, "fps": f"{total / sequential_s:.0f}",
          "speedup": f"{sequential_s / batched_s:.2f}x"})


def _bench_query(n_sessions: int, n_queries: int, chunk: int = 64):
    """Batched query path vs sequential, same keys → same results."""
    worlds = [VideoWorld(WorldConfig(n_scenes=6, seed=20 + s))
              for s in range(n_sessions)]
    n_frames = min(w.total_frames for w in worlds)
    mgr = SessionManager(VenusConfig(), PixelEmbedder(dim=64),
                         embed_dim=64)
    sids = [mgr.create_session() for _ in range(n_sessions)]
    for i in range(0, n_frames, chunk):
        mgr.ingest_tick({sid: w.frames[i:i + chunk]
                         for sid, w in zip(sids, worlds)})
    mgr.flush()

    oracle_qs = {sid: OracleEmbedder(w, dim=64).embed_queries(
        w.make_queries(n_queries, seed=31))
        for sid, w in zip(sids, worlds)}

    # warm both query paths (vmapped AKR + scalar AKR compiles)
    mgr.query_batch(sids[0], query_embs=oracle_qs[sids[0]])
    mgr.query(sids[0], "", query_emb=oracle_qs[sids[0]][0])

    t0 = time.perf_counter()
    n_frames_batched = 0
    timings: Dict[str, float] = {}
    for sid in sids:
        results = mgr.query_batch(sid, query_embs=oracle_qs[sid])
        n_frames_batched += sum(len(r.frame_ids) for r in results)
        for k, v in results[0].timings.items():
            timings[k] = timings.get(k, 0.0) + v
    batched_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for sid in sids:
        for qe in oracle_qs[sid]:
            mgr.query(sid, "", query_emb=qe)
    sequential_s = time.perf_counter() - t0

    nq = len(sids) * n_queries
    emit("multistream/query_batched", batched_s,
         {"sessions": len(sids), "queries": nq,
          "qps": f"{nq / batched_s:.1f}",
          "frames_retrieved": n_frames_batched,
          **{f"{k}_s": f"{v:.4f}" for k, v in timings.items()}})
    emit("multistream/query_sequential", sequential_s,
         {"qps": f"{nq / sequential_s:.1f}",
          "speedup": f"{sequential_s / batched_s:.2f}x"})


def _bench_query_plan(n_sessions: int, n_queries: int, chunk: int = 64,
                      ticks: int = 5, n_scenes: int = 6):
    """Mixed-strategy service ticks through the declarative planner.

    Each tick answers ``n_queries`` queries per session with a strategy
    mix (AKR / top-k / BOLT). The planner must fuse the tick into one
    execution group per strategy — ``group_scans`` counts exactly
    ``len(strategies)`` scans per tick no matter how many sessions or
    queries the tick spans."""
    from repro.core.queryplan import QuerySpec

    mix = ("akr", "topk", "bolt")
    worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=20 + s))
              for s in range(n_sessions)]
    n_frames = min(w.total_frames for w in worlds)
    mgr = SessionManager(VenusConfig(), PixelEmbedder(dim=64),
                         embed_dim=64)
    sids = [mgr.create_session() for _ in range(n_sessions)]
    for i in range(0, n_frames, chunk):
        mgr.ingest_tick({sid: w.frames[i:i + chunk]
                         for sid, w in zip(sids, worlds)})
    mgr.flush()

    def tick_specs(t):
        specs = []
        for si, (sid, w) in enumerate(zip(sids, worlds)):
            qes = OracleEmbedder(w, dim=64).embed_queries(
                w.make_queries(n_queries, seed=131 + 7 * t))
            specs += [QuerySpec(sid=sid, embedding=qes[qi],
                                strategy=mix[(si + qi) % len(mix)],
                                budget=8)
                      for qi in range(n_queries)]
        return specs

    # specs (incl. embeddings) precomputed so the timed loop measures the
    # planner/executor only — comparable to the cross bench's qe_by_tick
    specs_by_tick = [tick_specs(t) for t in range(ticks)]
    plan = mgr.plan(specs_by_tick[0])
    assert plan.n_scans == len(mix), plan.describe()
    mgr.execute(plan)                                   # warm
    base = dict(mgr.io_stats)
    t0 = time.perf_counter()
    for specs in specs_by_tick:
        mgr.query_specs(specs)
    plan_s = time.perf_counter() - t0
    scans_per_tick = (mgr.io_stats["group_scans"]
                      - base["group_scans"]) / ticks
    assert scans_per_tick == len(mix), scans_per_tick
    emit("multistream/query_plan_mixed", plan_s,
         {"sessions": n_sessions, "queries_per_tick": len(sids) * n_queries,
          "strategies": len(mix), "ticks": ticks,
          "scans_per_tick": f"{scans_per_tick:.1f}"})


def _bench_query_cross(n_sessions: int, n_queries: int, chunk: int = 64,
                       ticks: int = 5, n_scenes: int = 6):
    """Cross-session fused query path vs per-session vs sequential.

    Each "tick" answers ``n_queries`` queries per session (the service
    scenario: queries spread over every stream arriving together). The
    fused path must issue ONE scan per tick regardless of S; the
    per-session path issues S; sequential issues S×Q. Transfer counters
    come straight from the memory/manager io_stats."""
    worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=20 + s))
              for s in range(n_sessions)]
    n_frames = min(w.total_frames for w in worlds)

    def build():
        mgr = SessionManager(VenusConfig(), PixelEmbedder(dim=64),
                             embed_dim=64)
        sids = [mgr.create_session() for _ in range(n_sessions)]
        for i in range(0, n_frames, chunk):
            mgr.ingest_tick({sid: w.frames[i:i + chunk]
                             for sid, w in zip(sids, worlds)})
        mgr.flush()
        return mgr, sids

    # per tick: qsids repeats each session n_queries times, embeddings
    # packed (S * n_queries, d) in the same order
    qe_by_tick = [np.concatenate([OracleEmbedder(w, dim=64).embed_queries(
        w.make_queries(n_queries, seed=31 + 7 * t)) for w in worlds])
        for t in range(ticks)]

    def transfers(mgr, sids):
        return {
            "full_uploads": sum(mgr[s].memory.io_stats["full_uploads"]
                                for s in sids),
            "appended_rows": sum(mgr[s].memory.io_stats["appended_rows"]
                                 for s in sids),
            "host_expand_gathers": sum(
                mgr[s].memory.io_stats["host_expand_gathers"]
                for s in sids),
        }

    qsids = [sid for sid in range(n_sessions) for _ in range(n_queries)]

    # --- fused: one scan over the whole stack per tick
    mgr, sids = build()
    tick_sids = [sids[s] for s in qsids]
    mgr.query_batch_cross(tick_sids, query_embs=qe_by_tick[0])   # warm
    base_scans = dict(mgr.io_stats)
    t0 = time.perf_counter()
    for t in range(ticks):
        mgr.query_batch_cross(tick_sids, query_embs=qe_by_tick[t])
    fused_s = time.perf_counter() - t0
    scans_per_tick = (mgr.io_stats["fused_scans"]
                      - base_scans["fused_scans"]) / ticks
    emit("multistream/query_cross_fused", fused_s,
         {"sessions": n_sessions, "queries_per_tick": len(qsids),
          "ticks": ticks, "scans_per_tick": f"{scans_per_tick:.1f}",
          **transfers(mgr, sids)})

    # --- per-session batched: one scan per session per tick
    mgr, sids = build()
    mgr.query_batch(sids[0], query_embs=qe_by_tick[0][:n_queries])  # warm
    base_scans = dict(mgr.io_stats)
    t0 = time.perf_counter()
    for t in range(ticks):
        for si, sid in enumerate(sids):
            lo = si * n_queries
            mgr.query_batch(sid,
                            query_embs=qe_by_tick[t][lo:lo + n_queries])
    per_session_s = time.perf_counter() - t0
    emit("multistream/query_cross_per_session", per_session_s,
         {"scans_per_tick":
          f"{(mgr.io_stats['scans'] - base_scans['scans']) / ticks:.1f}",
          "speedup_vs_fused": f"{per_session_s / fused_s:.2f}x",
          **transfers(mgr, sids)})

    # --- sequential scalar queries
    mgr, sids = build()
    mgr.query(sids[0], "", query_emb=qe_by_tick[0][0])         # warm
    t0 = time.perf_counter()
    for t in range(ticks):
        for j, s in enumerate(qsids):
            mgr.query(sids[s], "", query_emb=qe_by_tick[t][j])
    sequential_s = time.perf_counter() - t0
    emit("multistream/query_cross_sequential", sequential_s,
         {"speedup_vs_fused": f"{sequential_s / fused_s:.2f}x",
          **transfers(mgr, sids)})


def _bench_arena(n_sessions: int, n_queries: int, chunk: int = 64,
                 ticks: int = 5, n_scenes: int = 6):
    """Grow-in-place arena vs the PR-2/3 restack path.

    The adversarial schedule for a version-cached stack: every tick
    grows EVERY session (``max_partition_len`` < chunk forces ≥ 1
    partition close per tick), then a query plan runs — the detached
    path must restack the grown sessions' device buffers before each
    scan, the arena path consumes its super-buffers as-is. Reports
    wall time split into ingest/query, restacks per tick, and append
    bandwidth (rows moved per second of ingest)."""
    cfg = VenusConfig(max_partition_len=min(48, chunk - 16))
    worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=20 + s))
              for s in range(n_sessions)]

    def chunk_at(w, t):
        lo = (t * chunk) % max(w.total_frames - chunk, 1)
        return w.frames[lo:lo + chunk]

    qe_by_tick = [np.concatenate([OracleEmbedder(w, dim=64).embed_queries(
        w.make_queries(n_queries, seed=31 + 7 * t)) for w in worlds])
        for t in range(ticks)]
    qsids = [s for s in range(n_sessions) for _ in range(n_queries)]

    def run_mode(use_arena: bool):
        mgr = SessionManager(cfg, PixelEmbedder(dim=64), embed_dim=64,
                             use_arena=use_arena)
        sids = [mgr.create_session() for _ in range(n_sessions)]
        tick_sids = [sids[s] for s in qsids]
        # warm-up: compile ingest + append + scan + expansion paths
        mgr.ingest_tick({sid: chunk_at(w, 0)
                         for sid, w in zip(sids, worlds)})
        mgr.query_batch_cross(tick_sids, query_embs=qe_by_tick[0])
        mgr.reset_io_stats()
        rows0 = sum(mgr[s].memory.size for s in sids)

        t_ingest = t_query = 0.0
        for t in range(1, ticks + 1):
            t0 = time.perf_counter()
            mgr.ingest_tick({sid: chunk_at(w, t)
                             for sid, w in zip(sids, worlds)})
            t_ingest += time.perf_counter() - t0
            t0 = time.perf_counter()
            mgr.query_batch_cross(tick_sids,
                                  query_embs=qe_by_tick[t % ticks])
            t_query += time.perf_counter() - t0
        # rows actually indexed over the timed window — identical units
        # for both modes (io_stats appended_rows counts raw rows on the
        # deferred arena path but bucket-padded rows on the detached
        # path, so it cannot be compared across modes)
        rows = sum(mgr[s].memory.size for s in sids) - rows0
        return mgr, sids, t_ingest, t_query, rows

    # a full untimed pass per mode first: the clustering stage's eager
    # ops compile per partition-length, and those caches are GLOBAL —
    # without this, whichever mode runs first pays every compile and
    # the comparison measures compiler order, not the memory paths
    for use_arena in (True, False):
        run_mode(use_arena)

    out = {}
    for name, use_arena in (("arena", True), ("restack", False)):
        mgr, sids, t_ingest, t_query, rows = run_mode(use_arena)
        restacks_per_tick = mgr.io_stats["stack_rebuilds"] / ticks
        out[name] = {"total": t_ingest + t_query, "query": t_query,
                     "restacks_per_tick": restacks_per_tick}
        emit(f"multistream/arena_{name}", t_ingest + t_query,
             {"sessions": n_sessions, "ticks": ticks,
              "queries_per_tick": len(qsids),
              "ingest_s": f"{t_ingest:.4f}",
              "query_s": f"{t_query:.4f}",
              "restacks_per_tick": restacks_per_tick,
              "indexed_rows": rows,
              "append_rows_per_s": f"{rows / max(t_ingest, 1e-9):.0f}"})

    # the tentpole invariant, asserted where CI runs it: the arena never
    # restacks, the detached path restacks every round it grew
    assert out["arena"]["restacks_per_tick"] == 0.0, out["arena"]
    assert out["restack"]["restacks_per_tick"] >= 1.0, out["restack"]
    emit("multistream/arena_speedup", 0.0,
         {"query_speedup":
          f"{out['restack']['query'] / out['arena']['query']:.2f}x",
          "total_speedup":
          f"{out['restack']['total'] / out['arena']['total']:.2f}x"},
         value=out["restack"]["query"] / out["arena"]["query"])


def _bench_churn(n_sessions: int, n_queries: int, chunk: int = 64,
                 rounds: int = 3, ticks: int = 4, n_scenes: int = 6):
    """24/7 churn workload: create → ingest ⇄ query → close → recreate.

    One stream churns every round (closed, then recreated — its arena
    slot must be RECYCLED from the free-list, not grown) while the rest
    run long enough to overflow ``memory_capacity`` and evict under the
    sliding-window policy. Reports wall time, steady-state slot count
    (must equal the live-stream count — no monotonic growth), slot
    reuses, evictions per tick, and restacks per tick (must be 0): the
    production invariants ``tests/test_lifecycle.py`` pins, measured on
    the full workload."""
    cfg = VenusConfig(max_partition_len=32, memory_capacity=24,
                      eviction="sliding_window")
    worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=20 + s))
              for s in range(n_sessions)]
    mgr = SessionManager(cfg, PixelEmbedder(dim=64), embed_dim=64)
    stable = [mgr.create_session() for _ in range(n_sessions - 1)]
    churn_sid = mgr.create_session()
    steady = mgr.arena.n_sessions

    def chunk_at(w, t):
        lo = (t * chunk) % max(w.total_frames - chunk, 1)
        return w.frames[lo:lo + chunk]

    def stream_map():
        m = {sid: worlds[i] for i, sid in enumerate(stable)}
        m[churn_sid] = worlds[-1]
        return m

    # per-(round, tick) query embeddings, precomputed so the timed loop
    # measures the lifecycle paths, not the oracle embedder
    qe_by_step = [np.concatenate([
        OracleEmbedder(w, dim=64).embed_queries(
            w.make_queries(n_queries, seed=31 + 13 * step))
        for w in worlds])
        for step in range(rounds * ticks)]

    # warm-up: one tick + one query round compiles ingest/scan/expand
    mgr.ingest_tick({sid: chunk_at(w, 0)
                     for sid, w in stream_map().items()})
    qsids = [s for s in range(n_sessions) for _ in range(n_queries)]
    mgr.query_batch_cross([list(stream_map())[s] for s in qsids],
                          query_embs=qe_by_step[0])
    mgr.reset_io_stats()          # zeroes every memory's counters too

    t0 = time.perf_counter()
    total_ticks = 0
    for r in range(rounds):
        mgr.close_session(churn_sid)
        churn_sid = mgr.create_session()        # must recycle the slot
        for t in range(ticks):
            step = r * ticks + t
            smap = stream_map()
            mgr.ingest_tick({sid: chunk_at(w, 1 + step)
                             for sid, w in smap.items()})
            sids_now = list(smap)
            mgr.query_batch_cross([sids_now[s] for s in qsids],
                                  query_embs=qe_by_step[step])
            total_ticks += 1
    churn_s = time.perf_counter() - t0

    # closed_mem_stats keeps churned tenants' counters — summing live
    # sessions alone would drop every closed round's evictions
    evictions = mgr.closed_mem_stats.get("evicted_rows", 0) + sum(
        mgr[s].memory.io_stats["evicted_rows"] for s in mgr.sessions)
    restacks_per_tick = mgr.io_stats["stack_rebuilds"] / total_ticks
    evictions_per_tick = evictions / total_ticks
    # the lifecycle invariants, asserted where CI runs them: slots hold
    # at the steady-state maximum, churned slots are reused not grown,
    # and nothing ever restacks
    assert mgr.arena.n_sessions == steady, mgr.arena.n_sessions
    assert mgr.arena.io_stats["grows"] == 0, mgr.arena.io_stats
    assert mgr.arena.io_stats["slot_reuses"] == rounds, mgr.arena.io_stats
    assert restacks_per_tick == 0.0, restacks_per_tick
    assert evictions > 0, "churn workload never reached capacity"
    emit("multistream/churn", churn_s,
         {"sessions": n_sessions, "rounds": rounds,
          "ticks_per_round": ticks,
          "queries_per_tick": len(qsids),
          "steady_state_slots": steady,
          "slot_reuses": mgr.arena.io_stats["slot_reuses"],
          "grows_after_warmup": mgr.arena.io_stats["grows"],
          "evictions_per_tick": f"{evictions_per_tick:.1f}",
          "restacks_per_tick": restacks_per_tick,
          "sessions_closed": mgr.io_stats["sessions_closed"]})


def _bench_spill(n_sessions: int, chunk: int = 64, ticks: int = 8,
                 n_scenes: int = 4, host_retain: int = 64,
                 segment_frames: int = 16):
    """Disk spill tier: demote/fault throughput on bounded-host
    ``eviction="none"`` sessions.

    N keep-everything streams ingest ``ticks`` chunks each (≥ 4× the
    ``host_retain`` budget), so ``_trim_archives`` demotes their cold
    frames into npy segments every tick. Measures demotion throughput
    (inside the ingest ticks), cold fault-in throughput (full-history
    sweep with an empty LRU cache), and warm re-read throughput (the
    same sweep again, served by the cache). The production invariants
    are asserted in-harness: host ``retained ≤ host_retain`` on every
    stream, every demotion and fault accounted by the counters,
    bit-identical round-trips against the ingested chunks, zero
    restacks, and the spill tmpdir is removed in a ``finally``."""
    assert ticks * chunk >= 4 * host_retain, (ticks, chunk, host_retain)
    tmp = tempfile.mkdtemp(prefix="venus-spill-bench-")
    try:
        cfg = VenusConfig(max_partition_len=32, spill_dir=tmp,
                          host_retain=host_retain,
                          spill_segment_frames=segment_frames)
        worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=40 + s))
                  for s in range(n_sessions)]
        mgr = SessionManager(cfg, PixelEmbedder(dim=64), embed_dim=64)
        sids = [mgr.create_session() for _ in range(n_sessions)]
        twins = {sid: [] for sid in sids}

        def chunk_at(w, t):
            lo = (t * chunk) % max(w.total_frames - chunk, 1)
            return np.asarray(w.frames[lo:lo + chunk], np.float32)

        # warm-up tick compiles segment/embed paths before timing
        mgr.ingest_tick({sid: chunk_at(w, 0)
                         for sid, w in zip(sids, worlds)})
        for sid, w in zip(sids, worlds):
            twins[sid].extend(chunk_at(w, 0))

        t0 = time.perf_counter()
        for t in range(1, ticks):
            mgr.ingest_tick({sid: chunk_at(w, t)
                             for sid, w in zip(sids, worlds)})
            for sid, w in zip(sids, worlds):
                twins[sid].extend(chunk_at(w, t))
        ingest_s = time.perf_counter() - t0

        spilled_frames = spilled_bytes = 0
        for sid in sids:
            fs = mgr[sid].frames
            # the bounded-host invariant, where CI runs it
            assert fs.retained <= host_retain, (fs.retained, host_retain)
            assert fs.io_stats["spilled_frames"] == fs.trimmed > 0
            spilled_frames += fs.io_stats["spilled_frames"]
            spilled_bytes += fs.io_stats["spilled_bytes"]

        # cold sweep: every historical id of every stream faults its
        # segment from disk (caches are empty — nothing was read yet)
        t0 = time.perf_counter()
        for sid in sids:
            fs = mgr[sid].frames
            got = fs.get(list(range(len(fs))))
            assert got.tobytes() == np.stack(twins[sid]).tobytes()
        cold_s = time.perf_counter() - t0
        faults = sum(mgr[sid].frames.io_stats["spill_faults"]
                     for sid in sids)
        assert faults > 0, "cold sweep never touched disk"

        # warm sweep: identical reads — the LRU cache absorbs re-reads
        # of the most recent segments (small cache ⇒ partial hits only)
        t0 = time.perf_counter()
        for sid in sids:
            mgr[sid].frames.get(list(range(len(mgr[sid].frames))))
        warm_s = time.perf_counter() - t0
        hits = sum(mgr[sid].frames.io_stats["spill_cache_hits"]
                   for sid in sids)
        # every spilled read was either a fault or a cache hit
        reads = 2 * spilled_frames
        total_faults = sum(mgr[sid].frames.io_stats["spill_faults"]
                           for sid in sids)
        assert total_faults + hits == reads, (total_faults, hits, reads)
        assert mgr.io_stats["stack_rebuilds"] == 0
        total_frames = sum(len(mgr[sid].frames) for sid in sids)
        emit("multistream/spill", ingest_s,
             {"sessions": n_sessions, "ticks": ticks,
              "host_retain": host_retain,
              "frames_total": total_frames,
              "spilled_frames": spilled_frames,
              "spilled_mb": f"{spilled_bytes / 2**20:.1f}",
              "demote_frames_per_s":
                  f"{spilled_frames / max(ingest_s, 1e-9):.0f}",
              "cold_fault_frames_per_s":
                  f"{total_frames / max(cold_s, 1e-9):.0f}",
              "warm_read_frames_per_s":
                  f"{total_frames / max(warm_s, 1e-9):.0f}",
              "spill_faults": total_faults,
              "spill_cache_hits": hits,
              "restacks": mgr.io_stats["stack_rebuilds"]})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_fused(n_sessions: int, n_queries: int, chunk: int = 64,
                 ticks: int = 5, n_scenes: int = 6,
                 index_dtype: str = "int8"):
    """One-launch fused retrieval + quantised index vs the dense path.

    Three arms over identical worlds and identical query plans (all
    strategies fused-eligible):

    * ``dense_fp32``  — ``execute(plan, fused=False)``: every group
      materialises the (S, Q, cap) score/probability tensors, then
      draws/top-ks in separate launches (the PR-3..5 path).
    * ``fused_fp32``  — the fused epilogue: draws + drawn probabilities
      + top-k leave the scan launch directly; nothing O(cap) per query
      crosses the launch boundary.
    * ``fused_<dt>``  — fused epilogue over the quantised arena
      (``VenusConfig(index_dtype=...)``): the scan streams 1-byte index
      rows (per-row scales cancel under the kernel's row
      normalisation), cutting scanned bytes 4× on top.

    Reports per-tick wall time, scanned index bytes per tick
    (``kops_scan_bytes`` deltas), fused vs dense launch counts, and the
    peak live index bytes (arena super-buffer + scales). The reduction
    row asserts the headline ≥ 2× scanned-bytes cut."""
    from repro.core.queryplan import QuerySpec
    from repro.kernels import ops as kops

    mix = ("akr", "topk", "sampling")
    worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=20 + s))
              for s in range(n_sessions)]
    n_frames = min(w.total_frames for w in worlds)

    # per-(tick, session, query) embeddings precomputed; sids are fresh
    # 0..S-1 for every build() so specs transfer across managers
    qe_by_tick = [[OracleEmbedder(w, dim=64).embed_queries(
        w.make_queries(n_queries, seed=31 + 7 * t)) for w in worlds]
        for t in range(ticks)]

    def tick_specs(t):
        return [QuerySpec(sid=s, embedding=qe_by_tick[t][s][qi],
                          strategy=mix[(s + qi) % len(mix)], budget=8)
                for s in range(n_sessions) for qi in range(n_queries)]

    def build(dtype):
        mgr = SessionManager(VenusConfig(index_dtype=dtype),
                             PixelEmbedder(dim=64), embed_dim=64)
        sids = [mgr.create_session() for _ in range(n_sessions)]
        assert sids == list(range(n_sessions))
        for i in range(0, n_frames, chunk):
            mgr.ingest_tick({sid: w.frames[i:i + chunk]
                             for sid, w in zip(sids, worlds)})
        mgr.flush()
        return mgr

    def peak_index_bytes(mgr):
        a = mgr.arena
        b = a.emb.size * a.emb.dtype.itemsize
        if a.emb_scale is not None:
            b += a.emb_scale.size * a.emb_scale.dtype.itemsize
        return int(b)

    out = {}
    arms = (("dense_fp32", "float32", False),
            ("fused_fp32", "float32", True),
            (f"fused_{index_dtype}", index_dtype, True))
    for name, dtype, fused in arms:
        mgr = build(dtype)
        plans = [mgr.plan(tick_specs(t)) for t in range(ticks)]
        mgr.execute(plans[0], fused=fused)                  # warm
        kops.reset_scan_counts()
        t0 = time.perf_counter()
        for plan in plans:
            mgr.execute(plan, fused=fused)
        dt = time.perf_counter() - t0
        c = kops.scan_counts()
        out[name] = c["scan_bytes"] / ticks
        emit(f"multistream/fused_retrieval_{name}", dt,
             {"sessions": n_sessions, "ticks": ticks,
              "queries_per_tick": n_sessions * n_queries,
              "index_dtype": dtype,
              "scan_bytes_per_tick": int(out[name]),
              "fused_launches": c["fused_draw_launches"],
              "dense_launches": c["dense_score_launches"],
              "peak_index_bytes": peak_index_bytes(mgr)})

    # the headline: dense fp32 scan traffic vs fused + quantised
    reduction = out["dense_fp32"] / max(out[f"fused_{index_dtype}"], 1)
    assert reduction >= 2.0, out
    emit("multistream/fused_scan_bytes_reduction", 0.0,
         {"scan_bytes_reduction": f"{reduction:.2f}x",
          "fused_fp32_vs_dense":
          f"{out['dense_fp32'] / max(out['fused_fp32'], 1):.2f}x"},
         value=reduction)


def _bench_shards(n_sessions: int, n_queries: int, chunk: int = 64,
                  ticks: int = 4, n_scenes: int = 6):
    """Sharded-arena fan-out: a 1-shard vs a K-shard (K ≤ 4) mesh.

    Same worlds, same ticks, same queries through managers whose arena
    super-buffers live on a ``model=1`` vs ``model=K`` mesh
    (``make_memory_mesh``). Reports wall time per tick, group scans per
    tick, per-shard fused launches, and the bytes the candidate
    all_gather moves across shard boundaries
    (``kops_shard_gather_bytes``) against the dense O(S·Q·capacity)
    leak bound — the gather is O(S·Q·(T+K)) outputs only, so the
    counter must come in far below one (S, Q, cap) f32 tensor. Both
    arms assert ``stack_rebuilds == 0``. The K-shard arm additionally
    runs with double buffering off to price the ingest/query overlap
    (the donated append scatter lands on the trailing buffer set while
    the front set serves the fused scan).

    With one visible device (no
    ``XLA_FLAGS=--xla_force_host_platform_device_count``) only the
    1-shard arm runs; the row still lands so CI diffs stay aligned."""
    import jax
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_memory_mesh

    k = min(4, len(jax.devices()))
    worlds = [VideoWorld(WorldConfig(n_scenes=n_scenes, seed=20 + s))
              for s in range(n_sessions)]
    qsids = [s for s in range(n_sessions) for _ in range(n_queries)]
    qe_by_tick = [np.concatenate([
        OracleEmbedder(w, dim=64).embed_queries(
            w.make_queries(n_queries, seed=31 + 13 * t))
        for w in worlds]) for t in range(ticks)]

    def chunk_at(w, t):
        lo = (t * chunk) % max(w.total_frames - chunk, 1)
        return w.frames[lo:lo + chunk]

    def drive(shards, double_buffer):
        mgr = SessionManager(VenusConfig(), PixelEmbedder(dim=64),
                             embed_dim=64,
                             mesh=make_memory_mesh(shards=shards),
                             double_buffer=double_buffer)
        sids = [mgr.create_session() for _ in range(n_sessions)]
        # warm: compile ingest + the (sharded) fused scan once
        mgr.ingest_tick({sid: chunk_at(w, 0)
                         for sid, w in zip(sids, worlds)})
        mgr.query_batch_cross([sids[s] for s in qsids],
                              query_embs=qe_by_tick[0])
        mgr.reset_io_stats()
        kops.reset_scan_counts()
        t0 = time.perf_counter()
        for t in range(ticks):
            mgr.ingest_tick({sid: chunk_at(w, 1 + t)
                             for sid, w in zip(sids, worlds)})
            mgr.query_batch_cross([sids[s] for s in qsids],
                                  query_embs=qe_by_tick[t])
        return mgr, time.perf_counter() - t0, kops.scan_counts()

    overlap = {}
    for shards in (1,) + ((k,) if k > 1 else ()):
        mgr, dt, c = drive(shards, double_buffer=True)
        a = mgr.arena
        assert a.n_shards == shards, a.n_shards
        assert mgr.io_stats["stack_rebuilds"] == 0, mgr.io_stats
        if shards > 1:
            # the sharded lane actually ran, and its cross-shard
            # traffic stayed candidate-sized (no dense-score leak)
            assert c["sharded_stack_launches"] > 0, c
            dense = a.n_sessions * len(qsids) * a.capacity * 4
            assert 0 < c["shard_gather_bytes"] < dense * ticks, c
            _, dt_nodb, _ = drive(shards, double_buffer=False)
            overlap["ingest_query_overlap"] = \
                f"{dt_nodb / max(dt, 1e-9):.2f}x"
        emit(f"multistream/sharded_{shards}shard", dt,
             {"sessions": n_sessions, "ticks": ticks,
              "queries_per_tick": len(qsids),
              "arena_shards": a.n_shards,
              "scans_per_tick": mgr.io_stats["group_scans"] / ticks,
              "sharded_group_scans": mgr.io_stats["sharded_group_scans"],
              "sharded_stack_launches": c["sharded_stack_launches"],
              "shard_gather_bytes_per_tick":
                  c["shard_gather_bytes"] // ticks,
              "stack_rebuilds": mgr.io_stats["stack_rebuilds"],
              "double_flushes": a.io_stats["double_flushes"],
              **overlap})


def _bench_tiered(n_queries: int = 2, smoke: bool = False):
    """Hierarchical consolidation tier: flat scan vs two-stage retrieval.

    One session ingests 4× its fine capacity of clustered rows under
    ``eviction="consolidate"`` (evictees fold into the coarse summary
    tier), then answers the SAME top-k plan two ways:

    * ``flat`` — ``execute(plan, coarse=False)``: the escape hatch, one
      1×-capacity fused scan (the tier is ignored);
    * ``two_stage`` — coarse scan over the summary tier → top-B winner
      blocks → gathered fine candidates → second fused scan.

    Reports wall time, per-plan scanned index bytes from the ``kops``
    counters (coarse + gathered fine vs the flat scan — the bandwidth
    claim, asserted), the effective capacity ratio (reachable history ÷
    rows streamed per query), and ``stack_rebuilds`` (asserted 0 — the
    tier rides the arena, nothing restacks). The recall-vs-compression
    curve from ``bench_fig10.recall_vs_compression`` runs last so its
    rows land in the same JSON artifact."""
    from benchmarks.bench_fig10 import recall_vs_compression
    from repro.core.queryplan import QuerySpec
    from repro.kernels import ops as kops

    dim, capacity, n_clusters = 32, 512, 8
    cfg = VenusConfig(memory_capacity=capacity, member_cap=8,
                      eviction="consolidate", coarse_capacity=64,
                      coarse_block=32, coarse_topb=4)

    class _DirectEmbedder:
        def embed_queries(self, texts):
            raise AssertionError("bench passes explicit embeddings")

        def embed_frames(self, frames, aux=None, frame_ids=None):
            raise AssertionError("bench inserts rows directly")

    def _unit(rows):
        rows = np.asarray(rows, np.float32)
        return rows / (np.linalg.norm(rows, axis=-1, keepdims=True)
                       + 1e-12)

    rng = np.random.default_rng(7)
    cen = _unit(rng.normal(size=(n_clusters, dim)))
    total = 4 * capacity
    labels = rng.integers(0, n_clusters, size=total)
    rows = _unit(cen[labels] + 0.05 * rng.normal(size=(total, dim)))

    mgr = SessionManager(cfg, _DirectEmbedder(), embed_dim=dim)
    sid = mgr.create_session()
    mem = mgr.sessions[sid].memory
    t0 = time.perf_counter()
    for lo in range(0, total, 64):
        batch = rows[lo:lo + 64]
        fids = np.arange(lo, lo + len(batch))
        with mgr.arena.deferred_appends():
            mem.insert_batch(batch, scene_ids=[0] * len(batch),
                             index_frames=fids,
                             member_lists=[[int(f)] for f in fids])
    ingest_s = time.perf_counter() - t0
    a = mgr.arena
    assert a.has_consolidated()

    specs = [QuerySpec(sid=sid, embedding=cen[qi % n_clusters],
                       strategy="topk", budget=8)
             for qi in range(n_queries)]
    plan = mgr.plan(specs)
    mgr.execute(plan, coarse=False)                # warm both paths
    mgr.execute(plan)
    reps = 2 if smoke else 10
    out = {}
    for name, coarse in (("flat", False), ("two_stage", True)):
        kops.reset_scan_counts()
        t0 = time.perf_counter()
        for _ in range(reps):
            mgr.execute(plan, coarse=coarse)
        dt = time.perf_counter() - t0
        c = kops.scan_counts()
        out[name] = c["scan_bytes"] / reps          # bytes per plan
        derived = {"queries": n_queries, "reps": reps,
                   "fine_capacity": capacity, "ingested_rows": total,
                   "scan_bytes_per_plan": int(out[name]),
                   "stack_rebuilds": mgr.io_stats["stack_rebuilds"],
                   "ingest_s": f"{ingest_s:.3f}"}
        if coarse:
            per_query_rows = (a.n_coarse
                              + c["fine_gather_rows"] // (reps
                                                          * n_queries))
            derived.update(
                {"coarse_scan_bytes_per_plan":
                     c["coarse_scan_bytes"] // reps,
                 "fine_gather_rows_per_query":
                     c["fine_gather_rows"] // (reps * n_queries),
                 "two_stage_scans": c["two_stage_scans"],
                 "scanned_rows_per_query": per_query_rows,
                 "effective_capacity":
                     f"{total / per_query_rows:.1f}x"})
        emit(f"multistream/tiered_{name}", dt, derived)

    # the tentpole invariants, asserted where CI runs them: the tier
    # never restacks and the two-stage scan undercuts the flat one
    assert mgr.io_stats["stack_rebuilds"] == 0, mgr.io_stats
    assert out["two_stage"] < out["flat"], out
    reduction = out["flat"] / max(out["two_stage"], 1)
    # the recorded headline must be a real reduction, smoke included —
    # a 0.0 here means the smoke run never actually consolidated
    assert reduction > 1.0, out
    emit("multistream/tiered_scan_bytes_reduction", 0.0,
         {"scan_bytes_reduction": f"{reduction:.2f}x",
          "history_vs_flat_reach":
          f"{total / capacity:.0f}x"},
         value=reduction)

    # recall-vs-compression-ratio curve (fig10 accuracy harness) — the
    # rows land in this bench's JSON sink / trajectory
    recall_vs_compression(ratios=(1, 4) if smoke else (1, 2, 4, 8),
                          prefix="multistream/tiered_recall")


def _bench_incremental_index(capacity: int = 16384, dim: int = 256,
                             rounds: int = 20):
    """Post-ingest query latency: incremental append vs full re-upload."""
    rng = np.random.default_rng(0)
    base = rng.normal(0, 1, (capacity // 4, dim)).astype(np.float32)
    q = rng.normal(0, 1, (1, dim)).astype(np.float32)

    out = {}
    for name, incremental in (("incremental", True), ("seed_reupload",
                                                      False)):
        mem = VenusMemory(capacity, dim, member_cap=8,
                          incremental=incremental)
        mem.insert_batch(base, scene_ids=[0] * len(base),
                         index_frames=list(range(len(base))),
                         member_lists=[[i] for i in range(len(base))])
        mem.search(jnp.asarray(q), tau=0.1)      # warm: index on device

        def step(r):
            rows = rng.normal(0, 1, (8, dim)).astype(np.float32)
            lo = mem.size
            mem.insert_batch(rows, scene_ids=[1] * 8,
                             index_frames=list(range(lo, lo + 8)),
                             member_lists=[[i] for i in range(lo, lo + 8)])
            _, p = mem.search(jnp.asarray(q), tau=0.1)
            np.asarray(p)                         # block
        step(-1)                                  # warm the append jit
        t0 = time.perf_counter()
        for r in range(rounds):
            step(r)
        out[name] = (time.perf_counter() - t0) / rounds
        emit(f"multistream/post_ingest_query_{name}", out[name],
             {"full_uploads": mem.io_stats["full_uploads"],
              "appended_rows": mem.io_stats["appended_rows"]})
    emit("multistream/post_ingest_query_speedup", 0.0,
         {"speedup": f"{out['seed_reupload'] / out['incremental']:.2f}x"},
         value=out["seed_reupload"] / out["incremental"])


def _bench_standing(n_sessions: int = 4, smoke: bool = False):
    """Standing queries on the ingest path (``repro.core.standing``).

    ``n_sessions`` direct-insert streams each carry standing top-k
    specs keyed to known cluster centroids; every tick commits a batch
    of rows per stream and runs the ONE extra fused launch over the
    ``(S, max_new, d)`` new-row slab. Reports the evaluate() wall time
    per tick, alerts+suppressions per tick, and the headline bytes
    claim — ``standing_scan_bytes`` per tick vs the full-capacity
    re-scan the slab replaces — asserted in-harness: the slab stays
    within the 2× pow2-padding envelope of ``new_rows · d`` and far
    under the capacity bound, with ``stack_rebuilds == 0``."""
    from repro.core.queryplan import QuerySpec
    from repro.kernels import ops as kops

    dim, capacity, rows_per_tick = 32, 4096, 16
    ticks = 4 if smoke else 20
    cfg = VenusConfig(memory_capacity=capacity, member_cap=8)

    class _DirectEmbedder:
        def embed_queries(self, texts):
            raise AssertionError("bench passes explicit embeddings")

        def embed_frames(self, frames, aux=None, frame_ids=None):
            raise AssertionError("bench inserts rows directly")

    def _unit(rows):
        rows = np.asarray(rows, np.float32)
        return rows / (np.linalg.norm(rows, axis=-1, keepdims=True)
                       + 1e-12)

    rng = np.random.default_rng(11)
    cen = _unit(rng.normal(size=(n_sessions, dim)))
    mgr = SessionManager(cfg, _DirectEmbedder(), embed_dim=dim)
    sids = [mgr.create_session() for _ in range(n_sessions)]
    for s, sid in enumerate(sids):
        mgr.register_standing(
            sid, QuerySpec(sid=sid, embedding=cen[s], strategy="topk",
                           budget=4),
            threshold=0.8, hysteresis=0.1)

    def _tick(t):
        phys = {}
        for s, sid in enumerate(sids):
            # ~half the ticks carry a near-centroid row -> live alert
            # traffic through the trigger machine, not a dead registry
            hit = (t + s) % 2 == 0
            rows = _unit(rng.normal(size=(rows_per_tick, dim)))
            if hit:
                rows[0] = _unit(cen[s]
                                + 0.05 * rng.normal(size=dim))
            mem = mgr.sessions[sid].memory
            fids = np.arange(t * rows_per_tick,
                             (t + 1) * rows_per_tick)
            with mgr.arena.deferred_appends():
                p = mem.insert_batch(
                    rows, scene_ids=[0] * len(rows),
                    index_frames=fids,
                    member_lists=[[int(f)] for f in fids])
            phys[sid] = [p]
        return phys

    first = _tick(0)                       # warm the slab-shape jits
    mgr.standing.evaluate(mgr.sessions, first, mgr.io_stats)
    mgr.poll_alerts()
    kops.reset_scan_counts()
    fired = supp0 = 0
    supp0 = mgr.io_stats["alerts_suppressed"]
    t0 = time.perf_counter()
    eval_s = 0.0
    for t in range(1, ticks + 1):
        phys = _tick(t)
        te = time.perf_counter()
        fired += len(mgr.standing.evaluate(mgr.sessions, phys,
                                           mgr.io_stats))
        eval_s += time.perf_counter() - te
    total_s = time.perf_counter() - t0
    bytes_per_tick = kops.scan_counts()["standing_scan_bytes"] / ticks
    full_scan_bound = n_sessions * capacity * dim * 4
    # the O(new_rows · d) claim, asserted where CI runs it
    assert bytes_per_tick <= 2 * n_sessions * rows_per_tick * dim * 4, \
        bytes_per_tick
    assert bytes_per_tick < full_scan_bound / 16, bytes_per_tick
    assert mgr.io_stats["stack_rebuilds"] == 0, mgr.io_stats
    assert fired > 0, "bench must exercise live alert traffic"
    emit("multistream/standing_tick", eval_s / ticks,
         {"sessions": n_sessions, "specs": mgr.standing.n_specs,
          "ticks": ticks, "rows_per_tick": rows_per_tick,
          "alerts_per_tick": f"{fired / ticks:.2f}",
          "suppressed":
              mgr.io_stats["alerts_suppressed"] - supp0,
          "scan_bytes_per_tick": int(bytes_per_tick),
          "ingest_plus_eval_s": f"{total_s:.4f}",
          "stack_rebuilds": mgr.io_stats["stack_rebuilds"]})
    emit("multistream/standing_scan_bytes_reduction", 0.0,
         {"vs_full_rescan":
          f"{full_scan_bound / max(bytes_per_tick, 1):.0f}x",
          "full_rescan_bytes_per_tick": full_scan_bound},
         value=full_scan_bound / max(bytes_per_tick, 1))


ALL_PARTS = ("ingest", "query", "cross", "plan", "arena", "churn",
             "fused", "shards", "tiered", "spill", "standing",
             "incremental")
JSON_PATH = "BENCH_multistream.json"


def write_json_artifact(json_path: str, rows: list, meta: dict) -> dict:
    """Merge one run's rows into the cross-run JSON artifact.

    The ``trajectory`` key accumulates ACROSS runs: the previous
    artifact at ``json_path`` is re-read and this run's compact summary
    appended — a bare mode-"w" ``json.dump`` would wipe the history
    every run and leave the trajectory perpetually length-1. A missing
    or corrupt previous artifact starts a fresh trajectory. NOTE for
    CI: the artifact is gitignored, so accumulation only works if the
    workflow RESTORES the previous run's file into the workspace before
    the bench runs (ci.yml does this with ``actions/cache``) — uploads
    alone never land back in the next run's tree. Returns the payload
    it wrote (pinned by ``tests/test_bench_artifact.py``)."""
    try:
        with open(json_path) as f:
            trajectory = json.load(f).get("trajectory", [])
    except (OSError, ValueError):
        trajectory = []
    trajectory.append(
        {"timestamp": meta["timestamp"], "parts": meta["parts"],
         "smoke": meta["smoke"],
         # metric rows (seconds=0.0, headline scalar in "value") track
         # their VALUE across runs; timing rows track seconds
         "rows": {r["name"]: round(r.get("value", r["seconds"]), 6)
                  for r in rows}})
    payload = {"meta": meta, "benchmarks": rows,
               "trajectory": trajectory}
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"[bench_multistream] wrote {json_path} "
          f"({len(rows)} rows, {len(trajectory)} runs in trajectory)")
    return payload


def run(n_sessions: int = 4, n_queries: int = 8, *,
        cross_only: bool = False, smoke: bool = False,
        parts=None, json_path: str | None = None,
        index_dtype: str = "int8") -> None:
    assert n_sessions >= 4, "multi-tenant scenario needs ≥4 sessions"
    if parts is None:
        parts = ("cross", "plan", "arena") if cross_only else ALL_PARTS
    rows: list = []
    common.set_sink(rows)
    # smoke: tiny worlds / few ticks — CI exercises the fused cross
    # path, the planner path, and the arena-vs-restack comparison
    # end-to-end in ~a minute
    ticks = 2 if smoke else 5
    n_scenes = 3 if smoke else 6
    if smoke:
        n_queries = min(n_queries, 2)
    try:
        if "ingest" in parts:
            _bench_ingest(n_sessions)
        if "query" in parts:
            _bench_query(n_sessions, n_queries)
        if "cross" in parts:
            _bench_query_cross(n_sessions, n_queries, ticks=ticks,
                               n_scenes=n_scenes)
        if "plan" in parts:
            _bench_query_plan(n_sessions, n_queries, ticks=ticks,
                              n_scenes=n_scenes)
        if "arena" in parts:
            _bench_arena(n_sessions, n_queries, ticks=ticks,
                         n_scenes=n_scenes)
        if "churn" in parts:
            _bench_churn(n_sessions, n_queries, ticks=ticks,
                         n_scenes=n_scenes)
        if "fused" in parts:
            _bench_fused(n_sessions, n_queries, ticks=ticks,
                         n_scenes=n_scenes, index_dtype=index_dtype)
        if "shards" in parts:
            _bench_shards(n_sessions, n_queries, ticks=ticks,
                          n_scenes=n_scenes)
        if "tiered" in parts:
            _bench_tiered(smoke=smoke)
        if "spill" in parts:
            _bench_spill(n_sessions, ticks=5 if smoke else 8,
                         n_scenes=n_scenes,
                         host_retain=32 if smoke else 64)
        if "standing" in parts:
            _bench_standing(n_sessions, smoke=smoke)
        if "incremental" in parts:
            _bench_incremental_index()
    finally:
        # the JSON artifact is written in the finally so a crashed part
        # still leaves every completed row on disk for CI to compare
        common.set_sink(None)
        if json_path:
            write_json_artifact(
                json_path, rows,
                {"bench": "multistream", "sessions": n_sessions,
                 "queries": n_queries, "smoke": smoke,
                 "parts": list(parts), "index_dtype": index_dtype,
                 "timestamp": time.time()})


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--cross", action="store_true",
                    help="the cross-session fused query benches "
                         "(query_batch_cross shim + mixed-strategy plan)")
    ap.add_argument("--arena", action="store_true",
                    help="the grow-in-place arena vs restack bench")
    ap.add_argument("--churn", action="store_true",
                    help="the session-lifecycle churn bench "
                         "(create/ingest/query/close; slot recycling + "
                         "sliding-window eviction)")
    ap.add_argument("--fused", action="store_true",
                    help="the one-launch fused retrieval bench "
                         "(fused epilogue + quantised index vs the "
                         "dense score path)")
    ap.add_argument("--shards", action="store_true",
                    help="the sharded-arena fan-out bench (1 vs K "
                         "host devices: scans/tick, candidate-gather "
                         "bytes, ingest/query overlap; K>1 arms need "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count)")
    ap.add_argument("--tiered", action="store_true",
                    help="the hierarchical consolidation-tier bench "
                         "(flat vs two-stage scanned bytes, effective "
                         "capacity, restacks==0) + the recall-vs-"
                         "compression-ratio curve from bench_fig10")
    ap.add_argument("--spill", action="store_true",
                    help="the disk spill-tier bench (host_retain-"
                         "bounded eviction='none' streams: demotion + "
                         "cold-fault + warm-read throughput; bounded "
                         "host / bit-identity / counter accounting "
                         "asserted in-harness; tmpdir-scoped)")
    ap.add_argument("--standing", action="store_true",
                    help="the standing-query bench (per-tick trigger "
                         "evaluation over the new-row slab: alerts/"
                         "tick, standing_scan_bytes vs the full-scan "
                         "bound it replaces — asserted in-harness)")
    ap.add_argument("--index-dtype", choices=("float32", "int8"),
                    default="int8",
                    help="index dtype for the fused bench's quantised "
                         "arm (default int8)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny worlds / few ticks for CI")
    ap.add_argument("--json", action="store_true",
                    help=f"also write every emitted row to {JSON_PATH}")
    args = ap.parse_args()
    parts = None
    if args.cross or args.arena or args.churn or args.fused or \
            args.shards or args.tiered or args.spill or args.standing:
        parts = (("cross", "plan") if args.cross else ()) + \
                (("arena",) if args.arena else ()) + \
                (("churn",) if args.churn else ()) + \
                (("fused",) if args.fused else ()) + \
                (("shards",) if args.shards else ()) + \
                (("tiered",) if args.tiered else ()) + \
                (("spill",) if args.spill else ()) + \
                (("standing",) if args.standing else ())
    run(args.sessions, args.queries, smoke=args.smoke, parts=parts,
        json_path=JSON_PATH if args.json else None,
        index_dtype=args.index_dtype)


if __name__ == "__main__":
    main()
