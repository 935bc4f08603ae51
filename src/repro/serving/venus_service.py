"""Multi-tenant edge box: Venus sessions feeding the serving engine.

The deployment scenario the paper targets (§II): one edge box ingests N
concurrent camera streams and answers real-time queries against any of
them with a (cloud) VLM. This module wires the session layer into the
continuous-batching engine:

  camera chunks ──ingest_tick──▶ SessionManager (per-stream memories)
  user queries  ──query_batch──▶ retrieved keyframe sets per stream
                └─▶ patch-embedded into ``Request.vision_embeds`` and
                    submitted to the ``ServingEngine`` slots.

Queries arriving in the same service tick compile to ONE query plan:
each query becomes a declarative ``QuerySpec`` and the planner groups
compatible specs (same strategy + budget class) into execution groups —
one fused similarity scan answers a whole group regardless of how many
sessions it spans, whatever the strategy mix, and the VLM answers
everything under continuous batching. The scan operand is the session
manager's grow-in-place ``MemoryArena`` (ingest ticks append into the
shared device super-buffers, queries consume them as-is), so a serving
deployment never restacks device memory between ingest and answer —
``VenusService.io_stats()["stack_rebuilds"]`` stays 0.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

from repro.core.pipeline import patchify
from repro.core.queryplan import QueryPlan, QuerySpec
from repro.core.session import SessionManager
from repro.core.standing import Alert
from repro.kernels import ops as kops
from repro.serving.engine import Request, ServingEngine


@dataclass
class StreamQuery:
    """A user query against one camera stream."""
    rid: int
    sid: int
    text: str
    prompt_tokens: np.ndarray
    query_emb: Optional[np.ndarray] = None
    budget: Optional[int] = None
    strategy: str = "akr"          # any registered retrieval strategy
    max_new_tokens: int = 12
    # filled by the service
    frame_ids: Optional[np.ndarray] = None

    def to_spec(self) -> QuerySpec:
        return QuerySpec(sid=self.sid, text=self.text,
                         embedding=self.query_emb,
                         strategy=self.strategy, budget=self.budget)


class VenusService:
    """Session manager + serving engine behind one submission API."""

    def __init__(self, manager: SessionManager, engine: ServingEngine, *,
                 max_frames: int = 4, patch: int = 8):
        self.manager = manager
        self.engine = engine
        self.max_frames = max_frames
        self.patch = patch

    # ------------------------------------------------------------- ingestion
    def create_stream(self, sid: Optional[int] = None, *,
                      eviction: Optional[str] = None) -> int:
        """Open a camera stream (recycles a freed arena slot when one
        exists). ``eviction`` picks this stream's bounded-memory policy
        ("none" | "sliding_window" | "cluster_merge" | "consolidate") —
        24/7 streams should use a window policy so they never stop
        ingesting. "consolidate" additionally folds evictees into the
        manager-wide coarse summary tier (``VenusConfig
        (coarse_capacity=...)``) so long-horizon queries keep answering
        through the two-stage coarse→fine scan after the fine window
        moved on. A stream left on "none" raises a "memory full" error
        from ``ingest_tick`` once its capacity fills."""
        return self.manager.create_session(sid, eviction=eviction)

    def close_stream(self, sid: int) -> Dict[str, int]:
        """End a camera stream: frees its arena slot for the next
        ``create_stream`` (slot recycling — zero device work, zero
        restacks; visible as ``arena_slot_releases``/``sessions_closed``
        in ``io_stats()``). Returns the stream's final ingest stats."""
        return self.manager.close_session(sid)

    def ingest_tick(self, chunks: Mapping[int, np.ndarray]
                    ) -> Dict[str, float]:
        return self.manager.ingest_tick(chunks)

    def flush(self) -> None:
        self.manager.flush()

    # --------------------------------------------------------------- serving
    def _vision_embeds(self, sid: int, frame_ids: np.ndarray) -> np.ndarray:
        """Retrieved raw frames → the VLM's prefix vision tokens."""
        cfg = self.engine.cfg
        st = self.manager[sid]
        if len(frame_ids) == 0:
            return np.zeros((cfg.vision_tokens, cfg.d_model), np.float32)
        frames = st.frames.get(frame_ids[: self.max_frames])
        pe = np.asarray(patchify(frames, self.patch, cfg.d_model))
        pe = pe.reshape(-1, cfg.d_model)[: cfg.vision_tokens]
        if pe.shape[0] < cfg.vision_tokens:
            pe = np.pad(pe, ((0, cfg.vision_tokens - pe.shape[0]), (0, 0)))
        return pe.astype(np.float32)

    def plan(self, queries: Sequence[StreamQuery]) -> QueryPlan:
        """The retrieval plan one service tick compiles to — inspectable
        before anything runs (``plan.n_scans`` == number of execution
        groups == number of fused scans)."""
        return self.manager.plan([q.to_spec() for q in queries],
                                 rids=[q.rid for q in queries])

    def submit(self, queries: Sequence[StreamQuery]) -> List[Request]:
        """Compile the tick's queries into ONE plan (the planner groups
        compatible specs; each group costs one fused cross-session scan
        no matter how many streams it spans), retrieve, build the VLM
        requests, and enqueue them on the engine in arrival order."""
        results = self.manager.execute(self.plan(queries))
        reqs: List[Request] = []
        for q, res in zip(queries, results):
            q.frame_ids = res.frame_ids
            req = Request(
                rid=q.rid, tokens=np.asarray(q.prompt_tokens, np.int32),
                max_new_tokens=q.max_new_tokens,
                vision_embeds=self._vision_embeds(q.sid, res.frame_ids))
            reqs.append(req)
            self.engine.submit(req)
        return reqs

    def answer(self, queries: Sequence[StreamQuery]) -> List[Request]:
        """Submit and drain: run engine steps until every slot is free."""
        self.submit(queries)
        return self.engine.drain()

    # ------------------------------------------------------ standing queries
    def register_standing(self, sid: int, query, *, threshold: float,
                          hysteresis: float = 0.0,
                          cooldown_ticks: int = 0,
                          priority: float = 0.0) -> int:
        """Register a persistent trigger on a stream: evaluated inside
        every ``ingest_tick`` against only that tick's newly committed
        memory rows (one extra slab-sized fused launch — see
        ``kops_standing_scan_bytes``), firing debounced ``Alert``s
        through ``poll_alerts()`` / ``on_alert`` callbacks. ``query``
        is a ``QuerySpec`` or a ``StreamQuery`` (converted via
        ``to_spec``); returns the spec id for
        ``manager.unregister_standing``."""
        spec = query.to_spec() if isinstance(query, StreamQuery) else query
        return self.manager.register_standing(
            sid, spec, threshold=threshold, hysteresis=hysteresis,
            cooldown_ticks=cooldown_ticks, priority=priority)

    def poll_alerts(self, max_alerts: Optional[int] = None
                    ) -> List[Alert]:
        """Drain pending standing-query alerts, priority-ordered
        (priority desc, then score desc, then tick/firing order) —
        the pull half of the delivery surface."""
        return self.manager.poll_alerts(max_alerts)

    def on_alert(self, callback) -> None:
        """Push half of the delivery surface: ``callback(alert)`` runs
        once per fired alert, in priority order within each ingest
        tick, immediately after the tick's standing evaluation. Alerts
        remain pollable regardless — callbacks observe the stream,
        ``poll_alerts`` drains it."""
        self.manager.standing.on_alert(callback)

    # ------------------------------------------------------------ monitoring
    def io_stats(self) -> Dict[str, int]:
        """One monitoring surface over the whole service: the manager's
        scan/restack/lifecycle counters, the arena's
        grow/append/slot-recycling counters (``arena_*``), and the
        per-memory transfer/eviction counters summed over live AND
        closed sessions (``mem_*`` — the manager folds a closing
        stream's counters into ``closed_mem_stats``, so the sums stay
        monotonic across churn). The production invariants to alert on:
        ``stack_rebuilds == 0`` (arena mode), ``mem_full_uploads`` flat
        after warm-up, and ``arena_grows`` flat under churn (slot
        recycling — churned streams must reuse slots, not grow the
        arena). For 24/7 streams, ``mem_evicted_rows`` rising at the
        ingest rate is HEALTHY steady-state; see the counter glossary in
        ARCHITECTURE.md.

        The ``kops_*`` counters come from the kernel dispatch layer
        (``repro.kernels.ops.scan_counts`` — process-global, shared by
        every manager in the process): ``kops_scan_bytes`` is the index
        bytes streamed by all similarity scans (int8 indices count 1
        byte/element — the quantisation lever),
        ``kops_fused_draw_launches`` counts scans resolved in the fused
        epilogue (no dense score tensor), ``kops_dense_score_launches``
        counts scans that DID materialise (S, Q, cap) scores (the
        BOLT/MDF/AKS fallback and legacy ``search`` calls).

        Hierarchical-tier deployments (``eviction="consolidate"``) add
        the two-stage counters: ``kops_coarse_scan_bytes`` (the subset
        of ``kops_scan_bytes`` streamed by stage-1 scans over the
        summary tier), ``kops_fine_gather_rows`` (candidate fine rows
        gathered into stage-2 operands), ``kops_two_stage_scans`` /
        ``two_stage_groups`` (kernel- and plan-level counts of
        completed coarse→fine retrievals), and ``mem_consolidated_rows``
        / ``arena_coarse_appends`` (evictees folded into summary rows,
        and the deferred scatters that pushed them to the device tier).
        The bandwidth invariant to alert on: per query group,
        ``kops_coarse_scan_bytes`` plus the gathered candidate bytes
        stay below one flat capacity×dim scan.

        Sharded deployments additionally surface ``arena_shards`` (the
        mesh ``model``-axis size the arena slot axis is slabbed over),
        ``sharded_group_scans`` (plan-level launches that fanned out
        under shard_map), ``kops_sharded_stack_launches`` (kernel-level
        count of the same), and ``kops_shard_gather_bytes`` — the bytes
        of per-shard scan OUTPUTS crossing shard boundaries at the
        candidate gather: O(S·Q·(T+K)) fused, no O(S·Q·capacity) term,
        which is the whole point of scanning shard-locally;
        ``shard_gather_bytes`` is the same count for this manager's
        groups alone.
        ``archive_trimmed_frames`` counts host frames the bounded
        ``FrameStore`` dropped below the live eviction windows.

        Spill-tier deployments (``VenusConfig(spill_dir=...)``) add the
        storage-tier counters, summed over live AND closed sessions
        (closes fold into ``closed_frame_stats`` like the ``mem_*``
        sums): ``spilled_frames`` / ``spilled_bytes`` (demotions the
        host tier wrote to disk segments), ``spill_faults`` (segment
        loads a ``get`` of a spilled id paid), ``spill_cache_hits``
        (spilled reads served from the LRU segment cache), and the
        gauge ``spill_disk_bytes`` (bytes currently in live sessions'
        segment files — returns to baseline when streams close, which
        is the disk-leak invariant to alert on).

        Standing-query deployments add ``standing_specs`` (gauge: live
        registered specs), ``alerts_fired`` / ``alerts_suppressed``
        (debounced trigger outcomes, from the manager counters), and
        ``kops_standing_scan_bytes`` — the index bytes streamed by the
        per-tick new-row slab launches. The invariant to alert on:
        ``kops_standing_scan_bytes`` grows O(new_rows · dim) per tick,
        NEVER O(capacity · dim) — standing evaluation must ride the
        ingest path, not re-scan history."""
        out: Dict[str, int] = dict(self.manager.io_stats)
        out["standing_specs"] = self.manager.standing.n_specs
        for k, v in kops.scan_counts().items():
            out[f"kops_{k}"] = v
        if self.manager.arena is not None:
            for k, v in self.manager.arena.io_stats.items():
                out[f"arena_{k}"] = v
            out["arena_shards"] = self.manager.arena.n_shards
        mem_sums = dict(self.manager.closed_mem_stats)
        for st in self.manager.sessions.values():
            for k, v in st.memory.io_stats.items():
                mem_sums[k] = mem_sums.get(k, 0) + v
        for k, v in mem_sums.items():
            out[f"mem_{k}"] = v
        frame_sums = dict(self.manager.closed_frame_stats)
        disk_bytes = 0
        for st in self.manager.sessions.values():
            for k, v in st.frames.io_stats.items():
                frame_sums[k] = frame_sums.get(k, 0) + v
            disk_bytes += st.frames.disk_bytes
        out.update(frame_sums)
        out["spill_disk_bytes"] = disk_bytes
        return out
