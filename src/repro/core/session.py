"""Session layer: multi-stream, batch-first Venus (paper Fig. 6 at scale).

The monolithic single-stream system is decomposed into composable
per-stream stages operating on a ``SessionState``:

* ``segment_stage``   — chunk → closed scene partitions (①),
* ``cluster_stage``   — one closed partition → an ``EmbedJob`` holding
  its centroid index frames + cluster membership (②–③),
* ``commit_jobs``     — ALL embed jobs closed in one tick, across every
  session, concatenated into a SINGLE jit'd MEM call, then scattered
  into each session's device-resident memory with batched appends (④).

``SessionManager`` owns N concurrent streams (the edge box's cameras)
and drives the stages. By default every session's memory lives inside
one shared ``MemoryArena`` — device-resident ``(S, capacity, …)``
super-buffers that tick appends extend in place with donated writes, so
the fused query path scans the arena buffers directly and NO
ingest↔query interleaving ever restacks anything
(``io_stats["stack_rebuilds"]`` stays 0; ``use_arena=False`` restores
the PR-2 detached memories + version-cached ``MemoryStack`` path).
Querying is declarative: ``plan(specs)`` groups ``QuerySpec``s into
execution groups and ``execute(plan)`` runs ONE fused similarity scan
per group over the arena (or stack) views plus vmapped per-strategy
post-processing (``repro.core.queryplan``). The legacy entry points —
``query``, ``query_batch``, ``query_batch_cross``, ``query_topk`` — are
thin shims over plan/execute and stay draw-for-draw identical to their
pre-redesign outputs (same per-session PRNG chains).

Sessions have a full LIFECYCLE (ARCHITECTURE.md draws the state
machine): ``create_session`` → ingest ⇄ query → (at capacity, with a
window ``EvictionPolicy``) evict ⇄ ingest/query → ``close_session`` →
slot reuse. Closing frees the session's arena slot into a free-list —
its lane scans as masked-out padding, no restack — and the next
``create_session`` recycles it after one donated device-side row
reset, so 24/7 churn holds the arena at its steady-state slot count.
Ownership here: the SessionManager owns the arena (and the embedder /
jit caches); each ``SessionState`` owns its host mirrors, PRNG chain,
segmenter, and raw-frame archive — which is why a closed session's
memory handle stays readable after detach while its device rows are
recycled under a new tenant.
"""

from __future__ import annotations

import contextlib
import os
import weakref
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aux_models import AuxModel, build_aux_prompt
from repro.core.clustering import cluster_partition, frame_vectors
from repro.core.memory import (ArenaStackView, FrameStore, MemoryArena,
                               MemoryStack, VenusMemory)
from repro.core.queryplan import (QueryPlan, QueryResult, QuerySpec,
                                  build_plan, execute_plan)
from repro.core.scene import Partition, StreamSegmenter, segment_streams
from repro.core.standing import Alert, StandingRegistry
from repro.launch.mesh import make_memory_mesh
from repro.launch.sharding import mesh_axis_size
from repro.obs import span

# live managers, so test harnesses can reset every launch/transfer
# counter between tests without threading references around
# (tests/conftest.py) — weak so managers die with their tests
_LIVE_MANAGERS: "weakref.WeakSet" = weakref.WeakSet()


def reset_all_io_stats() -> None:
    """Reset the io_stats of every live ``SessionManager`` (and their
    memories/arena). Test-isolation hook: launch-count assertions must
    not depend on which tests ran before them."""
    for mgr in list(_LIVE_MANAGERS):
        mgr.reset_io_stats()


@dataclass(frozen=True)
class VenusConfig:
    # ingestion
    scene_threshold: float = 0.075
    max_partition_len: int = 256
    cluster_threshold: float = 0.35
    max_clusters_per_partition: int = 16
    cluster_pool: int = 8
    # memory
    memory_capacity: int = 8192
    member_cap: int = 128
    # index storage dtype: "float32", or "int8" for the quantised index
    # (symmetric per-row int8 + f32 scales, quantised once at the append
    # scatter; scans stream 4× fewer bytes — see ARCHITECTURE.md)
    index_dtype: str = "float32"
    # lifecycle: what a session does when it outlives memory_capacity —
    # "none" (overflow raises; the pre-lifecycle contract),
    # "sliding_window" (device-side ring: evict the oldest rows, O(1)
    # head motion), "cluster_merge" (sliding window that first folds
    # evicted member reservoirs into similar surviving clusters), or
    # "consolidate" (evictees fold into the hierarchical coarse tier's
    # compressed summary rows — requires coarse_capacity > 0)
    eviction: str = "none"
    # cosine threshold for cluster_merge/consolidate folds: an evictee
    # joins its most similar survivor/summary only at >= this similarity
    # (None = the policy default, 0.8); validated in (0, 1] by
    # get_eviction_policy
    merge_threshold: Optional[float] = None
    # hierarchical two-level memory (ARCHITECTURE.md "Hierarchical
    # consolidation tier"): coarse_capacity > 0 gives every arena slot a
    # summary tier of ceil(capacity / coarse_block) block centroids plus
    # coarse_capacity consolidated rows; once consolidation populates
    # it, fused queries run the two-stage coarse-scan → winner-gather
    # path, streaming ~n_coarse + coarse_topb·coarse_block rows per
    # query instead of the full capacity
    coarse_capacity: int = 0
    coarse_block: int = 64
    coarse_topb: int = 4
    # disk spill tier (ARCHITECTURE.md "Storage tiers"): spill_dir set
    # turns FrameStore.trim into a DEMOTION — dropped host frames are
    # written to append-only npy segment files under
    # spill_dir/session-<sid>/ and get() faults them back through an
    # LRU segment cache, so every historical absolute id stays readable
    # (the paper's NVMe archive tier). host_retain additionally bounds
    # the HOST tier: _trim_archives demotes frames beyond the newest
    # host_retain even for eviction="none" sessions (closing their 24/7
    # RSS leak without breaking the keep-everything contract — the
    # history moves to disk instead of growing RSS forever).
    spill_dir: Optional[str] = None
    spill_segment_frames: int = 64
    spill_cache_segments: int = 4
    host_retain: Optional[int] = None
    # how many chips the arena's slot axis spans: above 1 the manager
    # builds make_memory_mesh(memory_shards) and the arena holds its
    # slots in that many contiguous slabs, one per chip (ARCHITECTURE.md
    # "Sharded arena"); 1 keeps everything on the default device
    memory_shards: int = 1
    # querying (Eq. 5-7)
    tau: float = 0.1
    theta: float = 0.9
    beta: float = 1.0
    n_max: int = 32
    seed: int = 0

    def __post_init__(self):
        if self.memory_shards < 1:
            raise ValueError(
                f"memory_shards must be >= 1, got {self.memory_shards}")
        if self.spill_segment_frames < 1:
            raise ValueError(
                f"spill_segment_frames must be >= 1, got "
                f"{self.spill_segment_frames}")
        if self.spill_cache_segments < 1:
            raise ValueError(
                f"spill_cache_segments must be >= 1, got "
                f"{self.spill_cache_segments}")
        if self.host_retain is not None:
            if self.spill_dir is None:
                raise ValueError(
                    "host_retain bounds the HOST tier by demoting cold "
                    "frames to disk — it requires spill_dir to be set "
                    "(without a spill tier, demotion would be deletion "
                    "and break the keep-everything contract)")
            if self.host_retain < 1:
                raise ValueError(
                    f"host_retain must be >= 1, got {self.host_retain}")


@dataclass
class EmbedJob:
    """One closed partition's centroid frames awaiting MEM embedding."""
    sid: int
    scene_id: int
    frames: np.ndarray                       # (n, H, W, 3) index frames
    frame_ids: np.ndarray                    # (n,) absolute frame ids
    member_lists: List[np.ndarray]           # per-cluster member frame ids
    aux_texts: Optional[List[str]]


class SessionState:
    """Per-stream state: segmenter, pending buffer, archive, memory."""

    def __init__(self, sid: int, cfg: VenusConfig, embed_dim: int,
                 arena: Optional[MemoryArena] = None,
                 slot: Optional[int] = None,
                 eviction: Optional[str] = None):
        self.sid = sid
        self.cfg = cfg
        self.segmenter = StreamSegmenter(
            threshold=cfg.scene_threshold,
            max_partition_len=cfg.max_partition_len)
        self.memory = VenusMemory(cfg.memory_capacity, embed_dim,
                                  cfg.member_cap, seed=cfg.seed,
                                  arena=arena, slot=slot,
                                  eviction=(cfg.eviction if eviction
                                            is None else eviction),
                                  index_dtype=cfg.index_dtype,
                                  merge_threshold=cfg.merge_threshold,
                                  coarse_capacity=cfg.coarse_capacity,
                                  coarse_block=cfg.coarse_block)
        spill = (None if cfg.spill_dir is None
                 else os.path.join(cfg.spill_dir, f"session-{sid:05d}"))
        self.frames = FrameStore(
            spill, segment_frames=cfg.spill_segment_frames,
            cache_segments=cfg.spill_cache_segments)
        self.pending: List[np.ndarray] = []   # frames not yet clustered
        self.pending_base = 0                 # abs index of pending[0]
        self.key = jax.random.key(cfg.seed)
        self.stats = {"frames_seen": 0, "frames_embedded": 0,
                      "partitions": 0, "clusters": 0,
                      "frames_trimmed": 0}

    def next_keys(self, n: int) -> jnp.ndarray:
        """Advance the session PRNG chain n steps — the same chain a
        sequence of n single queries would consume, so batched and
        sequential querying draw identical subkeys."""
        subs = []
        for _ in range(n):
            self.key, sub = jax.random.split(self.key)
            subs.append(sub)
        return jnp.stack(subs)


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------


def segment_stage(states: Sequence[SessionState],
                  chunks: Sequence[np.ndarray],
                  io_stats: Optional[Dict[str, int]] = None
                  ) -> List[List[Partition]]:
    """① scene segmentation of one tick: archive each stream's chunk,
    score every chunk in one batched pass (``segment_streams``), return
    each stream's closed partitions."""
    chunks = [np.asarray(c, np.float32) for c in chunks]
    for st, chunk in zip(states, chunks):
        with span("ingest.segment.archive"):
            st.frames.append(chunk)
        st.stats["frames_seen"] += len(chunk)
    with span("ingest.segment.scores", streams=len(states)):
        closed = segment_streams([st.segmenter for st in states], chunks,
                                 io_stats)
    for st, chunk in zip(states, chunks):
        st.pending.extend(chunk)
    return closed


def cluster_stage(state: SessionState, part: Partition,
                  aux_models: Sequence[AuxModel] = (),
                  annotation_fn=None) -> EmbedJob:
    """②–③ incremental clustering of one closed partition → embed job."""
    cfg = state.cfg
    lo = part.start - state.pending_base
    hi = part.end - state.pending_base
    pframes = np.stack(state.pending[lo:hi])
    vecs = frame_vectors(jnp.asarray(pframes), cfg.cluster_pool)
    res = cluster_partition(vecs, threshold=cfg.cluster_threshold,
                            max_clusters=cfg.max_clusters_per_partition)
    n = int(res.n_clusters)
    assign = np.asarray(res.assignments)
    index_local = np.asarray(res.index_frames)[:n]
    scene_id = state.stats["partitions"]
    members = [part.start + np.nonzero(assign == c)[0] for c in range(n)]
    aux_texts = None
    if aux_models and annotation_fn is not None:
        aux_texts = [build_aux_prompt(
            aux_models, pframes[int(index_local[j])],
            annotation_fn(part.start + int(index_local[j])))
            for j in range(n)]
    state.stats["partitions"] += 1
    state.stats["clusters"] += n
    return EmbedJob(sid=state.sid, scene_id=scene_id,
                    frames=pframes[index_local],
                    frame_ids=part.start + index_local,
                    member_lists=members, aux_texts=aux_texts)


def release_pending(state: SessionState, closed: List[Partition]) -> None:
    if closed:
        consumed = closed[-1].end - state.pending_base
        state.pending = state.pending[consumed:]
        state.pending_base = closed[-1].end


def commit_jobs(sessions: Mapping[int, SessionState], embedder,
                jobs: Sequence[EmbedJob], *,
                standing: Optional[StandingRegistry] = None,
                io_stats: Optional[Dict[str, int]] = None) -> int:
    """④ ONE batched MEM call over every index frame closed this tick,
    scattered into each owning session's memory with batched appends.
    Arena-backed sessions defer their device writes into the tick's
    fused scatter (one donated program per super-buffer per tick, no
    matter how many sessions closed clusters). This is also where the
    eviction hook fires: a session at ``memory_capacity`` consults its
    ``EvictionPolicy`` inside ``insert_batch`` — a sliding-window
    session sheds exactly as many oldest rows as the tick closed (O(1)
    head motion; the new rows overwrite the evicted positions within
    the same deferred scatter), so a 24/7 stream ingests forever in
    constant DEVICE memory. The raw-frame ``FrameStore`` (the paper's
    NVMe archive layer) is bounded separately: after the tick's commits
    the manager trims every host frame below the session's live
    references — see ``SessionManager._trim_archives``.

    ``standing`` hooks the standing-query registry into the tick: the
    physical slots every ``insert_batch`` returns are collected per
    session and — after the deferred scatters flush — evaluated with
    ONE extra fused launch over only those new rows (never a
    full-capacity re-scan; see ``repro.core.standing``). Fired alerts
    land in the registry's priority queue; counters bump in
    ``io_stats``."""
    if not jobs:
        return 0
    with span("ingest.embed"):
        # fail fast on eviction="none" sessions about to overflow: raising
        # here — before the embed call and the deferred scatter — names the
        # session and the fix, instead of a deep-in-scatter shape error
        # after embedding work is already spent
        incoming: Dict[int, int] = {}
        for j in jobs:
            incoming[j.sid] = incoming.get(j.sid, 0) + len(j.frame_ids)
        for sid, n_new in incoming.items():
            mem = sessions[sid].memory
            if mem.eviction.name == "none" and mem.size + n_new > mem.capacity:
                raise RuntimeError(
                    f"session {sid}: memory full ({mem.size} rows + {n_new} "
                    f"incoming > capacity {mem.capacity}) — enable eviction "
                    f"or consolidation (VenusConfig(eviction='sliding_window'"
                    f" | 'cluster_merge' | 'consolidate'))")
        frames = np.concatenate([j.frames for j in jobs])
        ids = np.concatenate([j.frame_ids for j in jobs])
        aux = None
        if any(j.aux_texts for j in jobs):
            aux = []
            for j in jobs:
                aux.extend(j.aux_texts or [""] * len(j.frame_ids))
        embs = embedder.embed_frames(frames, aux, frame_ids=ids)
    arenas = {id(a): a for a in
              (sessions[j.sid].memory.arena for j in jobs)
              if a is not None}
    new_by_sid: Dict[int, List[np.ndarray]] = {}
    with span("ingest.insert"), contextlib.ExitStack() as stack:
        for a in arenas.values():
            stack.enter_context(a.deferred_appends())
        off = 0
        for j in jobs:
            n = len(j.frame_ids)
            st = sessions[j.sid]
            phys = st.memory.insert_batch(
                embs[off:off + n], scene_ids=[j.scene_id] * n,
                index_frames=j.frame_ids, member_lists=j.member_lists)
            new_by_sid.setdefault(j.sid, []).append(phys)
            st.stats["frames_embedded"] += n
            off += n
    if standing is not None:
        with span("ingest.standing"):
            standing.evaluate(sessions, new_by_sid, io_stats)
    return len(ids)


# ---------------------------------------------------------------------------
# Session manager
# ---------------------------------------------------------------------------


class SessionManager:
    """N concurrent streams sharing one embedder and one jit cache."""

    def __init__(self, cfg: VenusConfig, embedder, embed_dim: int,
                 aux_models: Sequence[AuxModel] = (), annotation_fn=None,
                 *, use_arena: bool = True, mesh=None,
                 double_buffer: Optional[bool] = None):
        self.cfg = cfg
        self.embedder = embedder
        self.embed_dim = embed_dim
        self.aux_models = list(aux_models)
        self.annotation_fn = annotation_fn
        self.sessions: Dict[int, SessionState] = {}
        self._next_sid = 0
        self._stacks: Dict[Tuple[int, ...], MemoryStack] = {}
        # grow-in-place arena (default): sessions allocate their device
        # rows inside shared (S, capacity, …) super-buffers, so queries
        # never restack grown sessions. use_arena=False restores the
        # PR-2 detached memories + version-cached MemoryStack path.
        # mesh= shards the arena's slot axis over the mesh's "model"
        # axis (slabs of contiguous slots per device; the fused scans
        # fan out per shard under shard_map). cfg.memory_shards > 1
        # builds that mesh here; a mesh passed with it must agree, and
        # with memory_shards == 1 a passed mesh (the tests' host meshes)
        # decides alone. double_buffer defaults on whenever a mesh is
        # given — ingest scatters target the back buffer set so they
        # overlap the fused query launches — and can be forced either
        # way explicitly.
        self.use_arena = use_arena
        if cfg.memory_shards > 1:
            if mesh is None:
                mesh = make_memory_mesh(cfg.memory_shards)
            elif mesh_axis_size(mesh, "model") != cfg.memory_shards:
                raise ValueError(
                    f"memory_shards={cfg.memory_shards} but the mesh "
                    f"passed has {mesh_axis_size(mesh, 'model')} shards "
                    f"on its 'model' axis")
        self.mesh = mesh
        self.double_buffer = ((mesh is not None) if double_buffer is None
                              else double_buffer)
        self.arena: Optional[MemoryArena] = None
        # per-session scans vs fused cross-session scans, for the "one
        # scan per query tick" invariant (tests/benches assert on these);
        # group_scans counts every executor launch regardless of S;
        # stack_rebuilds counts device-side restacks of session buffers
        # (MUST stay 0 in arena mode — the zero-restack invariant);
        # sharded_group_scans counts launches that fanned out per slab
        # and shard_gather_bytes the epilogue bytes they stitched back
        # from the slabs (kops' shard_gather_bytes, per manager);
        # scene_score_launches counts segmentation launches (one per
        # chunk shape in a tick) and scene_score_streams the streams
        # they scored
        self.io_stats = {"scans": 0, "fused_scans": 0,
                         "device_expands": 0, "group_scans": 0,
                         "stack_rebuilds": 0, "sessions_closed": 0,
                         "sharded_group_scans": 0,
                         "shard_gather_bytes": 0,
                         "two_stage_groups": 0,
                         "archive_trimmed_frames": 0,
                         "alerts_fired": 0, "alerts_suppressed": 0,
                         "scene_score_launches": 0,
                         "scene_score_streams": 0}
        # standing queries: persistent per-session QuerySpecs evaluated
        # inside commit_jobs against each tick's newly committed rows
        # (one extra slab launch per tick — see repro.core.standing)
        self.standing = StandingRegistry(cfg)
        # summed io_stats of closed sessions' memories: keeps the
        # service-level mem_* monitoring counters monotonic across
        # stream closes (a popped session takes its live dict with it)
        self.closed_mem_stats: Dict[str, int] = {}
        # same treatment for closed sessions' FrameStore spill counters
        # (close_session deletes the store's segments, so the counters
        # must be folded here first to stay monotonic)
        self.closed_frame_stats: Dict[str, int] = {}
        self._arena_stack: Optional[ArenaStackView] = None
        # plans made so far: a plan's tick number joins its spans
        self.query_ticks = 0
        _LIVE_MANAGERS.add(self)

    def reset_io_stats(self, *, include_memories: bool = True) -> None:
        """Zero the scan counters (dict identity preserved) and, by
        default, every session memory's (and the arena's) transfer
        counters too — so benchmarks/tests can assert per-phase counts
        without rebuilding the manager."""
        for k in self.io_stats:
            self.io_stats[k] = 0
        if include_memories:
            self.closed_mem_stats.clear()
            self.closed_frame_stats.clear()
            for st in self.sessions.values():
                st.memory.reset_io_stats()
                st.frames.reset_io_stats()
            if self.arena is not None:
                self.arena.reset_io_stats()

    # ------------------------------------------------------------- lifecycle
    #
    # A session's memory walks one state machine (ARCHITECTURE.md):
    #   create → ingest ⇄ query → [evict ⇄ ingest/query] → close → reuse
    # ``create_session`` allocates — or, after a ``close_session``,
    # RECYCLES — an arena slot; ``close_session`` frees the slot into
    # the arena free-list (its lane scans as masked-out padding, so no
    # restack ever happens while holes exist); eviction runs inside
    # ``commit_jobs`` via each memory's ``EvictionPolicy``.

    def create_session(self, sid: Optional[int] = None, *,
                       eviction: Optional[str] = None) -> int:
        """Open a stream. Arena mode allocates a slot — reusing a freed
        one (a single donated device-side row reset, no growth) when the
        free-list is non-empty. ``eviction`` overrides ``cfg.eviction``
        for this session only (e.g. one 24/7 stream among bounded
        ones)."""
        if sid is None:
            sid = self._next_sid
        assert sid not in self.sessions, sid
        self._next_sid = max(self._next_sid, sid) + 1
        arena = slot = None
        if self.use_arena:
            if self.arena is None:
                self.arena = MemoryArena(
                    self.cfg.memory_capacity, self.embed_dim,
                    self.cfg.member_cap,
                    index_dtype=self.cfg.index_dtype, mesh=self.mesh,
                    double_buffer=self.double_buffer,
                    coarse_capacity=self.cfg.coarse_capacity,
                    coarse_block=self.cfg.coarse_block)
            arena, slot = self.arena, self.arena.add_session()
        self.sessions[sid] = SessionState(sid, self.cfg, self.embed_dim,
                                          arena=arena, slot=slot,
                                          eviction=eviction)
        return sid

    def close_session(self, sid: int) -> Dict[str, int]:
        """End a stream and free its memory slot for reuse.

        The session's arena slot goes onto the free-list — its lane
        reads window ``(0, 0)`` and scans as masked-out padding, so
        closing costs no device work and triggers no restack — and the
        NEXT ``create_session`` recycles it after one donated row
        reset. The popped session's memory is detached from the arena
        first, so any handle the caller still holds reads the session's
        own host mirrors instead of rows that are about to be recycled.
        Frame storage is released on BOTH tiers: the host ``FrameStore``
        is dropped and its spill segment files are deleted, so a churn
        workload leaks neither RSS nor disk (the store's spill counters
        are folded into ``closed_frame_stats`` first, keeping the
        service-level sums monotonic). Returns the session's final
        ingest stats."""
        st = self.sessions.pop(sid)
        for k, v in st.memory.io_stats.items():
            self.closed_mem_stats[k] = self.closed_mem_stats.get(k, 0) + v
        for k, v in st.frames.io_stats.items():
            self.closed_frame_stats[k] = (
                self.closed_frame_stats.get(k, 0) + v)
        st.frames.close()
        # drop the session's standing specs: a recycled slot's next
        # tenant must not inherit the old tenant's triggers (already
        # fired alerts stay pollable — they reference history, which
        # outlives the stream)
        self.standing.drop_session(sid)
        self._stacks = {k: v for k, v in self._stacks.items()
                        if sid not in k}
        if self.arena is not None:
            slot = st.memory.slot
            st.memory.detach_from_arena()
            self.arena.release_slot(slot)
        self.io_stats["sessions_closed"] += 1
        return dict(st.stats)

    def __getitem__(self, sid: int) -> SessionState:
        return self.sessions[sid]

    def __len__(self) -> int:
        return len(self.sessions)

    # ------------------------------------------------------------- ingestion
    def ingest_tick(self, chunks: Mapping[int, np.ndarray]
                    ) -> Dict[str, float]:
        """Consume one chunk per stream; embed everything that closed
        across ALL streams in one batched MEM call. Returns the tick's
        stage times in seconds, read off its spans: ``segment``
        (``venus.ingest.segment``), ``cluster`` (``venus.ingest.cluster``)
        and ``embed_insert`` (from the end of clustering to the end of
        ``venus.ingest.trim``: embedding, the arena scatter, standing
        queries and the archive trim), and of it ``trim``
        (``venus.ingest.trim``); and the keyframes ``embedded`` and
        archive frames ``trimmed``."""
        with span("ingest_tick", streams=len(chunks),
                  frames=sum(len(c) for c in chunks.values())):
            with span("ingest.segment") as seg:
                closed_by_sid = dict(zip(chunks, segment_stage(
                    [self.sessions[sid] for sid in chunks],
                    list(chunks.values()), self.io_stats)))
            jobs: List[EmbedJob] = []
            with span("ingest.cluster") as clu:
                for sid, closed in closed_by_sid.items():
                    st = self.sessions[sid]
                    for part in closed:
                        jobs.append(cluster_stage(st, part, self.aux_models,
                                                  self.annotation_fn))
                    release_pending(st, closed)
            n_emb = commit_jobs(self.sessions, self.embedder, jobs,
                                standing=self.standing,
                                io_stats=self.io_stats)
            with span("ingest.trim") as trim:
                n_trim = self._trim_archives(chunks.keys())
        return {"segment": seg.seconds, "cluster": clu.seconds,
                "embed_insert": trim.end - clu.end, "trim": trim.seconds,
                "embedded": float(n_emb), "trimmed": float(n_trim)}

    def flush(self, sids: Optional[Sequence[int]] = None) -> None:
        """Close every open partition and embed the remainder batched."""
        jobs: List[EmbedJob] = []
        sids = list(sids if sids is not None else self.sessions)
        for sid in sids:
            st = self.sessions[sid]
            for part in st.segmenter.flush():
                jobs.append(cluster_stage(st, part, self.aux_models,
                                          self.annotation_fn))
            st.pending = []
            st.pending_base = st.stats["frames_seen"]
        commit_jobs(self.sessions, self.embedder, jobs,
                    standing=self.standing, io_stats=self.io_stats)
        self._trim_archives(sids)

    def _trim_archives(self, sids) -> int:
        """Bound the raw-frame archive: after a tick's commits, drop
        every host frame BELOW all of a session's live references —
        the min over (a) index_frame ids and count-masked member
        reservoirs of the rows inside the current ring window (so
        ``cluster_merge``'s folded members keep their evicted frames
        reachable and retained) and (b) ``pending_base`` (frames not
        yet clustered).

        Without a spill tier, only sessions with a window eviction
        policy trim — under ``eviction="none"`` nothing ever leaves the
        window, so the historical keep-everything archive contract is
        untouched — and the ``uniform`` query strategy (which draws
        arbitrary archive ids) is incompatible with window-evicting
        sessions: ``build_plan`` rejects that combination up front and
        trimmed ids fail fast in ``FrameStore.get`` rather than
        silently aliasing.

        With ``VenusConfig(spill_dir=...)`` the trim is a DEMOTION —
        dropped frames move to npy segments and fault back through
        ``get`` — which changes the policy in two ways: (1)
        ``host_retain`` bounds the host tier even for
        ``eviction="none"`` sessions (their cold frames demote instead
        of growing RSS forever; every id stays readable, so the
        keep-everything contract holds at the *store* level), and (2)
        window-evicting sessions may demote beyond the live-reference
        horizon too (a faulted read is legal now), so ``uniform`` and
        ``cluster_merge``'s folded-reservoir reads succeed from disk.
        Demoting below ``pending_base`` is safe with spill on: frames
        awaiting clustering are duplicated in ``SessionState.pending``,
        which is what ``cluster_stage`` reads. Each session's store is
        ``sync()``'d here — the tick boundary is the fsync/durability
        point for that tick's demotions."""
        trimmed = 0
        retain = self.cfg.host_retain
        for sid in sids:
            st = self.sessions[sid]
            fs = st.frames
            spill = fs.spill_enabled
            if st.memory.eviction.name == "none":
                if not (spill and retain is not None):
                    continue
                keep = len(fs) - retain
            else:
                keep = min(st.memory.min_live_frame(), st.pending_base)
                if spill and retain is not None:
                    keep = max(keep, len(fs) - retain)
            n = fs.trim(keep)
            if spill:
                fs.sync()
            if n:
                st.stats["frames_trimmed"] += n
                trimmed += n
        self.io_stats["archive_trimmed_frames"] += trimmed
        return trimmed

    # -------------------------------------------------------------- querying
    #
    # The declarative plan/execute pair is the ONE query path; everything
    # below it is a thin shim kept for API compatibility. All shims
    # preserve the per-session PRNG chains draw-for-draw (see
    # tests/test_crosssession.py + tests/test_queryplan.py).

    def plan(self, specs: Sequence[QuerySpec], *,
             rids: Sequence[int] = ()) -> QueryPlan:
        """Group specs into execution groups (one fused scan each).
        Passing the live sessions lets the planner reject plans that
        could only fail deep in execution (e.g. ``uniform`` against a
        window-evicting session with no spill tier). Each plan is one
        query tick: it takes the manager's next tick number, which its
        ``venus.plan`` and ``venus.execute`` spans carry, with the
        request ids ``rids`` of its specs when the caller has them."""
        self.query_ticks += 1
        with span("plan", tick=self.query_ticks):
            plan = build_plan(specs, self.cfg, sessions=self.sessions)
        plan.tick, plan.rids = self.query_ticks, tuple(rids)
        return plan

    def execute(self, plan: QueryPlan, *, fused: bool = True,
                coarse: bool = True) -> List[QueryResult]:
        """Run a plan: ONE scan launch per group. ``fused=True`` (the
        default) resolves sampling/AKR/top-k groups inside the launch —
        draws and top-k come back instead of dense scores; strategies
        that genuinely need the (S, Q, cap) score tensor (BOLT/MDF/AKS,
        plus uniform) fall back to the dense scan per group regardless.
        ``fused=False`` forces the dense path for everything (debugging /
        A-B measurement escape hatch; results are draw-for-draw
        identical either way). ``coarse=False`` disables the two-stage
        coarse-tier path even when the arena holds consolidated summary
        rows (the flat-scan escape hatch — bit-identical to a build
        without a coarse tier)."""
        with span("execute", tick=plan.tick,
                  rids=";".join(map(str, plan.rids))):
            return execute_plan(self, plan, fused=fused, coarse=coarse)

    def query_specs(self, specs: Sequence[QuerySpec]) -> List[QueryResult]:
        """Convenience: ``execute(plan(specs))``."""
        return self.execute(self.plan(specs))

    # ------------------------------------------------------ standing queries
    #
    # The inverted loop: instead of ask-then-scan, a spec registered
    # here is evaluated inside every ingest tick's ``commit_jobs``
    # against ONLY that tick's newly committed rows (one extra fused
    # launch over the (G, max_new, d) new-row slab — never a
    # full-capacity re-scan; ``kops standing_scan_bytes`` pins it) and
    # fires ``Alert`` records through threshold + hysteresis + cooldown
    # debouncing. See repro.core.standing for the trigger semantics.

    def register_standing(self, sid: int, spec: QuerySpec, *,
                          threshold: float, hysteresis: float = 0.0,
                          cooldown_ticks: int = 0,
                          priority: float = 0.0) -> int:
        """Register a persistent query on ``sid``; returns its spec id.

        ``spec`` is validated through ``build_plan(standing=True)``
        (deterministic fused strategy — ``topk`` — and no explicit
        seed; budget/tau resolve exactly as an ad-hoc plan would, which
        is what makes standing scores bitwise comparable to ad-hoc
        ones). ``threshold`` is a raw cosine-similarity level (the
        fused scan's top-k scores); an alert fires when the best new
        row reaches it, then the spec re-arms only after the score
        falls to ``threshold - hysteresis`` and ``cooldown_ticks``
        committing ticks have drained. ``priority`` orders delivery in
        ``poll_alerts``. Text specs are embedded once, here."""
        assert sid in self.sessions, sid
        emb = spec.embedding
        if emb is None:
            emb = np.asarray(
                self.embedder.embed_queries([spec.text])[0], np.float32)
        return self.standing.register(
            sid, spec, emb, threshold=threshold, hysteresis=hysteresis,
            cooldown_ticks=cooldown_ticks, priority=priority,
            sessions=self.sessions)

    def unregister_standing(self, spec_id: int) -> None:
        """Remove one standing spec (already fired alerts stay
        pollable)."""
        self.standing.unregister(spec_id)

    def poll_alerts(self, max_alerts: Optional[int] = None
                    ) -> List[Alert]:
        """Drain pending standing-query alerts, priority-ordered
        (priority desc, score desc, tick, firing order)."""
        return self.standing.poll_alerts(max_alerts)

    @staticmethod
    def _legacy_strategy(budget: Optional[int], use_akr: bool) -> str:
        return "sampling" if (budget is not None and not use_akr) else "akr"

    def query(self, sid: int, text: str, *, budget: Optional[int] = None,
              use_akr: bool = True, query_emb: Optional[np.ndarray] = None
              ) -> QueryResult:
        """Single-query shim (budget set ⇒ fixed-N sampling; else AKR)."""
        return self.query_specs([QuerySpec(
            sid=sid, text=text, embedding=query_emb,
            strategy=self._legacy_strategy(budget, use_akr),
            budget=budget)])[0]

    def query_batch(self, sid: int, texts: Optional[Sequence[str]] = None,
                    *, query_embs: Optional[np.ndarray] = None,
                    budget: Optional[int] = None, use_akr: bool = True
                    ) -> List[QueryResult]:
        """Q same-session queries → one single-group plan → ONE scan.
        Draws the same per-query subkeys as Q sequential ``query`` calls,
        so results match query-for-query."""
        n = len(query_embs) if query_embs is not None else len(texts)
        return self.query_batch_cross(
            [sid] * n, texts, query_embs=query_embs, budget=budget,
            use_akr=use_akr)

    def query_batch_cross(self, sids: Sequence[int],
                          texts: Optional[Sequence[str]] = None, *,
                          query_embs: Optional[np.ndarray] = None,
                          budget: Optional[int] = None,
                          use_akr: bool = True) -> List[QueryResult]:
        """Queries against SEVERAL sessions through ONE fused scan.

        ``sids[j]`` is the session query j targets. All specs share one
        strategy/budget, so the planner emits a single execution group:
        one padded-stack scan + one fused sampling→expansion program,
        with each session's PRNG chain advancing by exactly its own
        query count. Results come back in input order."""
        sids = [int(s) for s in sids]
        strategy = self._legacy_strategy(budget, use_akr)
        if query_embs is not None:
            qe = np.asarray(query_embs, np.float32)
            assert len(sids) == qe.shape[0]
            specs = [QuerySpec(sid=s, embedding=qe[j], strategy=strategy,
                               budget=budget)
                     for j, s in enumerate(sids)]
        else:
            assert len(sids) == len(texts)
            specs = [QuerySpec(sid=s, text=t, strategy=strategy,
                               budget=budget)
                     for s, t in zip(sids, texts)]
        return self.query_specs(specs)

    # stacked device views are ~S×(index + members) buffers each; bound
    # how many distinct session subsets stay cached (LRU) so arbitrary
    # query groupings can't grow device memory without limit (arena-
    # covering stacks are views, not copies — they cost nothing extra)
    MAX_CACHED_STACKS = 8

    def scan_lanes(self, sids: Sequence[int]
                   ) -> Tuple[Optional[int], ...]:
        """The lanes one fused scan covers, in scan-lane order.

        Arena mode: ALWAYS one lane per arena SLOT, in slot order — the
        arena super-buffers ARE the scan operand, so a group targeting
        any subset of sessions still consumes them as-is. Lanes without
        queries are padding, and a FREE slot (closed session awaiting
        reuse) appears as ``None``: its window reads ``(0, 0)``, so the
        device-derived mask blanks it. Per-lane math is independent, so
        results for the queried lanes are bit-identical to a subset
        scan, and nothing ever restacks. Detached mode: exactly the
        requested sessions, stacked (and version-cached) on demand."""
        if self.arena is not None:
            by_slot = {st.memory.slot: s
                       for s, st in self.sessions.items()}
            return tuple(by_slot.get(k)
                         for k in range(self.arena.n_sessions))
        return tuple(sids)

    def memory_stack(self, lanes: Tuple[Optional[int], ...]):
        """The scan view over the given lanes.

        Lanes containing holes (``None`` — freed arena slots) get the
        zero-copy ``ArenaStackView``, whose lanes are the arena slots
        themselves. Hole-free lane tuples keep the cached
        ``MemoryStack`` (which detects full-arena coverage and aliases
        the super-buffers — still zero-copy, still zero rebuilds)."""
        if any(s is None for s in lanes):
            assert self.arena is not None
            if (self._arena_stack is None
                    or self._arena_stack.arena is not self.arena):
                self._arena_stack = ArenaStackView(self.arena)
            return self._arena_stack
        stk = self._stacks.pop(lanes, None)
        if stk is None:
            stk = MemoryStack([self.sessions[s].memory for s in lanes],
                              rebuild_stats=self.io_stats)
            while len(self._stacks) >= self.MAX_CACHED_STACKS:
                self._stacks.pop(next(iter(self._stacks)))
        self._stacks[lanes] = stk         # re-insert = mark most recent
        return stk

    def query_topk(self, sid: int, text: str, k: int,
                   query_emb: Optional[np.ndarray] = None) -> np.ndarray:
        """Greedy Top-K shim: same accounted device-index path as every
        other strategy (scan counted, no re-upload), frame ids in rank
        order via the device-resident index_frame table."""
        res = self.query_specs([QuerySpec(
            sid=sid, text=text, embedding=query_emb, strategy="topk",
            budget=k)])[0]
        return res.frame_ids
