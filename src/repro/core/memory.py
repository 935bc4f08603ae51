"""Hierarchical memory (paper §IV-C): index layer over a raw data layer.

* **Raw data layer** — every captured frame, archived as-is. Here it is a
  ``FrameStore`` holding frames by absolute index (the paper's NVMe
  archive); reasoning-time expansion pulls raw frames from it.
* **Index data layer** — one vector per *indexed frame* (cluster
  centroid), stored in a fixed-capacity packed array that is directly
  shardable over the ``model`` mesh axis (DESIGN.md: brute-force MXU
  similarity replaces FAISS ANN on TPU). Each indexed vector is linked to
  its scene cluster via a bounded **member reservoir** — up to
  ``member_cap`` member frame ids kept uniformly at random, so
  "uniformly sample n(oᵢ) frames from cluster c(oᵢ)" (§IV-D1) stays a
  fixed-shape gather.

The index is **device-resident and incrementally updated**: the first
query uploads the packed array once; afterwards batched inserts append
rows in place with a jit'd ``dynamic_update_slice`` (bucketed batch
sizes bound the jit cache), so a post-ingest query never re-transfers
the whole ``(capacity, dim)`` buffer. The member reservoirs get the
same treatment (``device_members``), so reasoning-time expansion is a
jit'd on-device gather (``expand_draws_device``) instead of a host
lookup. ``io_stats`` counts full uploads vs appended rows (and host vs
device expansion gathers) so tests/benches can assert the transfer
behaviour.

``MemoryArena`` is the grow-in-place form of the cross-session view:
one set of device-resident super-buffers ``(S, capacity, d)`` /
``(S, capacity, K)`` owned by the session manager, inside which every
session's index, member reservoirs, and index_frame rows live from the
start. Per-tick batched appends are donated ``dynamic_update_slice``
writes at ``(slot, pos)``, so the arena buffers ARE the fused-scan
operand — queries between (or after) ingest ticks never restack
anything. Only the per-session valid masks depend on the sizes, and
those are derived on device from the tiny ``(S,)`` sizes vector.

``MemoryStack`` remains the padded-stack view over S ``VenusMemory``
instances for the cross-session fused query path. When its members all
live in one arena and cover it exactly (the session manager's default),
every view IS the arena buffer — zero stack rebuilds ever. Detached
memories (standalone use) fall back to the PR-2 behaviour: device-side
``jnp.stack`` of the per-memory buffers, cached against the members'
insert versions and rebuilt when any version changes (each rebuild is
counted into ``rebuild_stats["stack_rebuilds"]`` when provided).

**Lifecycle for 24/7 streams** (see ARCHITECTURE.md for the full state
machine). Two mechanisms keep memory bounded under unbounded streaming:

* **Slot recycling** — a closed session's arena slot goes onto a
  free-list (``MemoryArena.release_slot``); its lane reads window
  ``(0, 0)`` and is masked out as padding until ``add_session``
  recycles it after ONE donated device-side row reset. The arena grows
  by whole slot blocks only when the free-list is empty, so a churn
  workload (create → ingest → close → recreate) holds the slot count at
  its steady-state maximum with zero restacks and zero reallocation.
* **Eviction** — a session that outlives ``capacity`` consults its
  ``EvictionPolicy``. ``none`` keeps the historical overflow-raises
  contract. The window policies turn the memory into a device-side
  ring: a ``head`` offset marks the oldest valid row, eviction is O(1)
  pointer motion (``head`` advances, ``size`` shrinks) and the incoming
  rows overwrite the evicted physical positions in place. Validity is
  therefore a ``(head, size)`` WINDOW, not a prefix: every scan path
  accepts ``(S, 2)`` ``[start, size)`` windows as its ``valid`` operand
  (masks derive on device — ``kernels.ref.as_valid_mask``), and the
  detached per-memory path derives the same ring mask, so arena and
  detached semantics cannot diverge.

What ``valid`` means, in one place: a **bool mask** is explicit
per-row validity; a **(S,) sizes vector** means prefix ``[0, size)``;
a **(S, 2) window** means ring ``[start, start+size) mod capacity``.
A sizes vector is exactly a window with ``start == 0``.
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import os
from collections import OrderedDict
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels.ref import as_valid_mask
from repro.launch.sharding import memory_sharding, mesh_axis_size


class FrameStore:
    """Raw data layer: two-tier host+disk archive of frames by absolute
    index (ARCHITECTURE.md "Storage tiers").

    Append-only at the front, BOUNDED at the back: ``trim(keep_from)``
    removes every host frame below an absolute id, closing the
    unbounded host-RSS leak a 24/7 stream would otherwise accumulate.
    Frames keep their ABSOLUTE ids across trims — ``_base`` offsets the
    retained list — so every id recorded in index/member tables stays
    stable.

    Without a ``spill_dir`` (the historical single-tier contract),
    trimming DELETES: reading a trimmed frame raises ``IndexError``
    with the trim horizon, never silently returns the wrong frame, and
    the session layer only trims below every live reference (ring
    windows + member reservoirs + un-clustered pending frames).

    With ``spill_dir`` set, ``trim`` becomes a DEMOTION to the paper's
    NVMe archive tier: dropped frames are written to append-only ``.npy``
    segment files of ≤ ``segment_frames`` frames each, contiguously
    tiling ``[0, base)`` (demotions always continue at the current
    base, so segment starts are strictly increasing and ``bisect``
    finds any spilled id). ``get`` then transparently FAULTS spilled
    ids back through a small LRU segment cache (``cache_segments``
    whole segments), returning bytes bit-identical to what was appended
    — the npy container round-trips dtype and contents exactly.
    Durability is a tick-boundary affair: segments are written eagerly
    but ``sync()`` (called by the session manager after each tick's
    trims) is what fsyncs them — and the directory — to disk.
    ``io_stats`` counts demotions (``spilled_frames``/``spilled_bytes``)
    and reads (``spill_faults`` = segment loads from disk,
    ``spill_cache_hits`` = reads served from the LRU cache) so tests
    and benches can account for every demotion and fault. ``close()``
    releases BOTH tiers: host frames, the cache, and every segment
    file (churned sessions must leak neither RSS nor disk)."""

    def __init__(self, spill_dir: Optional[str] = None, *,
                 segment_frames: int = 64, cache_segments: int = 4):
        assert segment_frames >= 1, segment_frames
        assert cache_segments >= 1, cache_segments
        self._frames: List[np.ndarray] = []
        self._base = 0            # absolute id of _frames[0]
        self.trimmed = 0          # total frames dropped from host so far
        self.spill_dir = spill_dir
        self.segment_frames = int(segment_frames)
        self.cache_segments = int(cache_segments)
        # (start, count, path, nbytes) per segment, tiling [0, _base)
        self._segments: List[Tuple[int, int, str, int]] = []
        self._seg_starts: List[int] = []       # bisect key for _segments
        self._cache: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._unsynced: List[str] = []         # written, not yet fsync'd
        self._disk_bytes = 0                   # live segment bytes gauge
        self.io_stats = {"spilled_frames": 0, "spilled_bytes": 0,
                         "spill_faults": 0, "spill_cache_hits": 0}
        self.recovered_frames = 0     # adopted from disk at open
        self.dropped_segments = 0     # rejected as short/corrupt/gapped
        if spill_dir is not None:
            os.makedirs(spill_dir, exist_ok=True)
            self._recover_segments()

    def _recover_segments(self) -> None:
        """Re-adopt segment files left by a previous process (crash
        recovery: a store re-opened on an existing spill dir must serve
        the frames it already demoted, not silently alias absolute ids
        from 0 again).

        Adoption walks the ``seg-<start>-<count>.npy`` names in start
        order and accepts the longest VALID prefix tiling ``[0, base)``:
        a segment is rejected — along with everything after it, since
        later starts would leave a hole in the id space — if its start
        leaves a gap or its payload doesn't round-trip as a
        ``(count, ...)`` npy array (the
        truncated-mid-write case: a torn header or short data section
        fails to load rather than returning garbage frames). Rejected
        files are deleted so the on-disk state matches the adopted
        prefix and future demotions can't collide with a half-written
        name; files that don't look like segments at all are left
        untouched (they're not ours to delete). The host tier restarts
        empty at ``base`` = the adopted
        frame count; ``get`` faults adopted ids back exactly as if this
        process had spilled them."""
        try:
            names = sorted(os.listdir(self.spill_dir))
        except OSError:
            return
        parsed = []
        rejects = []
        for name in names:
            parts = name.split("-")
            if (name.endswith(".npy") and len(parts) == 3
                    and parts[0] == "seg" and parts[1].isdigit()
                    and parts[2][:-4].isdigit()):
                parsed.append((int(parts[1]), int(parts[2][:-4]), name))
        parsed.sort()
        base = 0
        for start, count, name in parsed:
            path = os.path.join(self.spill_dir, name)
            ok = start == base and count >= 1
            if ok:
                try:
                    # mmap validates the header AND that the file holds
                    # the full payload (a short data section raises) —
                    # without reading the frames in
                    seg = np.load(path, mmap_mode="r",
                                  allow_pickle=False)
                    ok = seg.shape[0] == count
                    nbytes = seg.size * seg.dtype.itemsize
                    del seg
                except Exception:
                    ok = False
            if not ok:
                rejects.append(name)
                continue
            self._segments.append((start, count, path, nbytes))
            self._seg_starts.append(start)
            self._disk_bytes += nbytes
            base = start + count
        self._base = base
        self.trimmed = base
        self.recovered_frames = base
        for name in rejects:
            self.dropped_segments += 1
            with contextlib.suppress(OSError):
                os.remove(os.path.join(self.spill_dir, name))

    def append(self, frames: np.ndarray) -> None:
        for f in np.asarray(frames):
            self._frames.append(f)

    def __len__(self) -> int:
        """Total frames ever archived (absolute id space, incl. trimmed)."""
        return self._base + len(self._frames)

    @property
    def base(self) -> int:
        """Smallest absolute frame id still retained ON HOST. With
        spill enabled, ids below this are on disk, not gone."""
        return self._base

    @property
    def retained(self) -> int:
        """Frames currently held on host (the actual RSS footprint;
        the LRU fault cache is bounded separately by
        ``cache_segments * segment_frames``)."""
        return len(self._frames)

    @property
    def spill_enabled(self) -> bool:
        return self.spill_dir is not None

    @property
    def spill_floor(self) -> int:
        """Smallest absolute id ``get`` can serve: 0 with spill enabled
        (every demoted frame faults back in), else the host base."""
        return 0 if self.spill_enabled else self._base

    @property
    def disk_bytes(self) -> int:
        """Bytes currently held in spill segment files (gauge — drops
        to 0 at ``close``)."""
        return self._disk_bytes

    def reset_io_stats(self) -> None:
        for k in self.io_stats:
            self.io_stats[k] = 0

    def get(self, idx: Sequence[int]) -> np.ndarray:
        out = []
        for i in idx:
            i = int(i)
            if i >= self._base:
                out.append(self._frames[i - self._base])
            elif self.spill_enabled and 0 <= i < self._base:
                out.append(self._fault(i))
            else:
                raise IndexError(
                    f"frame {i} was trimmed from the archive "
                    f"(retained ids start at {self._base})")
        return np.stack(out)

    def trim(self, keep_from: int) -> int:
        """Drop every frame with absolute id < ``keep_from`` from the
        host tier; returns how many left the host. Trimming past the
        end is clamped. With spill enabled this is a demotion — the
        dropped frames are written to segment files first and stay
        readable through ``get``; without it they are gone."""
        drop = max(0, min(int(keep_from), len(self)) - self._base)
        if drop:
            if self.spill_enabled:
                self._spill(self._frames[:drop])
            del self._frames[:drop]
            self._base += drop
            self.trimmed += drop
        return drop

    def _spill(self, frames: List[np.ndarray]) -> None:
        """Demote ``frames`` (the host prefix starting at the current
        base) into ≤ ``segment_frames``-frame npy segments appended
        after the existing ones."""
        start = self._base
        for off in range(0, len(frames), self.segment_frames):
            chunk = np.stack(frames[off:off + self.segment_frames])
            seg_start = start + off
            path = os.path.join(
                self.spill_dir,
                f"seg-{seg_start:012d}-{len(chunk):05d}.npy")
            np.save(path, chunk, allow_pickle=False)
            self._segments.append(
                (seg_start, len(chunk), path, chunk.nbytes))
            self._seg_starts.append(seg_start)
            self._unsynced.append(path)
            self._disk_bytes += chunk.nbytes
            self.io_stats["spilled_frames"] += len(chunk)
            self.io_stats["spilled_bytes"] += chunk.nbytes

    def _fault(self, i: int) -> np.ndarray:
        """Serve one spilled absolute id from its segment, via the LRU
        whole-segment cache (a miss loads — and counts — one segment)."""
        k = bisect.bisect_right(self._seg_starts, i) - 1
        start, count, path, _ = self._segments[k]
        assert start <= i < start + count, (i, start, count)
        seg = self._cache.get(start)
        if seg is not None:
            self._cache.move_to_end(start)
            self.io_stats["spill_cache_hits"] += 1
        else:
            seg = np.load(path, allow_pickle=False)
            self.io_stats["spill_faults"] += 1
            self._cache[start] = seg
            while len(self._cache) > self.cache_segments:
                self._cache.popitem(last=False)
        return seg[i - start]

    def sync(self) -> int:
        """fsync every segment written since the last sync (plus the
        spill directory, so the new names are durable too). The session
        manager calls this at tick boundaries — segment writes inside a
        tick are buffered, the tick commit is the durability point.
        Returns how many files were synced."""
        if not self._unsynced:
            return 0
        for path in self._unsynced:
            with open(path, "rb") as f:
                os.fsync(f.fileno())
        dfd = os.open(self.spill_dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        n = len(self._unsynced)
        self._unsynced.clear()
        return n

    def close(self) -> None:
        """Release BOTH tiers: host frames, the fault cache, and every
        spill segment file (and the per-session spill directory, if
        empty). Idempotent; counters survive so the session layer can
        fold them into its closed-session sums first."""
        self._frames.clear()
        self._cache.clear()
        self._unsynced.clear()
        for _, _, path, _ in self._segments:
            with contextlib.suppress(OSError):
                os.remove(path)
        self._segments.clear()
        self._seg_starts.clear()
        self._disk_bytes = 0
        if self.spill_dir is not None:
            with contextlib.suppress(OSError):
                os.rmdir(self.spill_dir)


@dataclass
class IndexEntry:
    scene_id: int
    cluster_id: int
    ts: int                      # timestamp (frame index) of indexed frame


# Both mask helpers delegate to the kernels' shared `as_valid_mask`
# definition — the ring-window semantics live in exactly ONE place, so
# the arena, detached, oracle, and Pallas paths cannot diverge.

@functools.partial(jax.jit, static_argnames=("capacity",))
def _ring_valid_mask(head: jnp.ndarray, size: jnp.ndarray, *,
                     capacity: int) -> jnp.ndarray:
    """Physical-row validity of the ring window ``[head, head+size)``
    (mod capacity). ``head == 0`` reduces to the plain prefix mask."""
    return as_valid_mask(jnp.stack([head, size])[None], capacity)[0]


@functools.partial(jax.jit, static_argnames=("capacity",))
def _window_valid_stack(windows: jnp.ndarray, *, capacity: int
                        ) -> jnp.ndarray:
    """(S, 2) int ``[head, size]`` windows -> (S, capacity) bool masks,
    derived on device (only the tiny windows array ever transfers)."""
    return as_valid_mask(windows, capacity)


@functools.partial(jax.jit, donate_argnums=(0,))
def _append_rows(emb: jnp.ndarray, rows: jnp.ndarray,
                 pos: jnp.ndarray) -> jnp.ndarray:
    """Append a row block at ``pos``. The index buffer is donated, so
    XLA updates it in place — O(rows) bytes moved, not O(capacity)."""
    return jax.lax.dynamic_update_slice(emb, rows, (pos, 0))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _append_member_rows(members: jnp.ndarray, counts: jnp.ndarray,
                        rows: jnp.ndarray, cnts: jnp.ndarray,
                        pos: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """In-place append of member-reservoir rows + their counts."""
    members = jax.lax.dynamic_update_slice(members, rows, (pos, 0))
    counts = jax.lax.dynamic_update_slice(counts, cnts, (pos,))
    return members, counts


@functools.partial(jax.jit, donate_argnums=(0,))
def _append_id_rows(buf: jnp.ndarray, rows: jnp.ndarray,
                    pos: jnp.ndarray) -> jnp.ndarray:
    """In-place append for 1-D id tables (index_frame)."""
    return jax.lax.dynamic_update_slice(buf, rows, (pos,))


# Symmetric per-row int8 quantisation for the index super-buffers. The
# similarity kernels L2-normalise every index row in-register, so a
# per-row scale CANCELS out of the cosine scores — the kernels consume
# the int8 rows directly (one astype, no scales operand) and stream 4×
# fewer bytes per scan. The scales are still stored (one f32 per row,
# written by the same donated scatter as the rows) so anything that
# needs faithful magnitudes can dequantise: dequant = q * scale.
def quantise_rows(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """rows (..., d) f32 -> (int8 rows, (...,) f32 per-row scales) with
    scale = max|row|/127 (all-zero rows get scale 1.0 so dequant is
    exact there too)."""
    rows = np.asarray(rows, np.float32)
    scale = np.max(np.abs(rows), axis=-1) / 127.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.rint(rows / scale[..., None]), -127, 127)
    return q.astype(np.int8), scale


def _index_buf_dtype(index_dtype: str):
    assert index_dtype in ("float32", "int8"), index_dtype
    return jnp.int8 if index_dtype == "int8" else jnp.float32


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _arena_reset_slot(emb: jnp.ndarray, members: jnp.ndarray,
                      counts: jnp.ndarray, ifr: jnp.ndarray,
                      slot: jnp.ndarray):
    """Donated zero-reset of ONE slot's rows across every super-buffer —
    the whole device-side cost of recycling a freed slot for a new
    session is this single program (no reallocation, no restack)."""
    emb = jax.lax.dynamic_update_slice(
        emb, jnp.zeros((1,) + emb.shape[1:], emb.dtype), (slot, 0, 0))
    members = jax.lax.dynamic_update_slice(
        members, jnp.zeros((1,) + members.shape[1:], members.dtype),
        (slot, 0, 0))
    counts = jax.lax.dynamic_update_slice(
        counts, jnp.zeros((1,) + counts.shape[1:], counts.dtype),
        (slot, 0))
    ifr = jax.lax.dynamic_update_slice(
        ifr, jnp.zeros((1,) + ifr.shape[1:], ifr.dtype), (slot, 0))
    return emb, members, counts, ifr


@functools.partial(jax.jit, donate_argnums=(0,))
def _arena_reset_row(buf: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """Donated zero-reset of one slot's row in a (S, cap) table — the
    int8 arena's per-row scale buffer at slot-recycle time."""
    return jax.lax.dynamic_update_slice(
        buf, jnp.zeros((1,) + buf.shape[1:], buf.dtype), (slot, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _arena_scatter_rows(buf: jnp.ndarray, rows: jnp.ndarray,
                        slots: jnp.ndarray, poss: jnp.ndarray
                        ) -> jnp.ndarray:
    """Donated scatter of a whole TICK's rows — every session's appends
    in one program: buf (S, cap, …) gets rows (B, …) written at
    (slots[i], poss[i]) in place. Padding rows duplicate row 0 (same
    index, same value — a deterministic no-op rewrite)."""
    return buf.at[slots, poss].set(rows)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _arena_scatter_meta(counts: jnp.ndarray, ifr: jnp.ndarray,
                        cnt_rows: jnp.ndarray, if_rows: jnp.ndarray,
                        slots: jnp.ndarray, poss: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Donated per-tick scatter of the small (S, cap) tables."""
    return (counts.at[slots, poss].set(cnt_rows),
            ifr.at[slots, poss].set(if_rows))


# Uniform member pick: one variate per draw slot, represented as an
# integer u ∈ [0, 2^U_BITS) so host (int64) and device (int32) paths
# compute pick = (u * cnt) >> U_BITS *bit-identically* — no float
# rounding can make the two paths disagree at a floor boundary.
U_BITS = 20
_U_CARD = 1 << U_BITS


@jax.jit
def expand_gather(members: jnp.ndarray, counts: jnp.ndarray,
                  draws: jnp.ndarray, valid: jnp.ndarray,
                  u: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device reservoir gather: draws (..., n) index rows of the
    device-resident members table; u (n,) or (..., n) int32 variates pick
    one member per slot. Returns (frame ids (..., n), ok (..., n))."""
    cap = members.shape[0]
    safe = jnp.clip(draws, 0, cap - 1)
    cnt = counts[safe]                                    # (..., n)
    pick = (u.astype(jnp.int32) * cnt) >> U_BITS          # exact floor
    fids = jnp.take_along_axis(members[safe], pick[..., None], -1)[..., 0]
    ok = valid & (cnt > 0) & (draws >= 0)
    return fids, ok


from repro.util import pow2_bucket


# ---------------------------------------------------------------------------
# Eviction policies: what happens when an insert would overflow capacity
# ---------------------------------------------------------------------------


class EvictionPolicy:
    """Bounded-memory policy for sessions that outlive ``capacity``.

    ``none`` preserves the historical contract: overflow raises and the
    session simply stops ingesting. The window policies below turn the
    memory into a device-side ring — ``evict`` advances the logical
    window start (``head``) over the ``need`` oldest rows, O(1) pointer
    motion; the incoming rows then overwrite the evicted physical
    positions in place, so a 24/7 stream runs forever in constant
    device memory.
    """

    name = "none"

    def evict(self, mem: "VenusMemory", need: int) -> None:
        raise RuntimeError("memory capacity exhausted")


class SlidingWindowEviction(EvictionPolicy):
    """Keep only the newest ``capacity`` index rows: evict the oldest
    ``need`` rows by advancing the ring head (the streaming-systems
    baseline — bounded memory + explicit eviction, cf. LiveVLM)."""

    name = "sliding_window"

    def evict(self, mem: "VenusMemory", need: int) -> None:
        mem._advance_head(need)


class ClusterMergeEviction(SlidingWindowEviction):
    """Sliding window that first folds each evictee's member reservoir
    into its most similar surviving index row (cosine ≥ ``threshold``),
    so the raw frames of an evicted cluster stay reachable through the
    merged cluster instead of cliff-dropping at the window edge."""

    name = "cluster_merge"

    def __init__(self, threshold: float = 0.8):
        self.threshold = threshold

    def evict(self, mem: "VenusMemory", need: int) -> None:
        mem._merge_into_survivors(need, self.threshold)
        mem._advance_head(need)


class ConsolidationEviction(ClusterMergeEviction):
    """Hierarchical-tier eviction (paper §IV-C): each evictee folds
    into the session's COARSE summary tier — a running count-weighted
    centroid + merged member reservoir + frame-window metadata — before
    the ring head advances. Unlike ``cluster_merge``, the fold target
    is a dedicated summary row (not a surviving fine row), so evicted
    history stays retrievable through the two-stage coarse→fine scan
    long after it leaves the fine window. Requires the memory to be
    built with ``coarse_capacity > 0``."""

    name = "consolidate"

    def evict(self, mem: "VenusMemory", need: int) -> None:
        mem._consolidate(need, self.threshold)
        mem._advance_head(need)


_EVICTION_POLICIES = {
    "none": EvictionPolicy,
    "sliding_window": SlidingWindowEviction,
    "cluster_merge": ClusterMergeEviction,
    "consolidate": ConsolidationEviction,
}


def get_eviction_policy(policy,
                        threshold: Optional[float] = None) -> EvictionPolicy:
    """Resolve a policy by name (an ``EvictionPolicy`` instance passes
    through, so callers can hand in a configured one). ``threshold``
    configures the similarity cut of the merge/consolidation policies
    (``VenusConfig.merge_threshold`` reaches here); it is validated to
    (0, 1] — cosine similarity of normalised rows — and rejected for
    policies that have no threshold to configure."""
    if threshold is not None:
        if not (0.0 < float(threshold) <= 1.0):
            raise ValueError(
                f"merge threshold must be in (0, 1], got {threshold!r}")
    if isinstance(policy, EvictionPolicy):
        return policy
    try:
        cls = _EVICTION_POLICIES[policy]
    except KeyError:
        raise KeyError(f"unknown eviction policy {policy!r}; known: "
                       f"{sorted(_EVICTION_POLICIES)}") from None
    if threshold is not None and issubclass(cls, ClusterMergeEviction):
        return cls(float(threshold))
    return cls()


def coarse_rows_for(capacity: int, coarse_capacity: int,
                    coarse_block: int) -> Tuple[int, int]:
    """Geometry of the coarse tier: ``(n_blocks, n_coarse)`` where rows
    ``[0, n_blocks)`` are block summaries of the fine tier (one per
    ``coarse_block`` physical fine rows) and rows ``[n_blocks,
    n_coarse)`` are consolidated summaries of evicted history. A
    ``coarse_capacity`` of 0 disables the tier entirely."""
    if coarse_capacity <= 0:
        return 0, 0
    assert coarse_block > 0, coarse_block
    n_blocks = -(-capacity // coarse_block)        # ceil div
    return n_blocks, n_blocks + coarse_capacity


class MemoryArena:
    """Shared device-resident super-buffers for S sessions' memories.

    Sessions allocate their index (``emb``), member reservoirs
    (``members``/``member_count``), and ``index_frame`` rows directly
    inside ``(S, capacity, …)`` buffers owned here, so the fused
    cross-session query path scans the arena buffers AS-IS: batched tick
    appends are donated ``dynamic_update_slice`` writes at
    ``(slot, pos)``, and after warm-up no ingest↔query interleaving ever
    triggers a device-side restack (``stack_rebuilds`` stays 0 — see
    ``MemoryStack``). Per-session valid masks are derived on device from
    the ``(S,)`` sizes vector (the only thing that moves host→device
    per tick besides the appended rows themselves).

    Slot lifecycle: ``add_session`` prefers the free-list — a slot a
    closed session released via ``release_slot`` — and recycles it after
    ONE donated device-side row reset; the buffers grow by a whole slot
    block (a copy, counted in ``io_stats["grows"]``) only when the
    free-list is empty. Session churn therefore holds the slot count at
    its steady-state maximum: creation is warm-up, not the steady
    ingest↔query loop. Each slot carries a ``(head, size)`` ring window
    (``heads``/``sizes`` host mirrors); free slots read ``(0, 0)`` and
    are masked-out padding lanes until reuse.

    ``index_dtype="int8"`` stores the index super-buffer quantised
    (symmetric per-row int8, scales in ``emb_scale``): every append
    quantises once at the donated scatter, every scan streams 4× fewer
    bytes, and the scan math is unchanged because the kernels
    L2-normalise rows — the per-row scale cancels, so no dequant pass
    and no scales operand exist anywhere in the kernel contract.

    **Sharding** (``mesh=`` + the mesh's ``model`` axis size K > 1):
    every super-buffer is placed with ``memory_sharding`` — the leading
    slot axis split into K contiguous slabs, trailing dims replicated —
    and the fused scan entries in ``kernels.ops`` fan the SAME kernels
    out per-slab under ``shard_map`` (the stack kernels are pure
    per-lane programs, so a slab scan is bitwise the single-device scan
    restricted to that slab). To keep slabs rectangular the arena then
    grows in blocks of K slots: the block's first slot is handed out,
    the rest wait in ``virgin_slots`` (already zeroed — claiming one
    costs nothing and is not a ``slot_reuse``); allocation picks the
    free/virgin slot on the least-loaded shard so sessions stay
    balanced across devices. With K == 1 (or no mesh) every code path
    below is byte-for-byte the unsharded PR-6 behaviour — single-slot
    growth, exact LIFO free-list reuse, no placement.

    **Double buffering** (``double_buffer=True``): the arena keeps a
    second, back set of super-buffers one tick behind the front.
    A tick's flush replays last tick's blocks (the ``carry``) plus this
    tick's pending into the BACK set, then swaps front↔back — so the
    donated append scatter never writes the buffers queries are
    scanning, and XLA's async dispatch overlaps ingest with the fused
    query launches instead of serialising on the donation hazard.
    Because scatters compose last-write-wins per (slot, pos), the front
    after every flush is bitwise identical to the single-buffer state;
    slot resets and growth apply to both sets, and the carry is
    filtered when its slot is recycled.
    """

    def __init__(self, capacity: int, dim: int, member_cap: int = 128,
                 index_dtype: str = "float32", *, mesh=None,
                 mesh_axis: str = "model", double_buffer: bool = False,
                 coarse_capacity: int = 0, coarse_block: int = 64):
        self.capacity = capacity
        self.dim = dim
        self.member_cap = member_cap
        self.index_dtype = index_dtype
        self._emb_dtype = _index_buf_dtype(index_dtype)
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.n_shards = mesh_axis_size(mesh, mesh_axis)
        self.emb_scale: Optional[jnp.ndarray] = None    # (S, cap) f32
        self.n_sessions = 0       # allocated slots (incl. freed ones)
        self.emb: Optional[jnp.ndarray] = None          # (S, cap, d)
        self.members: Optional[jnp.ndarray] = None      # (S, cap, K)
        self.member_count: Optional[jnp.ndarray] = None  # (S, cap)
        self.index_frame: Optional[jnp.ndarray] = None   # (S, cap)
        self.sizes = np.zeros((0,), np.int32)            # host mirror
        self.heads = np.zeros((0,), np.int32)            # ring starts
        # coarse tier: (S, n_coarse, ·) summary super-buffers — rows
        # [0, n_blocks) summarise fine blocks, [n_blocks, n_coarse)
        # hold consolidated (evicted) history. Always f32: centroids
        # are running means and the scan normalises rows anyway, so
        # quantising the tiny coarse stack buys nothing.
        self.coarse_capacity = coarse_capacity
        self.coarse_block = coarse_block
        self.n_blocks, self.n_coarse = coarse_rows_for(
            capacity, coarse_capacity, coarse_block)
        self.coarse_emb: Optional[jnp.ndarray] = None        # (S, Nc, d)
        self.coarse_members: Optional[jnp.ndarray] = None    # (S, Nc, K)
        self.coarse_member_count: Optional[jnp.ndarray] = None  # (S, Nc)
        self.coarse_index_frame: Optional[jnp.ndarray] = None   # (S, Nc)
        self.coarse_valid = np.zeros((0, self.n_coarse), bool)  # host
        self._coarse_valid_dev: Optional[jnp.ndarray] = None
        self._coarse_valid_ver = -1
        self._coarse_deferred: Optional[list] = None
        self.free_slots: List[int] = []    # released, awaiting reuse
        self.virgin_slots: List[int] = []  # grown, never yet allocated
        self.version = 0          # bumped per append / grow / release
        self._sizes_dev: Optional[jnp.ndarray] = None
        self._windows_dev: Optional[jnp.ndarray] = None
        self._valid_dev: Optional[jnp.ndarray] = None
        self._valid_version = -1
        self._deferred: Optional[list] = None   # open tick batch, or None
        # back buffer set (double_buffer) + last tick's blocks to replay
        self._back: Optional[dict] = (
            {"emb": None, "members": None, "member_count": None,
             "index_frame": None, "emb_scale": None}
            if double_buffer else None)
        self._carry: list = []
        self.io_stats = {"grows": 0, "appends": 0, "appended_rows": 0,
                         "slot_releases": 0, "slot_reuses": 0,
                         "double_flushes": 0, "carry_rows": 0,
                         "coarse_appends": 0, "coarse_appended_rows": 0}

    @property
    def double_buffer(self) -> bool:
        return self._back is not None

    def reset_io_stats(self) -> None:
        for k in self.io_stats:
            self.io_stats[k] = 0

    # ------------------------------------------------------------- lifecycle
    def _place(self, buf: jnp.ndarray) -> jnp.ndarray:
        """Pin a super-buffer to its mesh placement: leading slot axis in
        contiguous per-device slabs, trailing dims replicated (the same
        spec the shard_map scan entries consume). No-op unsharded."""
        if self.mesh is not None and self.n_shards > 1:
            return jax.device_put(
                buf, memory_sharding(self.mesh, buf.ndim, self.mesh_axis))
        return buf

    def _grow(self, buf: Optional[jnp.ndarray], shape: Tuple[int, ...],
              dtype) -> jnp.ndarray:
        if buf is None:
            return self._place(jnp.zeros(shape, dtype))
        pad = [(0, shape[0] - buf.shape[0])] + [(0, 0)] * (buf.ndim - 1)
        # growth moves slab boundaries, so the pad includes a reshard
        # copy — acceptable: growth is warm-up, never the steady loop
        return self._place(jnp.pad(buf, pad))

    def _shard_of(self, slot: int) -> int:
        """Which contiguous slab (device) a slot currently lives on."""
        slab = max(1, self.n_sessions // self.n_shards)
        return min(slot // slab, self.n_shards - 1)

    def _recycle(self, slot: int) -> int:
        """Reset a released slot's device rows (one donated program per
        buffer set) and hand it out again."""
        js = jnp.asarray(slot, jnp.int32)
        (self.emb, self.members, self.member_count,
         self.index_frame) = _arena_reset_slot(
            self.emb, self.members, self.member_count,
            self.index_frame, js)
        if self.emb_scale is not None:
            self.emb_scale = _arena_reset_row(self.emb_scale, js)
        if self._back is not None:
            bk = self._back
            (bk["emb"], bk["members"], bk["member_count"],
             bk["index_frame"]) = _arena_reset_slot(
                bk["emb"], bk["members"], bk["member_count"],
                bk["index_frame"], js)
            if bk["emb_scale"] is not None:
                bk["emb_scale"] = _arena_reset_row(bk["emb_scale"], js)
            # drop the reset slot from the replay queue — last tick's
            # rows must not resurrect inside a recycled slot
            self._carry = [b for b in self._carry if b[0] != slot]
        if self.n_coarse:
            (self.coarse_emb, self.coarse_members, self.coarse_member_count,
             self.coarse_index_frame) = _arena_reset_slot(
                self.coarse_emb, self.coarse_members,
                self.coarse_member_count, self.coarse_index_frame, js)
            self.coarse_valid[slot] = False
        self.sizes[slot] = 0
        self.heads[slot] = 0
        self.version += 1
        self.io_stats["slot_reuses"] += 1
        return slot

    def _grow_block(self) -> int:
        """Grow every super-buffer by one slot block (``n_shards`` slots,
        so S always divides the mesh axis); returns the first new slot,
        parking the rest in ``virgin_slots``."""
        slot = self.n_sessions
        self.n_sessions = s = slot + self.n_shards
        cap, d, k = self.capacity, self.dim, self.member_cap
        self.emb = self._grow(self.emb, (s, cap, d), self._emb_dtype)
        if self.index_dtype == "int8":
            self.emb_scale = self._grow(self.emb_scale, (s, cap),
                                        jnp.float32)
        self.members = self._grow(self.members, (s, cap, k), jnp.int32)
        self.member_count = self._grow(self.member_count, (s, cap),
                                       jnp.int32)
        self.index_frame = self._grow(self.index_frame, (s, cap),
                                      jnp.int32)
        if self._back is not None:
            bk = self._back
            bk["emb"] = self._grow(bk["emb"], (s, cap, d), self._emb_dtype)
            if self.index_dtype == "int8":
                bk["emb_scale"] = self._grow(bk["emb_scale"], (s, cap),
                                             jnp.float32)
            bk["members"] = self._grow(bk["members"], (s, cap, k),
                                       jnp.int32)
            bk["member_count"] = self._grow(bk["member_count"], (s, cap),
                                            jnp.int32)
            bk["index_frame"] = self._grow(bk["index_frame"], (s, cap),
                                           jnp.int32)
        if self.n_coarse:
            nc = self.n_coarse
            self.coarse_emb = self._grow(self.coarse_emb, (s, nc, d),
                                         jnp.float32)
            self.coarse_members = self._grow(self.coarse_members,
                                             (s, nc, k), jnp.int32)
            self.coarse_member_count = self._grow(
                self.coarse_member_count, (s, nc), jnp.int32)
            self.coarse_index_frame = self._grow(
                self.coarse_index_frame, (s, nc), jnp.int32)
            self.coarse_valid = np.concatenate(
                [self.coarse_valid,
                 np.zeros((self.n_shards, nc), bool)])
        self.sizes = np.append(self.sizes,
                               np.zeros((self.n_shards,), np.int32))
        self.heads = np.append(self.heads,
                               np.zeros((self.n_shards,), np.int32))
        self.virgin_slots.extend(range(slot + 1, s))
        self.version += 1
        self.io_stats["grows"] += 1
        return slot

    def add_session(self) -> int:
        """Allocate a slot: recycle a released one (device rows reset
        via one donated program — no growth, no restack), claim a
        still-virgin slot from an earlier growth block, or grow every
        super-buffer by one whole slot block."""
        if self.n_shards == 1:
            # unsharded: exact PR-6 behaviour — LIFO reuse, 1-slot blocks
            if self.free_slots:
                return self._recycle(self.free_slots.pop())
            return self._grow_block()
        cand = sorted(set(self.free_slots) | set(self.virgin_slots))
        if not cand:
            return self._grow_block()
        # balance live sessions across slabs: pick the candidate on the
        # least-loaded shard (tie → lowest slot id)
        dead = set(self.free_slots) | set(self.virgin_slots)
        load = [0] * self.n_shards
        for s in range(self.n_sessions):
            if s not in dead:
                load[self._shard_of(s)] += 1
        slot = min(cand, key=lambda s: (load[self._shard_of(s)], s))
        if slot in self.virgin_slots:
            # never written: its rows are the zeros growth placed there,
            # so claiming costs no device work at all
            self.virgin_slots.remove(slot)
            return slot
        self.free_slots.remove(slot)
        return self._recycle(slot)

    def release_slot(self, slot: int) -> None:
        """Free a closed session's slot into the free-list. The lane's
        window reads ``(0, 0)`` — masked-out padding for every scan —
        until ``add_session`` recycles it; the stale device rows are
        reset at reuse time, so closing costs no device work at all."""
        assert 0 <= slot < self.n_sessions, slot
        assert slot not in self.free_slots, f"slot {slot} already free"
        assert slot not in self.virgin_slots, f"slot {slot} never allocated"
        self.free_slots.append(slot)
        self.sizes[slot] = 0
        self.heads[slot] = 0
        if self.n_coarse:
            # mask the lane's whole coarse tier out of stage-1 scans;
            # the stale device rows reset at reuse time like fine rows
            self.coarse_valid[slot] = False
        self.version += 1
        self.io_stats["slot_releases"] += 1

    # ------------------------------------------------------------ ingestion
    @contextlib.contextmanager
    def deferred_appends(self):
        """Batch every ``append`` issued inside the context into ONE
        donated scatter per super-buffer — the per-tick batched append
        path: a multi-stream ingest tick moves each buffer once, no
        matter how many sessions closed clusters. Device views read
        inside the window see pre-tick state; they refresh at exit (one
        version bump). Re-entrant: the outermost context flushes."""
        if self._deferred is not None:
            yield
            return
        self._deferred = []
        self._coarse_deferred = []
        try:
            yield
        finally:
            pending, self._deferred = self._deferred, None
            coarse, self._coarse_deferred = self._coarse_deferred, None
            self._flush(pending)
            # coarse rows land AFTER the fine flush: block summaries are
            # host-computed from the post-tick mirrors, so their device
            # write must not be overtaken by this tick's fine scatter
            self._flush_coarse(coarse)

    def append(self, slot: int, pos: int, emb_rows: np.ndarray,
               member_rows: np.ndarray, member_cnts: np.ndarray,
               if_rows: np.ndarray, window: Tuple[int, int]) -> int:
        """Append one session's contiguous row run at ``[slot,
        pos:pos+n]`` and record its new ``(head, size)`` ring window
        (applied when the write lands — a wrapped ring write arrives as
        two contiguous runs, each carrying the same final window).

        Inside a ``deferred_appends`` window the run is queued for the
        tick's fused scatter; otherwise it lands immediately as its own
        donated scatter. Either way the row count is bucketed with
        padding rows that DUPLICATE row 0 (same index, same value — a
        deterministic no-op rewrite), which is ring-safe: padding past
        the run could overwrite live rows once a session wraps. Returns
        the rows moved (raw count when deferred, padded when not)."""
        block = (slot, pos, np.asarray(emb_rows), np.asarray(member_rows),
                 np.asarray(member_cnts), np.asarray(if_rows),
                 (int(window[0]), int(window[1])))
        if self._deferred is not None:
            self._deferred.append(block)
            return len(emb_rows)
        return self._flush([block])

    def append_coarse(self, slot: int, pos: int, emb_rows: np.ndarray,
                      member_rows: np.ndarray, member_cnts: np.ndarray,
                      if_rows: np.ndarray, valid_rows: np.ndarray) -> int:
        """Queue one session's coarse summary-row run at ``[slot,
        pos:pos+n]`` — block summaries (``pos < n_blocks``) or
        consolidated rows. Inside a ``deferred_appends`` window the run
        rides the tick's coarse scatter; otherwise it lands immediately.
        ``valid_rows`` is each row's stage-1 visibility (an empty fine
        block's summary is masked out)."""
        assert self.n_coarse, "arena has no coarse tier"
        block = (slot, pos, np.asarray(emb_rows, np.float32),
                 np.asarray(member_rows), np.asarray(member_cnts),
                 np.asarray(if_rows),
                 np.asarray(valid_rows, bool))
        if self._coarse_deferred is not None:
            self._coarse_deferred.append(block)
            return len(emb_rows)
        return self._flush_coarse([block])

    def _flush_coarse(self, blocks: list) -> int:
        """One donated scatter per coarse super-buffer for the tick's
        summary-row writes (same last-write-wins dedup + pow2 bucketing
        as the fine scatter). The coarse tier is single-buffered even
        under ``double_buffer`` — summary rows are tiny, and a stale-by-
        one-tick coarse row only shifts which fine blocks stage 2
        gathers, never correctness."""
        if not blocks:
            return 0
        slots = np.concatenate([np.full(len(e), s, np.int32)
                                for s, _, e, *_ in blocks])
        poss = np.concatenate([np.arange(p, p + len(e), dtype=np.int32)
                               for _, p, e, *_ in blocks])
        emb_rows = np.concatenate([b[2] for b in blocks])
        mem_rows = np.concatenate([b[3] for b in blocks])
        cnt_rows = np.concatenate([b[4] for b in blocks])
        if_rows = np.concatenate([b[5] for b in blocks])
        val_rows = np.concatenate([b[6] for b in blocks])
        lin = slots.astype(np.int64) * self.n_coarse + poss
        if len(np.unique(lin)) != len(lin):
            last = {l: i for i, l in enumerate(lin)}
            keep = np.sort(np.fromiter(last.values(), np.int64))
            slots, poss = slots[keep], poss[keep]
            emb_rows, mem_rows = emb_rows[keep], mem_rows[keep]
            cnt_rows, if_rows = cnt_rows[keep], if_rows[keep]
            val_rows = val_rows[keep]
        self.coarse_valid[slots, poss] = val_rows
        n = len(slots)
        b = pow2_bucket(n, lo=8)
        if b != n:                       # pad = rewrite row 0 in place
            reps = np.zeros((b - n,), np.int32)
            slots = np.concatenate([slots, slots[reps]])
            poss = np.concatenate([poss, poss[reps]])
            emb_rows = np.concatenate([emb_rows, emb_rows[reps]])
            mem_rows = np.concatenate([mem_rows, mem_rows[reps]])
            cnt_rows = np.concatenate([cnt_rows, cnt_rows[reps]])
            if_rows = np.concatenate([if_rows, if_rows[reps]])
        sl, po = jnp.asarray(slots), jnp.asarray(poss)
        self.coarse_emb = _arena_scatter_rows(
            self.coarse_emb, jnp.asarray(emb_rows), sl, po)
        self.coarse_members = _arena_scatter_rows(
            self.coarse_members, jnp.asarray(mem_rows), sl, po)
        self.coarse_member_count, self.coarse_index_frame = \
            _arena_scatter_meta(
                self.coarse_member_count, self.coarse_index_frame,
                jnp.asarray(cnt_rows), jnp.asarray(if_rows), sl, po)
        self.version += 1
        self.io_stats["coarse_appends"] += 1
        self.io_stats["coarse_appended_rows"] += b
        return b

    def _scatter_into(self, bufs: dict, blocks: list) -> Tuple[dict, int]:
        """Apply ``blocks`` to the buffer set ``bufs``: ONE donated
        scatter per super-buffer, with the total row count bucketed
        (padding rows duplicate row 0 — same index, same values, a
        no-op rewrite). An evicting session can wrap within one tick
        and hit the same physical position twice, and the double-buffer
        replay re-applies last tick's blocks before this tick's;
        scatter order over duplicate indices is undefined, so only the
        LAST write per (slot, pos) is kept — which is exactly what
        makes carry+pending composition equal to sequential flushes."""
        slots = np.concatenate([np.full(len(e), s, np.int32)
                                for s, _, e, *_ in blocks])
        poss = np.concatenate([np.arange(p, p + len(e), dtype=np.int32)
                               for _, p, e, *_ in blocks])
        emb_rows = np.concatenate([b[2] for b in blocks])
        mem_rows = np.concatenate([b[3] for b in blocks])
        cnt_rows = np.concatenate([b[4] for b in blocks])
        if_rows = np.concatenate([b[5] for b in blocks])
        lin = slots.astype(np.int64) * self.capacity + poss
        if len(np.unique(lin)) != len(lin):
            last = {l: i for i, l in enumerate(lin)}
            keep = np.sort(np.fromiter(last.values(), np.int64))
            slots, poss = slots[keep], poss[keep]
            emb_rows, mem_rows = emb_rows[keep], mem_rows[keep]
            cnt_rows, if_rows = cnt_rows[keep], if_rows[keep]
        n = len(slots)
        b = pow2_bucket(n, lo=8)
        if b != n:                       # pad = rewrite row 0 in place
            reps = np.zeros((b - n,), np.int32)
            slots = np.concatenate([slots, slots[reps]])
            poss = np.concatenate([poss, poss[reps]])
            emb_rows = np.concatenate([emb_rows, emb_rows[reps]])
            mem_rows = np.concatenate([mem_rows, mem_rows[reps]])
            cnt_rows = np.concatenate([cnt_rows, cnt_rows[reps]])
            if_rows = np.concatenate([if_rows, if_rows[reps]])
        sl, po = jnp.asarray(slots), jnp.asarray(poss)
        out = dict(bufs)
        if self.index_dtype == "int8":
            # quantise ONCE, at the append scatter — scans stream the
            # int8 rows as-is from here on (scale cancels under the
            # kernels' row normalisation; kept for faithful dequant).
            # Pure per-row, so a carry replay re-quantises identically.
            emb_rows, scale_rows = quantise_rows(emb_rows)
            out["emb_scale"] = _arena_scatter_rows(
                bufs["emb_scale"], jnp.asarray(scale_rows), sl, po)
        out["emb"] = _arena_scatter_rows(bufs["emb"],
                                         jnp.asarray(emb_rows), sl, po)
        out["members"] = _arena_scatter_rows(bufs["members"],
                                             jnp.asarray(mem_rows), sl, po)
        out["member_count"], out["index_frame"] = _arena_scatter_meta(
            bufs["member_count"], bufs["index_frame"],
            jnp.asarray(cnt_rows), jnp.asarray(if_rows), sl, po)
        return out, b

    @staticmethod
    def _copy_block(block):
        """Deep-copy a queued block for the carry: ``append`` stores
        VIEWS of the session's host mirrors, which a later ring wrap
        would mutate before the replay lands."""
        s, p, e, m, c, f, w = block
        return (s, p, e.copy(), m.copy(), c.copy(), f.copy(), w)

    def _flush(self, pending: list) -> int:
        """Apply queued blocks; windows apply in queue order, so the
        last block a session queued wins.

        Single-buffer: one donated scatter per super-buffer, straight
        into the live (query-visible) set. Double-buffer: the scatter
        targets the BACK set — last tick's carry replayed first, then
        this tick's pending — and the sets swap, so ingest never
        donates the buffers a concurrent query launch is scanning and
        XLA dispatch overlaps the two instead of serialising. The
        swapped-in front is bitwise the single-buffer result (carry ∘
        pending composes last-write-wins)."""
        if not pending:
            return 0
        if self._back is None:
            bufs = {"emb": self.emb, "members": self.members,
                    "member_count": self.member_count,
                    "index_frame": self.index_frame,
                    "emb_scale": self.emb_scale}
            bufs, b = self._scatter_into(bufs, pending)
        else:
            carry = self._carry
            bufs, b = self._scatter_into(self._back, carry + pending)
            self._back = {"emb": self.emb, "members": self.members,
                          "member_count": self.member_count,
                          "index_frame": self.index_frame,
                          "emb_scale": self.emb_scale}
            self._carry = [self._copy_block(bl) for bl in pending]
            self.io_stats["double_flushes"] += 1
            self.io_stats["carry_rows"] += sum(len(bl[2]) for bl in carry)
        self.emb = bufs["emb"]
        self.members = bufs["members"]
        self.member_count = bufs["member_count"]
        self.index_frame = bufs["index_frame"]
        self.emb_scale = bufs["emb_scale"]
        for slot, _pos, _rows, _m, _c, _f, window in pending:
            self.heads[slot], self.sizes[slot] = window
        self.version += 1
        self.io_stats["appends"] += 1
        self.io_stats["appended_rows"] += b
        return b

    # ----------------------------------------------------------------- views
    def device_sizes(self) -> jnp.ndarray:
        """Per-session sizes (S,) on device (window lengths — pair with
        ``device_windows`` for the ring starts)."""
        if self._sizes_dev is None or self._valid_version != self.version:
            self._refresh_valid()
        return self._sizes_dev

    def device_windows(self) -> jnp.ndarray:
        """(S, 2) int32 ``[head, size]`` ring windows on device — the
        fused scan's ``valid`` operand (masks derive inside the kernel
        wrapper; free slots read ``[0, 0]`` and scan as padding)."""
        if (self._windows_dev is None
                or self._valid_version != self.version):
            self._refresh_valid()
        return self._windows_dev

    def device_valid(self) -> jnp.ndarray:
        """(S, capacity) bool valid mask, derived on device from the
        ring windows and cached per version (no O(S·cap) host traffic —
        only the (S, 2) windows array transfers)."""
        if self._valid_dev is None or self._valid_version != self.version:
            self._refresh_valid()
        return self._valid_dev

    def _refresh_valid(self) -> None:
        self._sizes_dev = jnp.asarray(self.sizes)
        self._windows_dev = jnp.asarray(
            np.stack([self.heads, self.sizes], axis=1).astype(np.int32))
        self._valid_dev = _window_valid_stack(self._windows_dev,
                                              capacity=self.capacity)
        self._valid_version = self.version

    def device_coarse_valid(self) -> jnp.ndarray:
        """(S, n_coarse) bool stage-1 mask for the coarse tier, cached
        per version (coarse validity is sparse and host-authored, so the
        explicit mask form is the canonical valid operand here)."""
        assert self.n_coarse, "arena has no coarse tier"
        if (self._coarse_valid_dev is None
                or self._coarse_valid_ver != self.version):
            self._coarse_valid_dev = jnp.asarray(self.coarse_valid)
            self._coarse_valid_ver = self.version
        return self._coarse_valid_dev

    def has_consolidated(self) -> bool:
        """True iff any lane holds a consolidated summary row — the
        two-stage trigger: until the first consolidation the coarse tier
        is "empty" and every query takes the flat scan unchanged."""
        return bool(self.n_coarse
                    and self.coarse_valid[:, self.n_blocks:].any())


class VenusMemory:
    """Index layer: packed vector store + cluster member reservoirs."""

    def __init__(self, capacity: int, dim: int, member_cap: int = 128,
                 seed: int = 0, *, incremental: bool = True,
                 arena: Optional[MemoryArena] = None,
                 slot: Optional[int] = None,
                 eviction="none", index_dtype: str = "float32",
                 merge_threshold: Optional[float] = None,
                 coarse_capacity: int = 0, coarse_block: int = 64):
        # the exact integer pick (u * cnt) >> U_BITS must fit in int32
        assert member_cap <= (1 << (31 - U_BITS)), member_cap
        self.capacity = capacity
        self.dim = dim
        self.member_cap = member_cap
        self.incremental = incremental
        self.eviction = get_eviction_policy(eviction, merge_threshold)
        # int8 option: host mirrors stay f32 (exact math for merges and
        # host expansion); the DEVICE copy is quantised — arena-backed
        # memories quantise inside the arena's append scatter, detached
        # ones at lazy upload / in-place append. Quantisation is a pure
        # per-row function of the host mirror, so arena and detached
        # device rows are bit-identical for the same contents.
        self.index_dtype = index_dtype
        _index_buf_dtype(index_dtype)          # validate early
        if arena is not None:
            assert arena.index_dtype == index_dtype, \
                (arena.index_dtype, index_dtype)
        # arena-backed: this memory's device rows live inside the shared
        # super-buffers at ``slot`` (appends are donated writes into the
        # arena; nothing is ever lazily uploaded). Detached fallback
        # (arena=None): standalone per-memory device buffers, lazily
        # uploaded on first query and appended in place (PR-1 path).
        self.arena = arena
        self.slot = slot
        if arena is not None:
            assert slot is not None and incremental
            assert (arena.capacity, arena.dim, arena.member_cap) == \
                (capacity, dim, member_cap)
            assert (arena.coarse_capacity, arena.coarse_block) == \
                (coarse_capacity, coarse_block), \
                "memory and arena disagree on coarse-tier geometry"
        # coarse consolidation tier: host-authoritative summary rows.
        # Block summaries ([0, n_blocks)) are computed on demand from
        # the fine mirrors; only the consolidated region keeps host
        # state (running centroid / merged reservoir / frame window).
        self.coarse_capacity = coarse_capacity
        self.coarse_block = coarse_block
        self.n_blocks, self.n_coarse = coarse_rows_for(
            capacity, coarse_capacity, coarse_block)
        if self.n_coarse:
            cc = coarse_capacity
            self._coarse_emb = np.zeros((cc, dim), np.float32)
            self._coarse_members = np.zeros((cc, member_cap), np.int32)
            self._coarse_count = np.zeros((cc,), np.int32)
            self._coarse_ifr = np.zeros((cc,), np.int32)
            self._coarse_weight = np.zeros((cc,), np.int64)
            self._coarse_fid_lo = np.zeros((cc,), np.int64)
            self._coarse_fid_hi = np.zeros((cc,), np.int64)
        self._coarse_csize = 0          # consolidated rows in use
        self._dirty_blocks: set = set()  # fine blocks to re-summarise
        self._emb = np.zeros((capacity, dim), np.float32)
        self._members = np.zeros((capacity, member_cap), np.int32)
        self._member_count = np.zeros((capacity,), np.int32)
        self._index_frame = np.zeros((capacity,), np.int32)
        # per-row trim horizon: the smallest frame id the row references
        # (its index frame and its count-masked members), kept in step
        # with the three tables above so ``min_live_frame`` reads one
        # int32 per row instead of the whole member table
        self._row_lo = np.zeros((capacity,), np.int32)
        self._scene_id = np.zeros((capacity,), np.int32)
        self._size = 0
        self._head = 0          # physical position of the oldest row
        self._rng = np.random.default_rng(seed)
        self._emb_dev: Optional[jnp.ndarray] = None
        self._members_dev: Optional[jnp.ndarray] = None
        self._member_count_dev: Optional[jnp.ndarray] = None
        self._index_frame_dev: Optional[jnp.ndarray] = None
        # version of the cached arena-row views (arena appends donate the
        # super-buffers, so row views must be re-sliced after inserts)
        self._emb_row_ver = -1
        self._members_row_ver = -1
        self._if_row_ver = -1
        self.version = 0               # bumped per insert (stack caching)
        self.io_stats = {"full_uploads": 0, "appended_rows": 0,
                         "member_uploads": 0, "index_frame_uploads": 0,
                         "scans": 0, "host_expand_gathers": 0,
                         "device_expand_gathers": 0,
                         "evicted_rows": 0, "reservoir_merges": 0,
                         "consolidated_rows": 0, "reservoir_sampled": 0}

    def reset_io_stats(self) -> None:
        """Zero the transfer/scan counters in place (the dict identity is
        preserved, so held references keep observing the live counters).
        Benchmarks and tests use this to assert per-phase counts without
        rebuilding the memory."""
        for k in self.io_stats:
            self.io_stats[k] = 0

    # ------------------------------------------------------------- ingestion
    def insert_cluster(self, embedding: np.ndarray, *, scene_id: int,
                       index_frame: int, member_frames: Sequence[int]
                       ) -> int:
        """Insert one indexed vector linked to its cluster members."""
        return int(self.insert_batch(
            np.asarray(embedding, np.float32)[None],
            scene_ids=[scene_id], index_frames=[index_frame],
            member_lists=[member_frames])[0])

    def insert_batch(self, embeddings: np.ndarray, *,
                     scene_ids: Sequence[int],
                     index_frames: Sequence[int],
                     member_lists: Sequence[Sequence[int]]) -> np.ndarray:
        """Insert a batch of indexed vectors in one shot.

        Host mirrors are written vectorised; if the device copy exists
        it is extended in place (no cache invalidation / full
        re-upload). When the batch would overflow ``capacity`` the
        eviction policy decides: ``none`` raises (the historical
        contract), the window policies advance ``head`` over exactly
        as many oldest rows as the batch needs — O(1) pointer motion —
        and the new rows overwrite the evicted physical positions (a
        ring write, split into at most two contiguous runs at the wrap
        point). Returns the physical slots the rows landed in.
        """
        embeddings = np.asarray(embeddings, np.float32)
        n = embeddings.shape[0]
        assert n == len(scene_ids) == len(index_frames) == len(member_lists)
        if n > self.capacity:
            if self.eviction.name == "none":
                raise RuntimeError("memory capacity exhausted")
            # window policies: the batch alone overflows — only its
            # newest `capacity` rows can survive, so the older ones are
            # evicted on arrival (counted like any other eviction; they
            # never reach a reservoir, so cluster_merge cannot fold
            # them either)
            drop = n - self.capacity
            embeddings = embeddings[drop:]
            scene_ids = list(scene_ids)[drop:]
            index_frames = list(index_frames)[drop:]
            member_lists = list(member_lists)[drop:]
            self.io_stats["evicted_rows"] += drop
            n = self.capacity
        overflow = self._size + n - self.capacity
        if overflow > 0:
            self.eviction.evict(self, overflow)   # raises for "none"
        tail = (self._head + self._size) % self.capacity
        ids = np.asarray(index_frames, np.int32)
        scn = np.asarray(scene_ids, np.int32)
        run1 = min(n, self.capacity - tail)
        runs = [(tail, 0, run1)]
        if run1 < n:                               # wrapped ring write
            runs.append((0, run1, n - run1))
        for pos, off, cnt in runs:
            self._emb[pos:pos + cnt] = embeddings[off:off + cnt]
            self._index_frame[pos:pos + cnt] = ids[off:off + cnt]
            self._scene_id[pos:pos + cnt] = scn[off:off + cnt]
        for j, member_frames in enumerate(member_lists):
            members = np.asarray(member_frames, np.int32)
            m = len(members)
            if m > self.member_cap:
                members = members[self._reservoir(members, ids[j])]
                m = self.member_cap
            pj = (tail + j) % self.capacity
            self._members[pj, :m] = members
            self._members[pj, m:] = 0      # no stale ids past the count
            self._member_count[pj] = m
        for pos, _off, cnt in runs:
            self._refresh_row_lo(pos, cnt)
        self._size += n
        self.version += 1
        self._sync_device(runs)
        if self.n_coarse:
            for pos, _off, cnt in runs:
                self._mark_blocks_dirty(pos, cnt)
            self._refresh_block_summaries()
        return (tail + np.arange(n)) % self.capacity

    def _reservoir(self, members: np.ndarray, index_frame: int
                   ) -> np.ndarray:
        """Sorted positions of the ``member_cap`` members an oversized
        cluster keeps: its index frame, when it is a member, plus a
        uniform draw without replacement from the others; a uniform draw
        from all of them otherwise."""
        self.io_stats["reservoir_sampled"] += 1
        m, at = len(members), np.flatnonzero(members == index_frame)
        if not at.size:
            return np.sort(self._rng.choice(m, self.member_cap,
                                            replace=False))
        rest = self._rng.choice(m - 1, self.member_cap - 1, replace=False)
        rest += rest >= at[0]               # skip the index frame's place
        return np.sort(np.append(rest, at[0]))

    def _refresh_row_lo(self, pos: int, cnt: int) -> None:
        """Recompute the trim horizon of physical rows ``[pos, pos+cnt)``
        from their index frames and count-masked members (only the
        columns some row's count reaches are read)."""
        rows = slice(pos, pos + cnt)
        counts = self._member_count[rows]
        lo = self._index_frame[rows].copy()
        k = int(counts.max(initial=0))
        if k:
            live = np.arange(k)[None, :] < counts[:, None]
            np.minimum(lo, np.where(live, self._members[rows, :k],
                                    np.iinfo(np.int32).max).min(1),
                       out=lo)
        self._row_lo[rows] = lo

    def _advance_head(self, need: int) -> None:
        """Sliding-window eviction: drop the ``need`` oldest rows by
        moving the window start — the physical rows stay in place
        (masked invalid by the new window) until the incoming write
        overwrites them, so evicting moves zero bytes."""
        assert 0 <= need <= self._size, (need, self._size)
        if self.n_coarse and need:
            run1 = min(need, self.capacity - self._head)
            self._mark_blocks_dirty(self._head, run1)
            if run1 < need:
                self._mark_blocks_dirty(0, need - run1)
        self._head = (self._head + need) % self.capacity
        self._size -= need
        self.io_stats["evicted_rows"] += need

    # ------------------------------------------------- coarse consolidation
    def _mark_blocks_dirty(self, pos: int, cnt: int) -> None:
        """Fine physical rows ``[pos, pos+cnt)`` changed validity or
        contents — their block summaries must be recomputed."""
        if cnt <= 0:
            return
        lo = pos // self.coarse_block
        hi = (pos + cnt - 1) // self.coarse_block
        self._dirty_blocks.update(range(lo, hi + 1))

    def _refresh_block_summaries(self) -> None:
        """Recompute the summary row (centroid over currently-valid
        fine rows) of every dirty block and push it to the arena's
        coarse tier, riding the tick's deferred scatter. Block
        summaries carry NO reservoir: a stage-1 win on a block expands
        into the block's own fine rows, which carry theirs."""
        if self.arena is None or not self._dirty_blocks:
            self._dirty_blocks.clear()
            return
        cap, blk = self.capacity, self.coarse_block
        idx = np.arange(cap)
        live = ((idx - self._head) % cap) < self._size
        k = self.member_cap
        for b in sorted(self._dirty_blocks):
            rows = slice(b * blk, min((b + 1) * blk, cap))
            v = live[rows]
            any_v = bool(v.any())
            if any_v:
                cen = self._emb[rows][v].mean(0, dtype=np.float64)
                ifr = int(self._index_frame[rows][v][0])
            else:
                cen = np.zeros((self.dim,), np.float64)
                ifr = 0
            self.arena.append_coarse(
                self.slot, b, cen.astype(np.float32)[None],
                np.zeros((1, k), np.int32), np.zeros((1,), np.int32),
                np.asarray([ifr], np.int32), np.asarray([any_v]))
        self._dirty_blocks.clear()

    def _consolidate(self, need: int, threshold: float) -> None:
        """Fold the ``need`` oldest rows into the consolidated region of
        the coarse tier before they leave the fine window: running
        count-weighted centroid, merged member reservoir (evictee's
        index_frame + members, up to ``member_cap``), widened frame
        window. Fold target: the most similar existing summary when its
        cosine clears ``threshold``, a fresh summary row while the
        region has space, else the most similar row unconditionally (a
        full tier degrades to coarser summaries, never to data loss)."""
        if self.n_coarse == 0:
            raise RuntimeError(
                "eviction='consolidate' needs coarse_capacity > 0 "
                "(VenusConfig(coarse_capacity=...))")
        need = min(need, self._size)
        if need <= 0:
            return
        phys = (self._head + np.arange(need)) % self.capacity
        touched = set()
        for pe in phys:
            e = self._emb[pe].astype(np.float64)
            cs = self._coarse_csize
            best, best_sim = -1, -np.inf
            if cs:
                en = e / (np.linalg.norm(e) + 1e-12)
                c = self._coarse_emb[:cs].astype(np.float64)
                cn = c / (np.linalg.norm(c, axis=-1, keepdims=True)
                          + 1e-12)
                best = int(np.argmax(cn @ en))
                best_sim = float(cn[best] @ en)
            cnt_e = int(self._member_count[pe])
            fids = np.concatenate(
                [[int(self._index_frame[pe])],
                 self._members[pe, :cnt_e].astype(np.int64)])
            if best >= 0 and (best_sim >= threshold
                              or cs >= self.coarse_capacity):
                r, w = best, int(self._coarse_weight[best])
                self._coarse_emb[r] = (
                    (self._coarse_emb[r].astype(np.float64) * w + e)
                    / (w + 1)).astype(np.float32)
                self._coarse_weight[r] = w + 1
                ct = int(self._coarse_count[r])
                take = min(len(fids), self.member_cap - ct)
                if take > 0:
                    self._coarse_members[r, ct:ct + take] = fids[:take]
                    self._coarse_count[r] = ct + take
                self._coarse_fid_lo[r] = min(int(self._coarse_fid_lo[r]),
                                             int(fids.min()))
                self._coarse_fid_hi[r] = max(int(self._coarse_fid_hi[r]),
                                             int(fids.max()))
            else:
                r = cs
                self._coarse_csize = cs + 1
                self._coarse_emb[r] = e.astype(np.float32)
                self._coarse_weight[r] = 1
                m = min(len(fids), self.member_cap)
                self._coarse_members[r, :m] = fids[:m]
                self._coarse_members[r, m:] = 0
                self._coarse_count[r] = m
                self._coarse_ifr[r] = int(self._index_frame[pe])
                self._coarse_fid_lo[r] = int(fids.min())
                self._coarse_fid_hi[r] = int(fids.max())
            touched.add(r)
        self.io_stats["consolidated_rows"] += int(need)
        for r in sorted(touched):
            self._resync_coarse(r)

    def _resync_coarse(self, row: int) -> None:
        """Push one consolidated summary row to the arena's coarse tier
        (position offset past the block-summary region)."""
        if self.arena is None:
            return
        self.arena.append_coarse(
            self.slot, self.n_blocks + row,
            self._coarse_emb[row:row + 1],
            self._coarse_members[row:row + 1],
            self._coarse_count[row:row + 1],
            self._coarse_ifr[row:row + 1],
            np.asarray([True]))

    def _merge_into_survivors(self, need: int, threshold: float) -> None:
        """Cluster-merge-aware eviction: before the ``need`` oldest rows
        leave the window, fold each one's member reservoir into its most
        similar SURVIVING index row (cosine ≥ threshold) with spare
        reservoir space, so the merged cluster keeps answering for the
        evicted frames. Host-mirror merge + one re-synced device row per
        modified survivor (coalesced per target)."""
        if need >= self._size:
            return
        cap = self.capacity
        phys = (self._head + np.arange(self._size)) % cap
        ev_phys, sv_phys = phys[:need], phys[need:]

        def _norm(rows):
            return rows / (np.linalg.norm(rows, axis=-1, keepdims=True)
                           + 1e-12)

        sims = _norm(self._emb[ev_phys]) @ _norm(self._emb[sv_phys]).T
        touched = set()
        for i, pe in enumerate(ev_phys):
            j = int(np.argmax(sims[i]))
            if sims[i, j] < threshold:
                continue
            pt = int(sv_phys[j])
            cnt_e = int(self._member_count[pe])
            take = min(cnt_e, self.member_cap
                       - int(self._member_count[pt]))
            if take <= 0:
                continue
            ct = int(self._member_count[pt])
            self._members[pt, ct:ct + take] = self._members[pe, :take]
            self._member_count[pt] = ct + take
            self._row_lo[pt] = min(int(self._row_lo[pt]),
                                   int(self._members[pe, :take].min()))
            self.io_stats["reservoir_merges"] += 1
            touched.add(pt)
        for pt in sorted(touched):
            self._resync_row(pt)

    def _resync_row(self, pos: int) -> None:
        """Push one already-resident row (reservoir merge) back to the
        device copy through the same append paths inserts use."""
        if self.arena is not None:
            self.arena.append(
                self.slot, pos, self._emb[pos:pos + 1],
                self._members[pos:pos + 1],
                self._member_count[pos:pos + 1],
                self._index_frame[pos:pos + 1], self.window)
            return
        if not self.incremental:
            return              # the insert's sync drops the caches anyway
        if self._members_dev is not None:
            self._members_dev, self._member_count_dev = _append_member_rows(
                self._members_dev, self._member_count_dev,
                jnp.asarray(self._members[pos:pos + 1]),
                jnp.asarray(self._member_count[pos:pos + 1]),
                jnp.asarray(pos, jnp.int32))

    def _sync_device(self, runs) -> None:
        """Push freshly written host-mirror runs to the device copy.
        ``runs`` is a list of contiguous ``(pos, off, cnt)`` physical
        row runs (two when a ring write wraps)."""
        if not self.incremental:
            self._emb_dev = None         # seed behaviour: full re-upload
            self._members_dev = None
            self._member_count_dev = None
            self._index_frame_dev = None
            return
        if self.arena is not None:
            # arena-backed: the rows are resident from this point on, no
            # lazy upload ever happens (full_uploads stays 0). Inside a
            # tick's deferred window the arena fuses every session's
            # blocks into one donated scatter per super-buffer.
            for pos, _off, cnt in runs:
                moved = self.arena.append(
                    self.slot, pos, self._emb[pos:pos + cnt],
                    self._members[pos:pos + cnt],
                    self._member_count[pos:pos + cnt],
                    self._index_frame[pos:pos + cnt], self.window)
                self.io_stats["appended_rows"] += moved
            return
        # bucketed padding past the run is only safe while the memory is
        # a plain append-only prefix (head == 0: padded rows land past
        # the valid window, stay masked, and later appends overwrite
        # them before they can become valid — eviction only ever shrinks
        # validity from the head side); once the ring has wrapped
        # (head != 0), "past the run" can hold live rows — append
        # exactly
        plain = self._head == 0
        for pos, _off, cnt in runs:
            b = (min(pow2_bucket(cnt, lo=8), self.capacity - pos)
                 if plain else cnt)
            if self._emb_dev is not None:  # lazy: first query uploads once
                rows = np.zeros((b, self.dim), np.float32)
                rows[:cnt] = self._emb[pos:pos + cnt]
                if self.index_dtype == "int8":
                    rows = quantise_rows(rows)[0]
                self._emb_dev = _append_rows(self._emb_dev,
                                             jnp.asarray(rows),
                                             jnp.asarray(pos, jnp.int32))
                self.io_stats["appended_rows"] += b
            if self._members_dev is not None:
                rows = np.zeros((b, self.member_cap), np.int32)
                rows[:cnt] = self._members[pos:pos + cnt]
                cnts = np.zeros((b,), np.int32)
                cnts[:cnt] = self._member_count[pos:pos + cnt]
                (self._members_dev,
                 self._member_count_dev) = _append_member_rows(
                    self._members_dev, self._member_count_dev,
                    jnp.asarray(rows), jnp.asarray(cnts),
                    jnp.asarray(pos, jnp.int32))
            if self._index_frame_dev is not None:
                rows = np.zeros((b,), np.int32)
                rows[:cnt] = self._index_frame[pos:pos + cnt]
                self._index_frame_dev = _append_id_rows(
                    self._index_frame_dev, jnp.asarray(rows),
                    jnp.asarray(pos, jnp.int32))

    # ----------------------------------------------------------------- query
    @property
    def size(self) -> int:
        return self._size

    @property
    def head(self) -> int:
        """Physical position of the oldest (logical-0) valid row."""
        return self._head

    @property
    def window(self) -> Tuple[int, int]:
        """The ``(head, size)`` ring window every valid mask derives
        from; ``(0, size)`` until the first eviction."""
        return self._head, self._size

    def min_live_frame(self) -> int:
        """Smallest absolute frame id any LIVE row still references —
        the archive-trim horizon for this memory: index_frame ids and
        the count-masked member reservoirs of every row inside the
        current ring window. Reservoirs are consulted FIRST-CLASS, so
        cluster_merge's folded members keep their raw frames reachable
        (and untrimmed) long after their own index row left the window.
        Consolidated summary rows count as live references too: their
        merged reservoirs are what a two-stage query expands, so their
        frame windows pin the archive exactly like fine reservoirs do.
        Each row's part is kept in ``_row_lo``, so this reads one int32
        per live row. An empty memory returns int64-max: it constrains
        nothing."""
        lo = int(np.iinfo(np.int64).max)
        if self._size:             # the window: one or two ring slices
            run1 = min(self._size, self.capacity - self._head)
            lo = int(self._row_lo[self._head:self._head + run1].min())
            if run1 < self._size:
                lo = min(lo, int(self._row_lo[:self._size - run1].min()))
        if self.n_coarse and self._coarse_csize:
            lo = min(lo, int(self._coarse_fid_lo[:self._coarse_csize]
                             .min()))
        return lo

    def detach_from_arena(self) -> None:
        """Sever this memory from its (about to be recycled) arena
        slot. Every previously returned device handle is stale the
        moment the slot is released, so the cached row views are
        dropped; the memory falls back to the detached lazy-upload
        contract over its host mirrors (which it owns and which stay
        correct across the detach)."""
        self.arena = None
        self.slot = None
        self._emb_dev = None
        self._members_dev = None
        self._member_count_dev = None
        self._index_frame_dev = None
        self._emb_row_ver = self._members_row_ver = self._if_row_ver = -1

    def device_index(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(embeddings (cap, d), valid (cap,)) as device arrays.

        Arena-backed: the rows already live on device inside the arena —
        this returns a per-version cached slice of the super-buffer
        (nothing uploads, ``full_uploads`` stays 0). Detached: first call
        uploads the packed host array once; subsequent inserts keep the
        device copy current via ``_append_rows``. NOTE: inserts DONATE
        the current buffer to the in-place append, so a handle returned
        here is invalidated by the next insert — re-call this method
        after inserting rather than holding the arrays."""
        if self.arena is not None:
            # keyed on the ARENA version: appends land at tick-flush
            # time, so that is when row views must refresh
            if (self._emb_dev is None
                    or self._emb_row_ver != self.arena.version):
                self._emb_dev = self.arena.emb[self.slot]
                self._emb_row_ver = self.arena.version
        elif self._emb_dev is None:
            self._emb_dev = jnp.asarray(
                quantise_rows(self._emb)[0]
                if self.index_dtype == "int8" else self._emb)
            self.io_stats["full_uploads"] += 1
        return self._emb_dev, _ring_valid_mask(
            jnp.asarray(self._head, jnp.int32),
            jnp.asarray(self._size, jnp.int32), capacity=self.capacity)

    def search(self, query_emb: jnp.ndarray, *, tau: float
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """query_emb (Q,d) -> (sims (Q,cap), probs (Q,cap)) — Eq. 4+5."""
        emb, valid = self.device_index()
        self.io_stats["scans"] += 1
        return kops.similarity(query_emb, emb, tau=tau, valid=valid)

    # ------------------------------------------------- cluster-level expand
    def members_table(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return jnp.asarray(self._members), jnp.asarray(self._member_count)

    def device_members(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(members (cap, member_cap), counts (cap,)) device-resident.

        Same contract as ``device_index``: arena rows are sliced from the
        super-buffers (no upload); detached buffers upload once on first
        call, then appends keep them current in place (and DONATE the
        buffers, so re-call after inserting rather than holding)."""
        if self.arena is not None:
            if (self._members_dev is None
                    or self._members_row_ver != self.arena.version):
                self._members_dev = self.arena.members[self.slot]
                self._member_count_dev = self.arena.member_count[self.slot]
                self._members_row_ver = self.arena.version
        elif self._members_dev is None:
            self._members_dev = jnp.asarray(self._members)
            self._member_count_dev = jnp.asarray(self._member_count)
            self.io_stats["member_uploads"] += 1
        return self._members_dev, self._member_count_dev

    def device_index_frames(self) -> jnp.ndarray:
        """index_frame ids (cap,) device-resident — the centroid frame id
        of each memory slot, for strategies whose draws map straight to
        indexed frames (top-k / BOLT / MDF / AKS) rather than through the
        member reservoirs. Same contract as ``device_index``: arena rows
        are sliced from the super-buffer (no upload); detached buffers
        upload once, then append in place (donated)."""
        if self.arena is not None:
            if (self._index_frame_dev is None
                    or self._if_row_ver != self.arena.version):
                self._index_frame_dev = self.arena.index_frame[self.slot]
                self._if_row_ver = self.arena.version
        elif self._index_frame_dev is None:
            self._index_frame_dev = jnp.asarray(self._index_frame)
            self.io_stats["index_frame_uploads"] += 1
        return self._index_frame_dev

    @staticmethod
    def expand_u(seed: int, size) -> np.ndarray:
        """The per-slot pick variates u ∈ [0, 2^U_BITS): one int per draw
        slot, a function of (seed, slot) only — every expansion path
        (loop / vectorised / batched / device) consumes this sequence."""
        return np.random.default_rng(seed).integers(
            0, _U_CARD, size=size, dtype=np.int64)

    def expand_draws(self, draws: np.ndarray, valid: np.ndarray,
                     seed: int = 0) -> np.ndarray:
        """Map index draws to frame ids: each draw of index i samples one
        member uniformly from cluster c(oᵢ) (paper §IV-D1). Vectorised
        fixed-shape gather over the members table — one uniform variate
        is consumed per slot (valid or not) so batched and sequential
        paths agree. Returns the deduplicated, time-ordered frame ids."""
        draws = np.atleast_1d(np.asarray(draws))
        valid = np.atleast_1d(np.asarray(valid, bool))
        u = self.expand_u(seed, draws.shape)
        return self._expand_u(draws, valid, u)

    def expand_draws_batch(self, draws: np.ndarray, valid: np.ndarray,
                           seed: int = 0) -> List[np.ndarray]:
        """Batched expansion: draws/valid (Q, n). Each row consumes the
        same per-slot variate sequence as a sequential ``expand_draws``
        call with the same seed, so results match query-for-query."""
        draws = np.asarray(draws)
        valid = np.asarray(valid, bool)
        q, n = draws.shape
        u = np.broadcast_to(self.expand_u(seed, n), (q, n))
        fids, ok = self._expand_u(draws, valid, u, dedup=False)
        return [np.unique(fids[i][ok[i]]) for i in range(q)]

    def expand_draws_device(self, draws: np.ndarray, valid: np.ndarray,
                            seed: int = 0) -> np.ndarray:
        """``expand_draws`` with the reservoir gather on device: a jit'd
        fixed-shape lookup over ``device_members()`` — no host-side
        members-table access; only the (n,) frame ids transfer back."""
        draws = np.atleast_1d(np.asarray(draws, np.int32))
        valid = np.atleast_1d(np.asarray(valid, bool))
        members, counts = self.device_members()
        u = self.expand_u(seed, draws.shape)
        fids, ok = expand_gather(members, counts, jnp.asarray(draws),
                                  jnp.asarray(valid),
                                  jnp.asarray(u, jnp.int32))
        self.io_stats["device_expand_gathers"] += 1
        fids, ok = np.asarray(fids), np.asarray(ok)
        return np.unique(fids[ok].astype(np.int64))

    def _expand_u(self, draws, valid, u, dedup: bool = True):
        self.io_stats["host_expand_gathers"] += 1
        safe = np.clip(draws, 0, self.capacity - 1)
        cnt = self._member_count[safe].astype(np.int64)
        pick = (np.asarray(u, np.int64) * cnt) >> U_BITS
        fids = self._members[safe, pick].astype(np.int64)
        ok = valid & (cnt > 0) & (draws >= 0)
        if dedup:
            return np.unique(fids[ok])
        return fids, ok

    def _expand_draws_loop(self, draws: np.ndarray, valid: np.ndarray,
                           seed: int = 0) -> np.ndarray:
        """Seed-style per-draw loop over the same sampling scheme —
        reference for the vectorised path (kept for tests/benches)."""
        rng = np.random.default_rng(seed)
        out = []
        for i, ok in zip(np.asarray(draws), np.asarray(valid)):
            u = int(rng.integers(0, _U_CARD, dtype=np.int64))
            if not ok or i < 0:
                continue
            cnt = int(self._member_count[int(i)])
            if cnt == 0:
                continue
            out.append(int(self._members[int(i), (u * cnt) >> U_BITS]))
        return np.unique(np.asarray(out, np.int64))

    def index_frames(self, idx: Sequence[int]) -> np.ndarray:
        return self._index_frame[np.asarray(idx, np.int64)]


# ---------------------------------------------------------------------------
# Cross-session stacked view
# ---------------------------------------------------------------------------


class MemoryStack:
    """Padded-stack view over S same-shape ``VenusMemory`` instances.

    Exposes the sessions' device-resident buffers as ``(S, capacity, …)``
    stacks for the fused cross-session query path. Two regimes:

    * **Arena-backed** (the session manager's default): when every
      member memory lives in one ``MemoryArena`` and together they cover
      it exactly (slots 0..S-1 in order), the views ARE the arena
      super-buffers — appends already landed in place, so no
      ingest↔query interleaving ever rebuilds anything and
      ``search`` passes the arena's (S,) sizes straight to the kernel
      wrapper, which derives the valid masks on device.
    * **Detached fallback**: device-side ``jnp.stack`` of the per-memory
      buffers, cached against the members' insert versions — rebuilt
      when any version changes (the PR-2 behaviour). Each rebuild bumps
      ``io_stats`` and, when provided, ``rebuild_stats["stack_rebuilds"]``
      (the session manager passes its own counter dict here so the
      zero-restack invariant is assertable at the manager level).
    """

    def __init__(self, memories: Sequence[VenusMemory], *,
                 rebuild_stats: Optional[dict] = None):
        memories = list(memories)
        assert memories, "empty stack"
        cap, dim, mcap = (memories[0].capacity, memories[0].dim,
                          memories[0].member_cap)
        for m in memories:
            assert (m.capacity, m.dim, m.member_cap) == (cap, dim, mcap), \
                "stacked memories must share capacity/dim/member_cap"
            assert m.index_dtype == memories[0].index_dtype, \
                "stacked memories must share index_dtype"
        self.memories = memories
        self.capacity, self.dim, self.member_cap = cap, dim, mcap
        self.rebuild_stats = rebuild_stats
        arena = getattr(memories[0], "arena", None)
        self._arena: Optional[MemoryArena] = None
        if (arena is not None
                and all(m.arena is arena for m in memories)
                and [m.slot for m in memories] == list(range(len(memories)))):
            self._arena = arena
        self._emb_stack: Optional[jnp.ndarray] = None
        self._valid: Optional[jnp.ndarray] = None
        self._members_stack: Optional[jnp.ndarray] = None
        self._counts_stack: Optional[jnp.ndarray] = None
        self._index_frame_stack: Optional[jnp.ndarray] = None
        self._emb_versions: Optional[Tuple[int, ...]] = None
        self._mem_versions: Optional[Tuple[int, ...]] = None
        self._if_versions: Optional[Tuple[int, ...]] = None
        self.io_stats = {"stack_builds": 0}

    def __len__(self) -> int:
        return len(self.memories)

    def _versions(self) -> Tuple[int, ...]:
        return tuple(m.version for m in self.memories)

    def arena_view(self) -> Optional[MemoryArena]:
        """The arena, iff this stack still covers it exactly (a session
        added to the arena after this stack was built voids coverage —
        the stack then falls back to the detached view path)."""
        a = self._arena
        if a is not None and len(self.memories) == a.n_sessions:
            return a
        return None

    def _count_rebuild(self) -> None:
        if self.rebuild_stats is not None:
            self.rebuild_stats["stack_rebuilds"] = \
                self.rebuild_stats.get("stack_rebuilds", 0) + 1

    # ----------------------------------------------------------- device views
    def device_stack(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(emb (S, cap, d), valid (S, cap)) device arrays."""
        a = self.arena_view()
        if a is not None:
            return a.emb, a.device_valid()
        vers = self._versions()
        if self._emb_stack is None or vers != self._emb_versions:
            self._emb_stack = jnp.stack(
                [m.device_index()[0] for m in self.memories])
            # windows only change with a version bump, so the valid mask
            # is cached alongside — queries between ticks transfer nothing
            wins = jnp.asarray([m.window for m in self.memories],
                               jnp.int32)
            self._valid = _window_valid_stack(wins, capacity=self.capacity)
            self._emb_versions = vers
            self.io_stats["stack_builds"] += 1
            self._count_rebuild()
        return self._emb_stack, self._valid

    def device_members(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(members (S, cap, member_cap), counts (S, cap)) device arrays."""
        a = self.arena_view()
        if a is not None:
            return a.members, a.member_count
        vers = self._versions()
        if self._members_stack is None or vers != self._mem_versions:
            tabs = [m.device_members() for m in self.memories]
            self._members_stack = jnp.stack([t[0] for t in tabs])
            self._counts_stack = jnp.stack([t[1] for t in tabs])
            self._mem_versions = vers
            self._count_rebuild()
        return self._members_stack, self._counts_stack

    def device_index_frames(self) -> jnp.ndarray:
        """index_frame ids (S, cap) device arrays (cached per version)."""
        a = self.arena_view()
        if a is not None:
            return a.index_frame
        vers = self._versions()
        if self._index_frame_stack is None or vers != self._if_versions:
            self._index_frame_stack = jnp.stack(
                [m.device_index_frames() for m in self.memories])
            self._if_versions = vers
            self._count_rebuild()
        return self._index_frame_stack

    # ----------------------------------------------------------------- query
    def search(self, query_emb: jnp.ndarray, *, tau: float
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """query_emb (S, Q, d) -> (sims, probs) (S, Q, cap) — every
        session scanned by ONE fused kernel launch. Arena-backed stacks
        pass the (S, 2) ring windows as ``valid`` — the mask
        materialises on device inside the kernel wrapper."""
        a = self.arena_view()
        if a is not None:
            return kops.similarity_stack(query_emb, a.emb, tau=tau,
                                         valid=a.device_windows(),
                                         mesh=a.mesh, mesh_axis=a.mesh_axis)
        emb, valid = self.device_stack()
        return kops.similarity_stack(query_emb, emb, tau=tau, valid=valid)

    def fused_retrieve(self, query_emb: jnp.ndarray, targets: jnp.ndarray,
                       *, tau: float, n_topk: int) -> "kops.FusedRetrieval":
        """``search``'s one-launch sibling: the same scan operand (arena
        super-buffers or the cached stack) but the draws/top-k resolve
        inside the launch — no (S, Q, cap) score tensor is returned (or,
        on the Pallas backend, ever materialised)."""
        a = self.arena_view()
        if a is not None:
            return kops.fused_retrieve_stack(
                query_emb, a.emb, tau=tau, valid=a.device_windows(),
                targets=targets, n_topk=n_topk,
                mesh=a.mesh, mesh_axis=a.mesh_axis)
        emb, valid = self.device_stack()
        return kops.fused_retrieve_stack(query_emb, emb, tau=tau,
                                         valid=valid, targets=targets,
                                         n_topk=n_topk)


class ArenaStackView:
    """The arena AS the stacked-scan operand: a ``MemoryStack``-shaped
    facade whose lanes are arena SLOTS, not live sessions.

    The session manager hands this to the plan executor whenever a slot
    is free (a closed session awaiting reuse): free slots are padding
    lanes — their windows read ``(0, 0)``, so the device-derived masks
    blank them, and per-lane math keeps every occupied lane
    bit-identical to a subset scan. Nothing is ever built or copied
    here; every view IS an arena super-buffer, so ``stack_builds`` is
    structurally zero."""

    def __init__(self, arena: MemoryArena):
        self.arena = arena
        self.capacity = arena.capacity
        self.dim = arena.dim
        self.member_cap = arena.member_cap
        self.io_stats = {"stack_builds": 0}

    def __len__(self) -> int:
        return self.arena.n_sessions

    def arena_view(self) -> MemoryArena:
        return self.arena

    def device_stack(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.arena.emb, self.arena.device_valid()

    def device_members(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.arena.members, self.arena.member_count

    def device_index_frames(self) -> jnp.ndarray:
        return self.arena.index_frame

    def search(self, query_emb: jnp.ndarray, *, tau: float
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        a = self.arena
        return kops.similarity_stack(query_emb, a.emb, tau=tau,
                                     valid=a.device_windows(),
                                     mesh=a.mesh, mesh_axis=a.mesh_axis)

    def fused_retrieve(self, query_emb: jnp.ndarray, targets: jnp.ndarray,
                       *, tau: float, n_topk: int) -> "kops.FusedRetrieval":
        a = self.arena
        return kops.fused_retrieve_stack(
            query_emb, a.emb, tau=tau, valid=a.device_windows(),
            targets=targets, n_topk=n_topk,
            mesh=a.mesh, mesh_axis=a.mesh_axis)
