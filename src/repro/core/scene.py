"""Scene detection & segmentation (paper §IV-B1, Eq. 1).

The stream is partitioned where the frame-difference score φ exceeds a
threshold; a *maximum* partition duration handles static cameras (the
paper's "minimum temporal threshold": if no scene change occurs within a
set duration, the period becomes one partition).

Two entry points:
* ``scene_scores`` — φ per frame (Pallas kernel or jnp oracle).
* ``segment`` — boundary decisions as a ``lax.scan`` over φ, carrying the
  frames-since-boundary counter; returns a boundary mask and per-frame
  partition ids so downstream stages stay fixed-shape under jit.

``StreamSegmenter`` is the online state of one stream: its carried last
frame and the rule's counters across chunks. ``segment_streams`` runs
one tick for many streams — one upload and one scene-score launch per
chunk shape, one read of φ, then each stream's rule — and
``StreamSegmenter.ingest`` is the same path for one stream.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

DEFAULT_WEIGHTS = (1.0, 1.0, 1.0, 2.0)       # (hue, sat, light, edge)


def scene_scores(frames: jnp.ndarray,
                 weights: Tuple[float, float, float, float] = DEFAULT_WEIGHTS
                 ) -> jnp.ndarray:
    """frames: (T,H,W,3) float in [0,1] -> φ (T,); φ[0]=0."""
    return kops.scene_score(frames, weights)


def segment(phi: jnp.ndarray, *, threshold: float,
            max_partition_len: int,
            carry_in: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Boundary decision per frame.

    Returns (boundary (T,) bool — True means frame i *starts* a new
    partition; part_id (T,) int32 0-based within this call; carry_out —
    frames since the last boundary after the final frame).
    """
    carry0 = jnp.zeros((), jnp.int32) if carry_in is None else carry_in

    def step(since, p):
        new = (p > threshold) | (since >= max_partition_len)
        since = jnp.where(new, 1, since + 1)
        return since, new

    carry_out, boundary = jax.lax.scan(step, carry0, phi)
    # frame 0 with no carry begins partition 0 implicitly
    boundary = boundary.at[0].set(boundary[0] | (carry0 == 0))
    part_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    part_id = jnp.maximum(part_id, 0)
    return boundary, part_id, carry_out


@dataclass
class Partition:
    """A closed scene partition: [start, end) absolute frame indices."""
    start: int
    end: int


@dataclass
class StreamSegmenter:
    threshold: float = 0.08
    max_partition_len: int = 256
    weights: Tuple[float, float, float, float] = DEFAULT_WEIGHTS

    _since: int = 0
    _open_start: int = 0
    _abs: int = 0
    _started: bool = False
    # the last frame ingested, on the device in ``kops.lane_dense`` form
    _last_frame: Optional[jax.Array] = None

    def ingest(self, frames) -> List[Partition]:
        """Consume a chunk (T,H,W,3); return partitions closed by it."""
        return segment_streams([self], [frames])[0]

    def flush(self) -> List[Partition]:
        if self._started and self._abs > self._open_start:
            part = [Partition(self._open_start, self._abs)]
            self._open_start = self._abs
            return part
        return []


def segment_streams(segmenters: Sequence[StreamSegmenter],
                    chunks: Sequence[np.ndarray],
                    io_stats: Optional[Dict[str, int]] = None
                    ) -> List[List[Partition]]:
    """One tick of segmentation over several streams: ``chunks[i]``
    (T,H,W,3) goes to ``segmenters[i]``; returns each one's closed
    partitions.

    Chunks of one (T, H, W) and weights go up in one ``device_put`` in
    ``kops.lane_dense`` form and are scored by one
    ``kops.scene_score_streams`` launch, each against its stream's
    carried last frame, which stays on the device; every launch's φ
    comes back in one read. A stream with no frame before this chunk
    scores its first frame against zeros, and the rule ignores that φ.
    ``io_stats``, when given, counts the launches
    (``scene_score_launches``) and the streams they scored
    (``scene_score_streams``)."""
    groups: Dict[tuple, List[int]] = {}
    for i, chunk in enumerate(chunks):
        if len(chunk):
            key = (tuple(np.shape(chunk)[:3]), tuple(segmenters[i].weights))
            groups.setdefault(key, []).append(i)
    launched = []
    for (shape, weights), idx in groups.items():
        dense = jax.device_put([kops.lane_dense(chunks[i]) for i in idx])
        rows = dense[0].shape[0] // shape[0]
        prev = [segmenters[i]._last_frame for i in idx]
        prev = [jnp.zeros((rows, dense[0].shape[1]), jnp.float32)
                if p is None else p for p in prev]
        phi, last = kops.scene_score_streams(prev, dense, shape, weights)
        for i, frame in zip(idx, last):
            segmenters[i]._last_frame = frame
        launched.append((idx, phi))
    phis = jax.device_get([phi for _, phi in launched])
    closed: List[List[Partition]] = [[] for _ in segmenters]
    for (idx, _), phi in zip(launched, phis):
        _apply_rule([segmenters[i] for i in idx], phi,
                    [closed[i] for i in idx])
    if io_stats is not None:
        io_stats["scene_score_launches"] += len(launched)
        io_stats["scene_score_streams"] += sum(len(i) for i, _ in launched)
    return closed


def _apply_rule(segs: Sequence[StreamSegmenter], phi: np.ndarray,
                closed: Sequence[List[Partition]]) -> None:
    """The boundary rule over φ (S, T), one row a stream, frame by frame
    across all rows at once: frame t starts a partition when its stream
    has a frame before it and φ exceeds the threshold, or the open
    partition already holds ``max_partition_len`` frames. Appends each
    stream's closed partitions to ``closed`` and advances its state."""
    # float32 thresholds: φ is compared in its own precision
    threshold = np.asarray([s.threshold for s in segs], phi.dtype)
    max_len = np.asarray([s.max_partition_len for s in segs])
    since = np.asarray([s._since for s in segs])
    started = np.asarray([s._started for s in segs])
    cut = np.zeros(phi.shape, bool)
    for t in range(phi.shape[1]):
        cut[:, t] = started & ((phi[:, t] > threshold) | (since >= max_len))
        since = np.where(cut[:, t], 1, since + 1)
        started[:] = True
    for seg, row, out, n in zip(segs, cut, closed, since):
        for t in np.flatnonzero(row) + seg._abs:
            out.append(Partition(seg._open_start, int(t)))
            seg._open_start = int(t)
        seg._since = int(n)
        seg._started = True
        seg._abs += phi.shape[1]
