"""Plain reference of scene segmentation (paper Eq. 1).

phi(t) is the weighted mean absolute change of per-pixel hue,
saturation, lightness and lightness-gradient maps between frames t-1
and t; frame t starts a new partition when phi(t) exceeds the threshold
or the open partition has reached its maximum length.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS = (1.0, 1.0, 1.0, 2.0)        # hue, saturation, lightness, edge


def _features(frame):
    r, g, b = frame[..., 0], frame[..., 1], frame[..., 2]
    mx = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    c = mx - mn
    light = 0.5 * (mx + mn)
    sat = c / (1.0 - jnp.abs(2.0 * light - 1.0) + 1e-6)
    safe = jnp.where(c > 0, c, 1.0)
    hue = jnp.where(mx == r, jnp.mod((g - b) / safe, 6.0),
                    jnp.where(mx == g, (b - r) / safe + 2.0,
                              (r - g) / safe + 4.0)) / 6.0
    hue = jnp.where(c > 0, hue, 0.0)
    dx = jnp.abs(jnp.diff(light, axis=1, prepend=light[:, :1]))
    dy = jnp.abs(jnp.diff(light, axis=0, prepend=light[:1, :]))
    return jnp.stack([hue, sat, light, dx + dy], -1)


@jax.jit
def _pair_scores(prev, cur):
    w = jnp.asarray(WEIGHTS, jnp.float32)
    d = jnp.abs(jax.vmap(_features)(cur) - jax.vmap(_features)(prev))
    hw = cur.shape[1] * cur.shape[2]
    return jnp.einsum("thwc,c->t", d, w) / (jnp.sum(w) * hw)


def pool_scores(pool: np.ndarray, block: int = 16) -> np.ndarray:
    """phi of every frame of a cyclic pool against the frame before it
    (frame 0 against the pool's last frame: the replay's wrap)."""
    out = []
    prev_idx = np.roll(np.arange(len(pool)), 1)
    for i in range(0, len(pool), block):
        sl = slice(i, min(i + block, len(pool)))
        out.append(np.asarray(_pair_scores(jnp.asarray(pool[prev_idx[sl]]),
                                           jnp.asarray(pool[sl]))))
    return np.concatenate(out).astype(np.float64)


def partitions(phi_pool: np.ndarray, n_frames: int, threshold: float,
               max_len: int) -> List[Tuple[int, int]]:
    """Closed partitions [start, end) of the first n_frames frames of a
    stream replaying its pool cyclically."""
    closed, start, since = [], 0, 0
    for t in range(n_frames):
        if t and (phi_pool[t % len(phi_pool)] > threshold
                  or since >= max_len):
            closed.append((start, t))
            start, since = t, 1
        else:
            since += 1
    return closed


def margin(phi_pool: np.ndarray, threshold: float) -> float:
    """How far the pool's scores stay from the threshold."""
    return float(np.min(np.abs(phi_pool - threshold)))


def row_violations(parts: Sequence[Tuple[int, int]], members: Sequence,
                   index_frames: Sequence[int]) -> int:
    """Rows whose member frames do not lie inside one closed partition,
    or whose index frame is not one of its members."""
    starts = np.asarray([s for s, _ in parts], np.int64)
    ends = np.asarray([e for _, e in parts], np.int64)
    bad = 0
    for mem, ifr in zip(members, index_frames):
        mem = np.asarray(mem, np.int64)
        if len(mem) == 0 or ifr not in set(mem.tolist()):
            bad += 1
            continue
        k = np.searchsorted(starts, mem.min(), side="right") - 1
        if k < 0 or mem.max() >= ends[k]:
            bad += 1
    return bad


def empty_partitions(parts: Sequence[Tuple[int, int]],
                     index_frames: Sequence[int]) -> int:
    """Closed partitions no stored row indexes: every closed partition
    is clustered, so each must hold at least one row's index frame —
    each one from the oldest row still in the sliding window on (older
    ones may have been evicted), or every one when no row is there."""
    ifr = np.sort(np.asarray(index_frames, np.int64))
    oldest = ifr[0] if len(ifr) else -1
    return sum(int(np.searchsorted(ifr, s) == np.searchsorted(ifr, e))
               for s, e in parts if e > oldest)
