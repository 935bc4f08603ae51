"""Seeded camera video: a bounded pool of frames per stream, replayed
cyclically while frame ids keep counting.

Each stream's pool is a run of scenes. A scene is a static background
(a colour drawn over the whole range, a gradient and a fixed texture)
with per-frame sensor noise and, when the mix asks for events, a sprite
whose colour encodes the event, visible and moving during the middle
third of the scene — the structure of the program's ``VideoWorld``,
rendered in bulk. Cuts fall at any frame. Two scenes whose colours lie
close score a soft cut, which segmentation may merge into one
partition, up to the configured maximum length, as a real camera's
would.

Every seed gets the same multiset of scene lengths, spread evenly over
``scene_len`` and dealt to streams in a seeded order, so seeds change
which stream holds which scenes and what they show, not how many frames
a run holds.

Parameters (the mix's ``video`` group): ``resolution``, ``chunk_frames``,
``scene_len`` [lo, hi], ``scenes_per_stream``, ``noise`` (std of the
sensor noise), ``noise_bank`` (distinct noise fields per stream),
``events`` (bool), ``event_types``.
"""

from __future__ import annotations

from typing import List, Mapping

import numpy as np

from vbench.util import rng


def scene_lengths(params: Mapping, n_streams: int, seed: int
                  ) -> List[List[int]]:
    lo, hi = params["scene_len"]
    k = params["scenes_per_stream"]
    lens = np.rint(np.linspace(lo, hi, n_streams * k)).astype(int)
    lens = rng(seed, "scene-lengths").permutation(lens)
    return [list(map(int, lens[s * k:(s + 1) * k])) for s in range(n_streams)]


def render_stream(params: Mapping, lengths: List[int], seed: int,
                  stream: int) -> np.ndarray:
    r = params["resolution"]
    g = rng(seed, "video", stream)
    gx = np.linspace(0, 1, r, dtype=np.float32)[None, :, None]
    gy = np.linspace(0, 1, r, dtype=np.float32)[:, None, None]
    bank = g.normal(0, params["noise"], (params["noise_bank"], r, r, 3)
                    ).astype(np.float32)
    out = np.empty((sum(lengths), r, r, 3), np.float32)
    t = 0
    for length in lengths:
        base = g.random((1, 1, 3), dtype=np.float32) * 0.75
        texture = g.random((r, r, 3), dtype=np.float32) * 0.08
        bg = np.clip(base + 0.25 * gx + 0.15 * gy + texture, 0, 1)
        scene = out[t:t + length]
        scene[:] = bg
        if params["events"]:
            ev = int(g.integers(params["event_types"]))
            hue = ev / params["event_types"]
            sprite = np.array([hue, 1 - hue, 0.5 + 0.5 * hue], np.float32)
            size = max(r // 8, 2)
            lim = r - size
            cx, cy = (int(v) for v in g.integers(0, lim, 2))
            vx, vy = (int(v) for v in g.integers(1, 3, 2))
            w0 = length // 3
            for i in range(w0, w0 + max(length // 3, 4)):
                if i >= length:
                    break
                x = int(lim - abs(lim - ((cx + vx * i) % (2 * lim))))
                y = int(lim - abs(lim - ((cy + vy * i) % (2 * lim))))
                scene[i, y:y + size, x:x + size] = sprite
        scene += bank[(np.arange(t, t + length) % len(bank))]
        np.clip(scene, 0, 1, out=scene)
        t += length
    return out


def make_pools(params: Mapping, n_streams: int, seed: int
               ) -> List[np.ndarray]:
    """One (P, r, r, 3) float32 pool per stream."""
    lens = scene_lengths(params, n_streams, seed)
    return [render_stream(params, lens[s], seed, s)
            for s in range(n_streams)]


def chunk(pool: np.ndarray, tick: int, n: int) -> np.ndarray:
    """Frames [tick*n, (tick+1)*n) of the stream: a view of its pool, or
    a copy where the chunk wraps round the pool's end."""
    lo = (tick * n) % len(pool)
    if lo + n <= len(pool):
        return pool[lo:lo + n]
    return pool[np.arange(lo, lo + n) % len(pool)]
