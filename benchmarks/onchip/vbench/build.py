"""Build the system under test for a cell: MEM with the seed's weights,
``SessionManager`` + ``VenusService`` with the configured streams, and
every stream's memory prefilled to capacity.

A 24/7 deployment is always at capacity, so the prefill is set-up the
traffic needs. History rows are seeded unit vectors; each row carries a
cluster of 1-16 member frames on a history timeline of negative frame
ids (the frames before this process started watching), its index frame
in the middle. Rows go in through the program's own insert path,
``VenusMemory.insert_batch`` inside ``MemoryArena.deferred_appends``.
The reference regenerates the same rows from the seed
(``history_rows``).

A query mix may shape the history (its ``history`` group) so that the
softmax over a stream peaks as it does over real footage, where a few
keyframes answer a question and the rest do not: every row is pushed
away from the mean of the mix's texts, and each stream holds a planted
cluster of a few rows at a set cosine to one of the texts. The texts'
embeddings come from the plain float32 tower (``vbench.ref_mem``) on the
seed's weights, so the reference makes the same rows without anything
the program produced. Counts and cosines are the same multiset for every
seed, dealt to streams in a seeded order.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, List, Mapping, Optional, Tuple

import numpy as np

from vbench import mem as vmem
from vbench.util import rng, sub_seed

MEMBER_MAX = 16


@functools.lru_cache(maxsize=None)
def _rows_fn(n: int, d: int):
    import jax
    import jax.numpy as jnp

    def make(key):
        x = jax.random.normal(key, (n, d), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _shaped_fn(n: int, d: int, m: int):
    import jax
    import jax.numpy as jnp

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    def make(key, mean, away, pos, anchor, cos):
        x = unit(jax.random.normal(key, (n, d), jnp.float32))
        x = unit(x - away * mean)
        w = jax.random.normal(jax.random.fold_in(key, 1), (m, d),
                              jnp.float32)
        w = unit(w - jnp.sum(w * anchor, -1, keepdims=True) * anchor)
        planted = cos[:, None] * anchor + jnp.sqrt(1.0 - cos ** 2)[:, None] \
            * w
        return x.at[pos].set(unit(planted))
    return jax.jit(make)


@dataclass(frozen=True)
class Shape:
    """A shaped history: the texts' mean direction and how far rows are
    pushed from it, and per stream the planted cluster's text, row count
    and cosine."""
    mean: np.ndarray
    away: float
    anchors: np.ndarray            # (texts, d)
    text: np.ndarray               # (streams,)
    rows: np.ndarray               # (streams,)
    cos: np.ndarray                # (streams,)


def history_shape(seed: int, params, mem: Mapping, traffic: Mapping,
                  streams: int) -> Optional[Shape]:
    from vbench import ref_mem
    h = traffic.get("history")
    if not h:
        return None
    # the mix's texts through the plain float32 tower, in one batch
    anchors = np.asarray(ref_mem.embed_texts(params, mem, traffic["texts"]),
                         np.float32)
    mean = anchors.mean(0)
    g = rng(seed, "history-shape")
    lo, hi = h["planted_rows"]
    rows = lo + np.arange(streams) % (hi - lo + 1)
    cos = np.linspace(*h["planted_cos"], streams)
    text = np.arange(streams) % len(anchors)
    return Shape(mean / np.linalg.norm(mean), float(h["away"]), anchors,
                 g.permutation(text), g.permutation(rows),
                 g.permutation(cos))


def history_rows(seed: int, stream: int, n: int, d: int, part: int = 0,
                 shape: Optional[Shape] = None):
    """(n, d) float32 unit rows of one stream's history, on the device."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(sub_seed(seed, "history")), stream), part)
    if shape is None or part:
        return _rows_fn(n, d)(key)
    m = int(shape.rows[stream])
    pos = rng(seed, "planted", stream).choice(n, m, replace=False)
    return _shaped_fn(n, d, m)(
        key, jnp.asarray(shape.mean, jnp.float32),
        jnp.float32(shape.away), jnp.asarray(pos, jnp.int32),
        jnp.asarray(shape.anchors[shape.text[stream]], jnp.float32),
        jnp.full((m,), shape.cos[stream], jnp.float32))


def history_meta(seed: int, stream: int, n: int, part: int = 0
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(member counts, first member id, index frame id) per history row.
    Every seed gets the same multiset of cluster sizes; ids count back
    from -1, the newest row holding the newest history frames."""
    cnt = rng(seed, "members", stream, part).permutation(
        np.arange(n) % MEMBER_MAX + 1)
    end = -np.sum(cnt) * part            # later parts sit further back
    first = end - np.cumsum(cnt[::-1])[::-1]
    return cnt, first, first + cnt // 2


def insert_history(mgr, seed: int, stream: int, n: int, part: int = 0,
                   shape: Optional[Shape] = None):
    """Write n history rows into a stream through the program's insert
    path, as one tick's deferred scatter."""
    st = mgr.sessions[stream]
    rows = np.asarray(history_rows(seed, stream, n, mgr.embed_dim, part,
                                   shape))
    cnt, first, ifr = history_meta(seed, stream, n, part)
    members = [np.arange(f, f + c, dtype=np.int64)
               for f, c in zip(first, cnt)]
    with mgr.arena.deferred_appends():
        st.memory.insert_batch(rows, scene_ids=list(np.arange(n) // 8),
                               index_frames=ifr, member_lists=members)


def venus_config(cfg: Mapping):
    from repro.core.session import VenusConfig
    return VenusConfig(**cfg["venus"])


@dataclass
class Built:
    mgr: Any
    svc: Any
    embedder: vmem.RecordingEmbedder
    mem_cfg: Mapping
    streams: int
    shape: Optional[Shape]


def build(cfg: Mapping, seed: int, spans,
          traffic: Optional[Mapping] = None) -> Built:
    from repro.core.pipeline import MEMEmbedder
    from repro.core.session import SessionManager
    from repro.models.mem import MEM
    from repro.serving.venus_service import VenusService
    mem = cfg["mem"]
    with spans.span("setup.weights"):
        params = vmem.make_params(mem, sub_seed(seed, "weights"))
        import jax
        jax.block_until_ready(params)
    model = MEM(vmem.mem_config(mem))
    emb = vmem.RecordingEmbedder(MEMEmbedder(
        model, params, patch=mem["patch"], text_max_len=mem["text_max_len"]))
    mgr = SessionManager(venus_config(cfg), emb, mem["embed_dim"])
    # the VLM answers outside the measured path (the paper's cloud side)
    svc = VenusService(mgr, None, patch=mem["patch"])
    n = cfg["streams"]
    for _ in range(n):
        svc.create_stream()
    cap = cfg["venus"]["memory_capacity"]
    with spans.span("setup.prefill"):
        shape = history_shape(seed, params, mem, traffic or {}, n)
        for s in range(n):
            insert_history(mgr, seed, s, cap, shape=shape)
        jax.block_until_ready(mgr.arena.emb)
    return Built(mgr, svc, emb, mem, n, shape)


def stream_windows(mgr) -> List[Tuple[int, int]]:
    return [tuple(int(v) for v in mgr[s].memory.window)
            for s in sorted(mgr.sessions)]
