"""Distributed Venus memory: the index sharded across the pod.

On a real deployment the edge keeps its own small index, but Venus's
memory also has a *fleet* story (DESIGN.md §5): a site with many cameras
aggregates indexed vectors into one pod-resident memory, sharded over the
``model`` mesh axis. Retrieval is then a shard_map program:

  1. every shard scans its local slice with the fused similarity kernel
     (Eq. 4) — embarrassingly parallel, MXU-bound;
  2. each shard reduces its slice to its local top-M candidates
     (M = n_max, so no recall loss for any budget ≤ n_max);
  3. one small all_gather of (M scores, M global ids) per shard —
     K·M·8 bytes, independent of index size;
  4. the temperature softmax (Eq. 5) + sampling/AKR run on the gathered
     candidate set exactly as in the single-node path.

Exactness: softmax probabilities of the true global top-(≤M) survivors
are identical to the dense computation restricted to them; AKR's mass
accounting is conservative (it can only under-count tail mass it would
never have sampled at θ ≤ the candidate mass). An empty (or
all-invalid) index returns ZERO mass — candidates carry ``probs == 0``
so no downstream sampler can draw garbage ids (a plain softmax over
all-``-1e30`` logits would have handed back a uniform distribution).

Ingestion is batched: a block of rows is round-robined across shards
with ONE scatter per insert call (no per-row ``.at[pos].set``) that
DONATES both sharded operands — the same in-place convention as the
arena's tick scatter, so an insert moves O(rows) bytes, never the full
``(capacity, d)`` buffer (``io_stats["scatter_bytes"]`` counts exactly
what crosses). The global-id → insert-order translation after search is
a vectorised device op rather than a per-candidate host loop.

This module and the arena path (``MemoryArena(mesh=...)`` +
``kernels.ops``' shard_map scan entries) share one substrate: the
``jax.shard_map`` program shape, the ``memory_sharding``
slab placement, and the per-shard-top-M + small-gather retrieval shape.
The arena generalises the (N, d) flat index here to per-session
``(S, capacity, ·)`` lanes; this class remains the flat pod-level
aggregate.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops
from repro.launch.sharding import memory_sharding, mesh_axis_size


@functools.partial(jax.jit, static_argnames=("top_m", "mesh", "mesh_axis"))
def _sharded_scan(query: jnp.ndarray, index: jnp.ndarray,
                  valid: jnp.ndarray, *, top_m: int, mesh,
                  mesh_axis: str = "model"):
    """query (d,) replicated; index (N, d) + valid (N,) sharded on axis 0
    over ``mesh_axis``. Returns (scores (K·M,), ids (K·M,)) gathered."""

    def local(q, idx, val):
        # idx: (N/K, d) local slice
        sims, _ = kops.similarity(q[None], idx, tau=1.0, valid=val)
        s = jnp.where(val, sims[0], -jnp.inf)
        m = min(top_m, s.shape[0])
        top_s, top_i = jax.lax.top_k(s, m)
        shard = jax.lax.axis_index(mesh_axis)
        gids = top_i + shard * s.shape[0]          # global ids
        # per-shard candidates; the sharded out_specs stitch them into
        # (K·M,) arrays — the all-gather happens at the consumer
        return top_s, gids

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(mesh_axis, None), P(mesh_axis)),
        out_specs=(P(mesh_axis), P(mesh_axis)))(query, index, valid)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_rows(emb: jnp.ndarray, valid: jnp.ndarray,
                  rows: jnp.ndarray, pos: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One batched scatter of ``rows`` into slots ``pos`` (+ validity).
    Both sharded operands are DONATED (the arena's donated-scatter
    convention): XLA updates them in place, so an insert moves O(rows)
    bytes instead of copying the whole (capacity, d) buffer per call."""
    return (emb.at[pos].set(rows), valid.at[pos].set(True))


class DistributedVenusMemory:
    """Pod-resident index: batched host inserts, shard_map retrieval."""

    def __init__(self, capacity: int, dim: int, mesh, *,
                 mesh_axis: str = "model", top_m: int = 64):
        k = mesh_axis_size(mesh, mesh_axis)
        assert capacity % k == 0, (capacity, k)
        self.capacity, self.dim = capacity, dim
        self.mesh, self.mesh_axis, self.top_m = mesh, mesh_axis, top_m
        self._emb = jax.device_put(jnp.zeros((capacity, dim), jnp.float32),
                                   memory_sharding(mesh, 2, mesh_axis))
        self._valid = jax.device_put(jnp.zeros((capacity,), bool),
                                     memory_sharding(mesh, 1, mesh_axis))
        self._size = 0
        # what actually crosses host→device per insert: the donated
        # scatter writes only the row block + its validity bits in
        # place, so scatter_bytes is O(rows·dim), independent of
        # capacity — the no-copy assertion tests pin this
        self.io_stats = {"inserts": 0, "scatter_rows": 0,
                         "scatter_bytes": 0, "searches": 0}

    @property
    def size(self) -> int:
        return self._size

    @property
    def _shards(self) -> int:
        return mesh_axis_size(self.mesh, self.mesh_axis)

    def insert(self, embeddings) -> None:
        """Append a batch of indexed vectors (host-side, like FAISS add).

        Round-robins rows across shards so load stays balanced; the whole
        block lands in one scatter instead of a per-row update loop."""
        embeddings = jnp.asarray(embeddings, jnp.float32)
        n = embeddings.shape[0]
        if self._size + n > self.capacity:
            raise RuntimeError("distributed memory capacity exhausted")
        k = self._shards
        per = self.capacity // k
        s = self._size + jnp.arange(n)             # insert orders
        pos = (s % k) * per + s // k               # slot of each row
        self._emb, self._valid = _scatter_rows(self._emb, self._valid,
                                               embeddings, pos)
        self._size += n
        self.io_stats["inserts"] += 1
        self.io_stats["scatter_rows"] += n
        # rows (n·d f32) + validity (n bool) + positions (n int32): the
        # donated in-place update moves nothing else
        self.io_stats["scatter_bytes"] += n * (self.dim * 4 + 1 + 4)

    def insert_orders(self, gids: jnp.ndarray) -> jnp.ndarray:
        """Vectorised global-id → insert-order translation (device op)."""
        per = self.capacity // self._shards
        return (gids % per) * self._shards + gids // per

    def global_id_to_insert_order(self, gid: int) -> int:
        return int(self.insert_orders(jnp.asarray(int(gid))))

    def search(self, query_emb, *, tau: float
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (candidate insert-order ids (K·M,), probs (K·M,)) —
        Eq. 4+5 over the gathered global candidate set.

        The softmax is MASKED: invalid candidate lanes (per-shard top-M
        slots whose score is ±inf/NaN — empty shards, padding past the
        live rows) contribute zero numerator AND are excluded from the
        normaliser, so an empty or all-invalid index returns all-zero
        probabilities instead of a uniform distribution over garbage
        ids. Callers detect "nothing to retrieve" as ``probs.sum() ==
        0`` — no candidate is ever drawable with zero valid mass."""
        self.io_stats["searches"] += 1
        scores, gids = _sharded_scan(
            jnp.asarray(query_emb, jnp.float32), self._emb,
            self._valid, top_m=self.top_m, mesh=self.mesh,
            mesh_axis=self.mesh_axis)
        finite = jnp.isfinite(scores)
        logits = jnp.where(finite, scores / tau, -1e30)
        # max over the finite lanes only; -1e30 for an all-invalid set
        # keeps exp() well-defined (everything hits the `finite` mask)
        e = jnp.where(finite, jnp.exp(logits - jnp.max(logits)), 0.0)
        z = jnp.sum(e)
        probs = jnp.where(z > 0, e / jnp.maximum(z, 1e-30), 0.0)
        return self.insert_orders(gids), probs
