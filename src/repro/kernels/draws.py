"""Canonical inverse-CDF draw primitives — the ONE definition of a
stochastic retrieval draw, shared verbatim by the materialised reference
path (``core.retrieval``), the jnp fused oracle (``kernels.ref``) and
the fused Pallas epilogue (``kernels.similarity``).

Why chunked: the fused kernel only ever holds one scan block of
probabilities in VMEM, so the draw must be defined over a *chunked* CDF
(DRAW_BLK lanes per chunk, sequential fp32 carry between chunks) that
decomposes into per-block work bit for bit. Within a chunk the prefix
sum is a fixed log-step shift-add scan (Hillis–Steele: eight rounds of
``x + (x shifted right by 2^i, zero-filled)`` for DRAW_BLK = 256);
across chunks the totals fold strictly left to right. Every step is an
elementwise add, a lane roll, a select, or an exact one-lane
extraction, so the same function lowers in Mosaic (the kernel passes
``pltpu.roll``) and in XLA (``jnp.roll``) and gives the same bits on
every platform and under every blocking. ``jnp.cumsum`` is NOT the
definition: its association follows XLA's lowering (an associative
scan on the CPU, ``reduce_window`` on the TPU), and Mosaic cannot
lower it at all.

Variates: one ``jax.random.randint`` in [0, 2^DRAW_U_BITS) per draw —
the same 20-bit integer-variate contract as the member-pick variates in
``core.memory`` (``(u * cnt) >> U_BITS``). The target of draw i is
t_i = (u_i + 0.5) / 2^DRAW_U_BITS ∈ (0, 1); the draw is the first lane
whose CDF exceeds t_i (== the count of lanes with cdf ≤ t_i), clipped
to cap-1 when t_i falls beyond the accumulated total mass (fp32
summation of a softmax can land marginally below 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DRAW_U_BITS = 20
DRAW_U_CARD = 1 << DRAW_U_BITS
DRAW_SHIFT = 8
DRAW_BLK = 1 << DRAW_SHIFT


def draw_targets(key, n: int) -> jnp.ndarray:
    """n inverse-CDF targets in (0, 1). One key consumption."""
    u = jax.random.randint(key, (n,), 0, DRAW_U_CARD)
    return (u.astype(jnp.float32) + 0.5) * jnp.float32(1.0 / DRAW_U_CARD)


def lane_at(x: jnp.ndarray, j) -> jnp.ndarray:
    """Exact extraction of lane ``j`` of the last axis as a (..., 1)
    column: a masked sum over one nonzero term, so the bits are the
    lane's own whatever order the reduction takes (and it lowers in
    Mosaic, where an unaligned one-lane slice may not)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.sum(jnp.where(lane == j, x, 0.0), -1, keepdims=True)


def chunk_cdf(x: jnp.ndarray, carry: jnp.ndarray, roll=jnp.roll
              ) -> jnp.ndarray:
    """The canonical fold over (..., n) probabilities, n a DRAW_BLK
    multiple, with an incoming (..., 1) carry: a log-step inclusive scan
    within each DRAW_BLK-lane chunk, plus the left fold of chunk totals
    started at ``carry``. Returns the (..., n) CDF; the outgoing carry
    is its last lane. The fused kernel calls this per scan block (carry
    in scratch, ``roll=pltpu.roll``); ``blockwise_cdf`` calls it once
    over the whole vector (carry 0) — the fold is sequential, so the
    per-lane CDF bits agree no matter how the lanes are blocked."""
    axis = x.ndim - 1
    n = x.shape[axis]
    assert n % DRAW_BLK == 0, (n, DRAW_BLK)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    pos = lane & (DRAW_BLK - 1)                        # lane within chunk
    chunk = lane >> DRAW_SHIFT
    cc = x
    shift = 1
    while shift < DRAW_BLK:
        cc = cc + jnp.where(pos >= shift, roll(cc, shift, axis), 0.0)
        shift *= 2
    off = carry
    offs = jnp.zeros_like(cc)
    for k in range(n // DRAW_BLK):
        if k:                                          # left fold of totals
            off = off + lane_at(cc, k * DRAW_BLK - 1)
        offs = jnp.where(chunk == k, off, offs)
    return cc + offs


def blockwise_cdf(probs: jnp.ndarray) -> jnp.ndarray:
    """The canonical chunked CDF of a (cap,) probability vector.
    Zero-pads to a DRAW_BLK multiple (flat CDF over pad lanes — exactly
    how the fused kernel's padded scan lanes behave)."""
    cap = probs.shape[0]
    pad = (-cap) % DRAW_BLK
    p = jnp.pad(probs.astype(jnp.float32), (0, pad))
    return chunk_cdf(p, jnp.zeros((1,), jnp.float32))[:cap]


def categorical_from_targets(probs: jnp.ndarray, t: jnp.ndarray
                             ) -> jnp.ndarray:
    """Inverse-CDF categorical draws over a (cap,) probability vector
    for (n,) targets: count of lanes with cdf ≤ t, clipped to cap-1."""
    cap = probs.shape[0]
    cdf = blockwise_cdf(probs)
    cnt = jnp.sum((cdf[None, :] <= t[:, None]).astype(jnp.int32), axis=-1)
    return jnp.clip(cnt, 0, cap - 1).astype(jnp.int32)
