"""Fused cosine-similarity scan over the Venus memory index (Eq. 4–5).

The memory index is an (N, d) matrix of MEM embeddings; each query scans
all of it (exact search — see DESIGN.md on why brute-force MXU matmul
replaces FAISS ANN on TPU). The kernel streams the index HBM→VMEM in
(BLK_N, d) blocks, L2-normalises rows in-register, computes the (Q, BLK_N)
cosine block on the MXU, and maintains online max / sum-exp accumulators
so the temperature-softmax denominator (Eq. 5) comes out of the same pass.
The wrapper finishes probs = exp(s/τ − m)/l — an O(N) vector epilogue XLA
fuses with the consumer.

``similarity_scan_stack`` is the cross-session form (``similarity_scan``
is its one-session case): a padded stack of S
session indices ``(S, capacity, d)`` with per-session valid masks and a
per-session query block ``(S, Q, d)`` scanned by ONE program over grid
``(S, capacity/BLK_N)`` — the multi-tenant edge box's whole query tick
in a single kernel launch. Capacities that do not divide the block size
are zero-padded by the wrapper (pad lanes are masked invalid, so they
contribute nothing to the softmax statistics).

Tier-agnostic by design (the hierarchical consolidation tier rides on
this): the same stack kernels scan the FINE arena ``(S, capacity, d)``
and the COARSE summary tier ``(S, n_coarse, d)`` — a coarse stage-1
scan is just a stack launch with a smaller N and the coarse validity
mask, and stage 2 re-enters as a ``(S·Q, B·block, d)`` scan over
gathered candidates. Nothing in this module knows which tier it is
scanning; the stage-1/stage-2 bookkeeping (``coarse_scan_bytes``,
``fine_gather_rows``) lives at the ``kernels.ops`` dispatch layer, and
the orchestration in ``core.tiering``. Since summary centroids are
means of unit rows, the in-register L2 row normalisation below is also
what makes block/consolidated centroids comparable to fine rows under
one cosine — keep it.

Shard-local entry contract (the sharded arena rides on this): every
stack kernel in this module is a pure per-lane program — softmax
statistics, inverse-CDF draw counts, and top-k selections are all
computed within one session lane, and the lane indices they emit are
SESSION-LOCAL. ``kernels.ops`` therefore fans a stack launch out over
mesh shards by calling these very kernels on each shard's contiguous
``(S/K, capacity, ·)`` slot slab inside shard_map, with NO kernel
changes and no global-id rebasing: the sharded result is the
single-device result restricted to the slab, concatenated. Anything
added here must preserve that property (no cross-lane reductions, no
absolute-S-dependent constants) or the arena's shard fan-out breaks.

Layer invariant — what ``valid`` means here: the kernels never trust
row CONTENT, only the mask. Callers may pass the mask in any of the
three canonical forms (explicit ``(S, N)`` bool; ``(S,)`` prefix sizes;
``(S, 2)`` ``[start, size)`` ring windows for sessions under
sliding-window eviction) and it is normalised on device by ONE shared
helper, ``ref.as_valid_mask`` — so stale rows (evicted, recycled-slot,
or block padding) can never leak into the softmax statistics no matter
which path produced the operand. The index/query buffers are borrowed
for the duration of the call: the kernel neither owns nor caches them,
so donation-invalidated handles are the CALLER's problem (re-read views
from the arena after any ingest tick — see ``core.memory``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.draws import DRAW_BLK, chunk_cdf, lane_at
from repro.kernels.ref import as_valid_mask

NEG_INF = -1e30
DEFAULT_BLK_N = 1024
# top-k bookkeeping inside the fused kernel: lane indices travel as f32
# (exact below 2^24, and f32 min/max lane reductions lower everywhere);
# _NO_LANE marks the seed slots, _TAKEN the candidates already selected
_NO_LANE = float(1 << 30)
_TAKEN = float("-inf")


def _lane_mask(valid: jnp.ndarray) -> jnp.ndarray:
    """(..., N) bool -> (..., 1, N) int32: the valid mask's kernel
    layout. A (1, BLK) block of it satisfies the TPU's (8, 128) tiling
    rule whatever the leading extent (a (1, BLK) block over an (S, N)
    array does not), and int32 is a layout Mosaic loads natively."""
    return valid.astype(jnp.int32)[..., None, :]


def _online_stats(logit, m_acc, l_acc):
    """One block of the online softmax: fold (Q, BLK) logits into the
    running (Q, 1) max and sum-exp."""
    m_prev = m_acc[...]
    m_new = jnp.maximum(m_prev, jnp.max(logit, -1, keepdims=True))
    l_acc[...] = l_acc[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logit - m_new), -1, keepdims=True)
    m_acc[...] = m_new


def _normalised_scores(q_ref, x_ref):
    """(Q, d) pre-normalised queries × (BLK, d) rows (fp32 or int8,
    dequantised here) -> (Q, BLK) cosine scores."""
    q = q_ref[0].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)
    xn = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)
    return jax.lax.dot_general(q, xn, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _aligned_blk(n: int, blk_n: int) -> int:
    """Scan block size for an index of n rows. When blk_n is a DRAW_BLK
    multiple (the default path), the block is kept a DRAW_BLK multiple
    too, so the fused epilogue's draw-CDF chunks tile every scan block
    exactly — a requirement for the chunked CDF fold (and therefore the
    draws) to be bit-identical between the fused kernel and the
    materialised path, whatever the capacity. Other block sizes (test
    sweeps) fall back to the legacy min(blk_n, n)."""
    if blk_n % DRAW_BLK == 0:
        return min(blk_n, DRAW_BLK * (-(-n // DRAW_BLK)))
    return min(blk_n, n)


def similarity_scan(query, index, valid, *, tau: float,
                    blk_n: int = DEFAULT_BLK_N, interpret: bool = True):
    """query: (Q,d); index: (N,d); valid: (N,) bool.

    Returns (sims (Q,N), m (Q,1), l (Q,1)) — cosine scores plus the online
    softmax statistics. probs = exp(sims/τ − m) / l on valid entries.
    The one-session case of ``similarity_scan_stack`` (same kernel, S=1).
    """
    sims, m, l = similarity_scan_stack(query[None], index[None],
                                       valid[None], tau=tau, blk_n=blk_n,
                                       interpret=interpret)
    return sims[0], m[0], l[0]


# ---------------------------------------------------------------------------
# Cross-session padded-stack scan
# ---------------------------------------------------------------------------


def _sim_stack_kernel(q_ref, x_ref, valid_ref, sims_ref, m_ref, l_ref,
                      m_acc, l_acc, *, tau, blocks):
    i = pl.program_id(1)                          # block within session s

    @pl.when(i == 0)
    def _init():                                  # fresh stats per session
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    s = _normalised_scores(q_ref, x_ref)          # (Q, BLK)
    sims_ref[0] = s.astype(sims_ref.dtype)
    valid = valid_ref[0] != 0                     # (1, BLK)
    _online_stats(jnp.where(valid, s / tau, NEG_INF), m_acc, l_acc)

    @pl.when(i == blocks - 1)
    def _final():
        m_ref[0] = m_acc[...]
        l_ref[0] = l_acc[...]


@functools.partial(jax.jit, static_argnames=("tau", "blk_n", "interpret"))
def similarity_scan_stack(query, index, valid, *, tau: float,
                          blk_n: int = DEFAULT_BLK_N,
                          interpret: bool = True):
    """query: (S,Q,d); index: (S,N,d); valid: (S,N) bool, (S,) int
    per-session sizes, or (S,2) int ``[start,size)`` ring windows (the
    arena passes windows — a sliding-window session's valid region
    wraps around capacity — and the mask materialises here, inside the
    jit: no host-side mask build, see ``ref.as_valid_mask``).

    One program over all S session indices: grid (S, N/BLK). Returns
    (sims (S,Q,N), m (S,Q,1), l (S,Q,1)); probs = exp(sims/τ − m)/l on
    valid entries, per session. N is zero-padded (invalid lanes) up to a
    block multiple, so any capacity works with any block size.
    """
    sn, qn, d = query.shape
    n = index.shape[1]
    valid = as_valid_mask(valid, n)
    blk = _aligned_blk(n, blk_n)
    pad = (-n) % blk
    if pad:
        index = jnp.pad(index, ((0, 0), (0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    npad = n + pad
    blocks = npad // blk

    q32 = query.astype(jnp.float32)
    qnorm = q32 * jax.lax.rsqrt(
        jnp.sum(q32 * q32, -1, keepdims=True) + 1e-12)

    kernel = functools.partial(_sim_stack_kernel, tau=tau, blocks=blocks)
    sims, m, l = pl.pallas_call(
        kernel,
        grid=(sn, blocks),
        in_specs=[
            pl.BlockSpec((1, qn, d), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, blk, d), lambda s, i: (s, i, 0)),
            pl.BlockSpec((1, 1, blk), lambda s, i: (s, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, qn, blk), lambda s, i: (s, 0, i)),
            pl.BlockSpec((1, qn, 1), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, 1), lambda s, i: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sn, qn, npad), jnp.float32),
            jax.ShapeDtypeStruct((sn, qn, 1), jnp.float32),
            jax.ShapeDtypeStruct((sn, qn, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qn, 1), jnp.float32),
            pltpu.VMEM((qn, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(qnorm, index, _lane_mask(valid))
    return sims[:, :, :n], m, l


# ---------------------------------------------------------------------------
# Fused retrieval scan: draws + top-k inside the launch, no (S,Q,N) output
# ---------------------------------------------------------------------------


def _fused_stack_kernel(q_ref, x_ref, valid_ref, t_ref,
                        cnt_ref, dp_ref, plast_ref, tv_ref, ti_ref,
                        m_ref, l_ref,
                        m_acc, l_acc, carry_acc, cnt_acc, dp_acc,
                        tv_acc, ti_acc,
                        *, tau, blocks, blk, last_blk, last_lane):
    """Two passes over a session's blocks in ONE grid walk (2·blocks
    steps; the index map re-fetches block ``i % blocks``).

    Pass 1 (i < blocks) is the standard online max/sum-exp scan. Pass 2
    revisits the same normalised blocks with the finalised (m, l): each
    block's probabilities ``exp(s/τ − m)/l`` are folded into the
    canonical chunked draw-CDF (``draws.chunk_cdf``, carry in scratch),
    every target accumulates its ``#{cdf ≤ t}`` lane count and its
    crossing-lane probability, and a running top-k merges the block's
    masked scores. Only O(Q·(T+K)) state ever leaves the kernel — the
    (Q, BLK) score tile dies in VMEM.

    Everything here is 2-D over (Q, BLK) or (Q, T|K) with lane
    reductions that keep their dims — the shapes Mosaic lowers. Per
    target and per top-k slot the loops are static (T, K ≤ a budget).
    """
    i = pl.program_id(1)                          # 0 .. 2*blocks-1
    s = _normalised_scores(q_ref, x_ref)          # (Q, BLK)
    valid = valid_ref[0] != 0                     # (1, BLK)
    logit = jnp.where(valid, s / tau, NEG_INF)

    @pl.when(i == 0)
    def _init():                                  # fresh stats per session
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(i < blocks)
    def _pass1():
        _online_stats(logit, m_acc, l_acc)

    @pl.when(i == blocks - 1)
    def _stats_out():
        m_ref[0] = m_acc[...]
        l_ref[0] = l_acc[...]

    @pl.when(i == blocks)
    def _init_epilogue():
        carry_acc[...] = jnp.zeros_like(carry_acc)
        cnt_acc[...] = jnp.zeros_like(cnt_acc)
        dp_acc[...] = jnp.zeros_like(dp_acc)
        tv_acc[...] = jnp.full_like(tv_acc, NEG_INF)
        ti_acc[...] = jnp.full_like(ti_acc, _NO_LANE)

    def _probs():                                 # bit-equal to the
        m = m_acc[...]                            # materialised probs
        l = jnp.maximum(l_acc[...], 1e-30)        # epilogue
        return jnp.exp(logit - m) / l

    @pl.when(i >= blocks)
    def _pass2():
        p = _probs()                              # (Q, BLK)
        carry = carry_acc[...]                    # (Q, 1)
        cdf = chunk_cdf(p, carry, roll=pltpu.roll)
        lane = jax.lax.broadcasted_iota(jnp.int32, cdf.shape, 1)
        prev = jnp.where(lane == 0, carry, pltpu.roll(cdf, 1, 1))
        carry_acc[...] = lane_at(cdf, blk - 1)
        # per target: #{cdf ≤ t} and p at the unique crossing lane
        # (cdf > t and the previous lane's cdf ≤ t)
        t = t_ref[0]                              # (Q, T)
        tlane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        cnt, dp = cnt_acc[...], dp_acc[...]
        for j in range(t.shape[1]):
            tj = lane_at(t, j)                    # (Q, 1)
            le = cdf <= tj
            cross = jnp.logical_and(jnp.logical_not(le), prev <= tj)
            cnt = cnt + jnp.where(tlane == j, jnp.sum(
                le.astype(jnp.float32), -1, keepdims=True), 0.0)
            dp = dp + jnp.where(tlane == j, jnp.sum(
                jnp.where(cross, p, 0.0), -1, keepdims=True), 0.0)
        cnt_acc[...] = cnt
        dp_acc[...] = dp

        # running top-k over (acc ∪ block): K rounds of "largest value,
        # lowest lane among equals" — lax.top_k's order, so the result
        # is the global top-k of the masked scores, ties to the lowest
        # lane
        sv = jnp.where(valid, s, NEG_INF)
        gi = ((i - blocks) * blk + lane).astype(jnp.float32)
        av, ai = tv_acc[...], ti_acc[...]
        klane = jax.lax.broadcasted_iota(jnp.int32, av.shape, 1)
        nv, ni = av, ai
        for k in range(av.shape[1]):
            top = jnp.maximum(jnp.max(sv, -1, keepdims=True),
                              jnp.max(av, -1, keepdims=True))
            at = jnp.minimum(
                jnp.min(jnp.where(sv == top, gi, _NO_LANE), -1,
                        keepdims=True),
                jnp.min(jnp.where(av == top, ai, _NO_LANE), -1,
                        keepdims=True))
            nv = jnp.where(klane == k, top, nv)
            ni = jnp.where(klane == k, at, ni)
            sv = jnp.where(gi == at, _TAKEN, sv)
            av = jnp.where(ai == at, _TAKEN, av)
        tv_acc[...] = nv
        ti_acc[...] = ni

    @pl.when(i == blocks + last_blk)
    def _plast():
        plast_ref[0] = lane_at(_probs(), last_lane)

    @pl.when(i == 2 * blocks - 1)
    def _final():
        cnt_ref[0] = cnt_acc[...].astype(jnp.int32)
        dp_ref[0] = dp_acc[...]
        tv_ref[0] = tv_acc[...]
        ti_ref[0] = ti_acc[...].astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("tau", "n_topk", "blk_n", "interpret"))
def fused_retrieve_scan_stack(query, index, valid, targets, *, tau: float,
                              n_topk: int, blk_n: int = DEFAULT_BLK_N,
                              interpret: bool = True):
    """One-launch fused retrieval over the session stack.

    query: (S,Q,d); index: (S,N,d) fp32 or int8 rows; valid in any
    canonical ``as_valid_mask`` form; targets: (S,Q,T) inverse-CDF draw
    targets in (0,1) (``draws.draw_targets``).

    Returns raw kernel outputs, the fused contract of
    ``ref.fused_retrieve_stack_ref`` — counts (S,Q,T) i32 UNCLIPPED
    ``#{cdf ≤ t}`` lane counts, drawn_p (S,Q,T) f32 crossing-lane
    probabilities (0 where the target overshot the total mass — the
    dispatch substitutes p_last there), p_last (S,Q,1), topk values and
    lane indices (S,Q,K), and the online-softmax stats m, l (S,Q,1).
    No (S,Q,N) tensor exists in HBM at any point.
    """
    sn, qn, d = query.shape
    n = index.shape[1]
    tn = targets.shape[2]
    assert blk_n % DRAW_BLK == 0, (blk_n, DRAW_BLK)
    assert 1 <= n_topk <= n, (n_topk, n)
    valid = as_valid_mask(valid, n)
    blk = _aligned_blk(n, blk_n)
    pad = (-n) % blk
    if pad:
        index = jnp.pad(index, ((0, 0), (0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    npad = n + pad
    blocks = npad // blk

    q32 = query.astype(jnp.float32)
    qnorm = q32 * jax.lax.rsqrt(
        jnp.sum(q32 * q32, -1, keepdims=True) + 1e-12)

    kernel = functools.partial(
        _fused_stack_kernel, tau=tau, blocks=blocks, blk=blk,
        last_blk=(n - 1) // blk, last_lane=(n - 1) % blk)
    xmap = lambda s, i: (s, i % blocks, 0)
    vmap_ = lambda s, i: (s, 0, i % blocks)
    out = pl.pallas_call(
        kernel,
        grid=(sn, 2 * blocks),
        in_specs=[
            pl.BlockSpec((1, qn, d), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, blk, d), xmap),
            pl.BlockSpec((1, 1, blk), vmap_),
            pl.BlockSpec((1, qn, tn), lambda s, i: (s, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qn, tn), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, tn), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, 1), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, n_topk), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, n_topk), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, 1), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, qn, 1), lambda s, i: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sn, qn, tn), jnp.int32),
            jax.ShapeDtypeStruct((sn, qn, tn), jnp.float32),
            jax.ShapeDtypeStruct((sn, qn, 1), jnp.float32),
            jax.ShapeDtypeStruct((sn, qn, n_topk), jnp.float32),
            jax.ShapeDtypeStruct((sn, qn, n_topk), jnp.int32),
            jax.ShapeDtypeStruct((sn, qn, 1), jnp.float32),
            jax.ShapeDtypeStruct((sn, qn, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qn, 1), jnp.float32),
            pltpu.VMEM((qn, 1), jnp.float32),
            pltpu.VMEM((qn, 1), jnp.float32),
            pltpu.VMEM((qn, tn), jnp.float32),
            pltpu.VMEM((qn, tn), jnp.float32),
            pltpu.VMEM((qn, n_topk), jnp.float32),
            pltpu.VMEM((qn, n_topk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(qnorm, index, _lane_mask(valid), targets)
    return out
