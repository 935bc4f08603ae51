"""Pure-jnp oracles for every Pallas kernel.

These are the correctness references: each kernel's test sweeps shapes and
dtypes and asserts allclose against the function here. They are also what
the dispatch layer (``kernels.ops``) runs on the CPU, so the whole system
runs there without Pallas in the loop.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.draws import blockwise_cdf

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# decode attention (GQA)
# ---------------------------------------------------------------------------


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         valid: jnp.ndarray, *, scale: float,
                         softcap: float = 0.0,
                         q_per_kv: int = 1) -> jnp.ndarray:
    """q: (B,1,H,D); k/v: (B,C,Hkv,D); valid: (B or 1, C) -> (B,1,H,D)."""
    b, _, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, 1, hkv, q_per_kv, d).astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgs", qg,
                        k.astype(jnp.float32)) * scale
    if softcap and softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    mask = jnp.broadcast_to(valid, (b, valid.shape[-1]))
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return ctx.reshape(b, 1, h, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (MLA, matrix-absorbed latent form)
# ---------------------------------------------------------------------------


def mla_decode_attention_ref(q_abs: jnp.ndarray, q_rope: jnp.ndarray,
                             ckv: jnp.ndarray, krope: jnp.ndarray,
                             valid: jnp.ndarray, *, scale: float
                             ) -> jnp.ndarray:
    """q_abs: (B,1,H,R); q_rope: (B,1,H,Dr); ckv: (B,C,R);
    krope: (B,C,Dr); valid: (B or 1, C) -> latent context (B,1,H,R)."""
    b, _, h, r = q_abs.shape
    f32 = jnp.float32
    logits = (jnp.einsum("bqhr,bsr->bhs", q_abs.astype(f32),
                         ckv.astype(f32))
              + jnp.einsum("bqhd,bsd->bhs", q_rope.astype(f32),
                           krope.astype(f32))) * scale
    mask = jnp.broadcast_to(valid, (b, valid.shape[-1]))
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, ckv.astype(f32))
    return ctx[:, None].astype(q_abs.dtype)


# ---------------------------------------------------------------------------
# fused cosine-similarity + temperature softmax over the memory index
# ---------------------------------------------------------------------------


def as_valid_mask(valid: jnp.ndarray, n: int) -> jnp.ndarray:
    """Canonical form of a stacked scan's ``valid`` argument. Three
    accepted forms, ONE definition shared by the Pallas wrapper, the
    oracle, and the ops dispatch layer, so the derived-mask semantics
    cannot diverge between them:

    * (S, N) bool mask — explicit per-row validity, passes through;
    * (S,) int sizes — per-session valid prefix ``[0, size)`` (the
      pre-eviction arena form; a window with ``start == 0``);
    * (S, 2) int ``[start, size]`` ring windows — valid rows are
      ``[start, start+size) mod N`` (the eviction path: a session's
      ``head`` advances on device-side sliding-window eviction, so the
      valid region wraps). Masks materialise here, on device — only
      the tiny sizes/window arrays ever cross the host boundary.
    """
    if valid.ndim == 1:
        return jnp.arange(n)[None, :] < valid[:, None]
    if (valid.ndim == 2 and valid.shape[-1] == 2
            and jnp.issubdtype(valid.dtype, jnp.integer)):
        j = jnp.arange(n)[None, :]
        return (j - valid[:, :1]) % n < valid[:, 1:2]
    return valid


def similarity_ref(query: jnp.ndarray, index: jnp.ndarray, *, tau: float,
                   valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """query: (Q,d); index: (N,d); valid: (N,) bool.

    Returns (sims (Q,N) cosine, probs (Q,N) temperature softmax over valid
    entries) — Eq. 4 + Eq. 5 of the paper in one op.
    """
    f32 = jnp.float32
    q = query.astype(f32)
    x = index.astype(f32)
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12)
    xn = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)
    # full fp32 contraction, as in the kernel (the TPU's default for an
    # f32 XLA dot is a single bf16 pass, which would make the oracle the
    # less exact of the two)
    sims = jnp.matmul(qn, xn.T, precision=jax.lax.Precision.HIGHEST)
    logits = jnp.where(valid[None, :], sims / tau, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return sims.astype(query.dtype), probs.astype(f32)


def similarity_stack_ref(query: jnp.ndarray, index: jnp.ndarray, *,
                         tau: float, valid: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-session form: query (S,Q,d); index (S,N,d); valid (S,N)
    bool mask, (S,) int per-session sizes, OR (S,2) int ``[start,size)``
    ring windows (the arena/eviction paths — the mask is derived on
    device here, see ``as_valid_mask``).

    Returns (sims (S,Q,N), probs (S,Q,N)) — per-session Eq. 4 + Eq. 5,
    vmapped so every lane matches ``similarity_ref`` on that session.
    """
    valid = as_valid_mask(valid, index.shape[1])
    fn = lambda q, x, v: similarity_ref(q, x, tau=tau, valid=v)
    return jax.vmap(fn)(query, index, valid)


# ---------------------------------------------------------------------------
# fused retrieval: scan + inverse-CDF draws + running top-k, one pass
# ---------------------------------------------------------------------------


class FusedRetrieveResult(NamedTuple):
    """Everything the retrieval strategies need, with NO (S, Q, N) score
    tensor in the contract: per-target inverse-CDF draw counts and drawn
    probabilities, the running top-k, and the online-softmax stats.

    ``counts`` are RAW lane counts (#{cdf ≤ t}, possibly == the padded
    lane total when t falls beyond the accumulated mass) — the dispatch
    layer clips them to cap-1 and substitutes ``p_last`` (the cap-1
    lane's probability) for the drawn probability in that edge, exactly
    what the materialised path's clipped gather produces."""
    counts: jnp.ndarray         # (S, Q, T) int32 raw cdf≤t lane counts
    drawn_p: jnp.ndarray        # (S, Q, T) f32 prob at the crossing lane
    p_last: jnp.ndarray         # (S, Q, 1) f32 prob of lane cap-1
    topk_v: jnp.ndarray         # (S, Q, K) f32 top-k sims (desc)
    topk_i: jnp.ndarray         # (S, Q, K) int32 top-k lane indices
    m: jnp.ndarray              # (S, Q, 1) f32 online-softmax max
    l: jnp.ndarray              # (S, Q, 1) f32 online-softmax sum-exp
    p_max: jnp.ndarray          # (S, Q, 1) f32 max probability


def fused_retrieve_stack_ref(query: jnp.ndarray, index: jnp.ndarray,
                             valid: jnp.ndarray, targets: jnp.ndarray, *,
                             tau: float, n_topk: int
                             ) -> FusedRetrieveResult:
    """Oracle for the fused retrieval scan: query (S,Q,d), index
    (S,N,d) fp32 or int8, valid in any canonical form, targets (S,Q,T)
    inverse-CDF draw targets.

    The oracle MAY materialise the (S,Q,N) scores internally (it is the
    correctness reference, not the bandwidth path); what it returns is
    exactly the fused kernel's contract. Draws use the canonical chunked
    CDF from ``kernels.draws`` — the same fold the kernel epilogue
    computes blockwise — and top-k matches ``lax.top_k`` over the masked
    scores (value-descending, ties to the lowest lane index).
    """
    n = index.shape[1]
    valid = as_valid_mask(valid, n)
    sims, probs = similarity_stack_ref(query, index, tau=tau, valid=valid)
    counts = jax.vmap(jax.vmap(
        lambda p, t: _raw_counts(p, t)))(probs, targets)
    clipped = jnp.clip(counts, 0, n - 1)
    drawn_p = jnp.take_along_axis(probs, clipped, axis=-1)
    p_last = probs[:, :, n - 1:n]
    masked = jnp.where(valid[:, None, :], sims.astype(jnp.float32),
                       NEG_INF)
    topk_v, topk_sel = jax.lax.top_k(masked, n_topk)
    logits = jnp.where(valid[:, None, :], sims.astype(jnp.float32) / tau,
                       NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    l = jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)
    return FusedRetrieveResult(counts, drawn_p, p_last, topk_v,
                               topk_sel.astype(jnp.int32), m, l,
                               jnp.max(probs, axis=-1, keepdims=True))


def _raw_counts(probs: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Raw (unclipped) inverse-CDF lane counts — ``#{cdf ≤ t}`` over the
    canonical chunked CDF, the quantity the kernel accumulates."""
    cdf = blockwise_cdf(probs)
    return jnp.sum((cdf[None, :] <= t[:, None]).astype(jnp.int32),
                   axis=-1)


# ---------------------------------------------------------------------------
# scene score (Eq. 1): fused HSL+edge frame-difference metric
# ---------------------------------------------------------------------------


def _hsle(frame: jnp.ndarray) -> jnp.ndarray:
    """frame: (H,W,3) float in [0,1] -> (H,W,4) hue/sat/light/edge maps."""
    f32 = jnp.float32
    rgb = frame.astype(f32)
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    mx = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    c = mx - mn
    light = 0.5 * (mx + mn)
    sat = c / (1.0 - jnp.abs(2.0 * light - 1.0) + 1e-6)
    safe_c = jnp.where(c > 0, c, 1.0)
    hue = jnp.where(
        mx == r, jnp.mod((g - b) / safe_c, 6.0),
        jnp.where(mx == g, (b - r) / safe_c + 2.0,
                  (r - g) / safe_c + 4.0)) / 6.0
    hue = jnp.where(c > 0, hue, 0.0)
    # edge map: L1 gradient magnitude of lightness (zero-padded)
    dx = jnp.abs(jnp.diff(light, axis=1, prepend=light[:, :1]))
    dy = jnp.abs(jnp.diff(light, axis=0, prepend=light[:1, :]))
    edge = dx + dy
    return jnp.stack([hue, sat, light, edge], axis=-1)


def scene_score_ref(frames: jnp.ndarray,
                    weights: Tuple[float, float, float, float]
                    ) -> jnp.ndarray:
    """frames: (T,H,W,3) in [0,1] -> phi (T,) per Eq. 1; phi[0] = 0."""
    w = jnp.asarray(weights, jnp.float32)
    feats = jax.vmap(_hsle)(frames)                       # (T,H,W,4)
    diffs = jnp.abs(feats[1:] - feats[:-1])               # (T-1,H,W,4)
    num = jnp.einsum("thwc,c->t", diffs, w)
    hw = frames.shape[1] * frames.shape[2]
    phi = num / (jnp.sum(w) * hw)
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), phi])
