"""Flash-decode Pallas kernels: one query token vs a long KV cache.

TPU-native tiling: the KV cache is streamed HBM→VMEM in ``(BLK_S, Hkv, D)``
blocks along the sequence; online-softmax accumulators (running max,
normaliser, weighted value sum) live in VMEM scratch across grid steps.
GQA query groups are packed as an (Hkv·G, D) matrix so the score matmul
hits the MXU. Two variants:

* ``gqa_decode``: scores q·kᵀ over head_dim; accumulates over v. The
  cache block is read as one (BLK_S, Hkv·D) tile (a free reshape), and
  the wrapper spreads each query head into its KV group's D-lane slot
  of an (H, Hkv·D) block-diagonal matrix, so both contractions are
  plain 2-D MXU matmuls; the wrapper then keeps each head's own group
  slot of the (H, Hkv·Dv) context.
* ``mla_decode``: latent (matrix-absorbed) form — scores
  q_abs·ckvᵀ + q_rope·kropeᵀ, accumulates over ckv itself, so per-token
  cache traffic is kv_lora + rope bytes (576 B/token for DeepSeek-V2).

Grid: ``(B, S/BLK_S)`` with the sequence axis sequential ("arbitrary")
so scratch carries across blocks; batch is parallel. The valid mask
travels as (B, 1, S) int32, so its (1, BLK_S) block meets the TPU's
(8, 128) tiling rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLK_S = 512


def _online_update(s, v, m_ref, l_ref, acc_ref):
    """Fold one block of masked (H, BLK) scores and its (BLK, ·) values
    into the running max, normaliser and weighted value sum."""
    m_prev = m_ref[...]                          # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                       # (H, BLK)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _gqa_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                m_ref, l_ref, acc_ref, *, scale, softcap, blocks):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (H, Hkv·D) block-diag
    k = k_ref[0].astype(jnp.float32)             # (BLK, Hkv·D)
    v = v_ref[0].astype(jnp.float32)             # (BLK, Hkv·Dv)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (H, BLK)
    if softcap and softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid_ref[0] != 0, s, NEG_INF)
    _online_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(i == blocks - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "q_per_kv",
                                             "blk_s", "interpret"))
def gqa_decode(q, k, v, valid, *, scale: float, softcap: float = 0.0,
               q_per_kv: int = 1, blk_s: int = DEFAULT_BLK_S,
               interpret: bool = True):
    """q: (B,1,H,D); k/v: (B,C,Hkv,D[v]); valid: (B,C) bool -> (B,1,H,Dv)."""
    b, _, h, d = q.shape
    c = k.shape[1]
    dv = v.shape[-1]
    hkv = k.shape[2]
    blk = min(blk_s, c)
    assert c % blk == 0, (c, blk)
    blocks = c // blk
    # head r reads KV group r // q_per_kv: place q[r] in that group's
    # D-lane slot (zeros elsewhere contribute exact zeros to q·k)
    group = (jnp.arange(h)[:, None] // q_per_kv
             == jnp.arange(hkv)[None, :]).astype(q.dtype)      # (H, Hkv)
    q_bd = (q[:, :, :, None, :] * group[None, None, :, :, None]
            ).reshape(b, 1, h, hkv * d)

    kernel = functools.partial(_gqa_kernel, scale=scale, softcap=softcap,
                               blocks=blocks)
    out = pl.pallas_call(
        kernel,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((1, 1, h, hkv * d), lambda bi, i: (bi, 0, 0, 0)),
            pl.BlockSpec((1, blk, hkv * d), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, blk, hkv * dv), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, 1, blk), lambda bi, i: (bi, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, hkv * dv),
                               lambda bi, i: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, hkv * dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hkv * dv), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q_bd, k.reshape(b, c, hkv * d), v.reshape(b, c, hkv * dv),
      valid.astype(jnp.int32)[:, None, :])
    # each head keeps its own group's Dv slot of the context
    return jnp.sum(out.reshape(b, 1, h, hkv, dv)
                   * group[None, None, :, :, None], axis=3)


# ---------------------------------------------------------------------------
# MLA latent decode
# ---------------------------------------------------------------------------


def _mla_kernel(qa_ref, qr_ref, ckv_ref, kr_ref, valid_ref, o_ref,
                m_ref, l_ref, acc_ref, *, scale, blocks):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qa = qa_ref[0, 0].astype(jnp.float32)        # (H, R)
    qr = qr_ref[0, 0].astype(jnp.float32)        # (H, Dr)
    ckv = ckv_ref[0].astype(jnp.float32)         # (BLK, R)
    kr = kr_ref[0].astype(jnp.float32)           # (BLK, Dr)

    s = (jax.lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)) * scale
    s = jnp.where(valid_ref[0] != 0, s, NEG_INF)     # (H, BLK)
    _online_update(s, ckv, m_ref, l_ref, acc_ref)

    @pl.when(i == blocks - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "blk_s", "interpret"))
def mla_decode(q_abs, q_rope, ckv, krope, valid, *, scale: float,
               blk_s: int = DEFAULT_BLK_S, interpret: bool = True):
    """q_abs: (B,1,H,R); q_rope: (B,1,H,Dr); ckv: (B,C,R); krope: (B,C,Dr);
    valid: (B,C) -> latent ctx (B,1,H,R)."""
    b, _, h, r = q_abs.shape
    c = ckv.shape[1]
    dr = q_rope.shape[-1]
    blk = min(blk_s, c)
    assert c % blk == 0, (c, blk)
    blocks = c // blk

    kernel = functools.partial(_mla_kernel, scale=scale, blocks=blocks)
    return pl.pallas_call(
        kernel,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((1, 1, h, r), lambda bi, i: (bi, 0, 0, 0)),
            pl.BlockSpec((1, 1, h, dr), lambda bi, i: (bi, 0, 0, 0)),
            pl.BlockSpec((1, blk, r), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, blk, dr), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, 1, blk), lambda bi, i: (bi, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, r), lambda bi, i: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, r), q_abs.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q_abs, q_rope, ckv, krope, valid.astype(jnp.int32)[:, None, :])
