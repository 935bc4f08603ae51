"""Plain float32 reference of MEM as configured.

The towers the program runs, written out in straightforward jnp: pre-
norm RMSNorm blocks, causal multi-head attention (RoPE in the text
tower, learned positions added to the patch embeddings in the image
tower), a non-gated tanh-GELU MLP, a final RMSNorm, mean pooling over
real tokens, a linear projection and L2 normalisation. Matmuls run at
``Precision.HIGHEST``, so on a TPU they are float32 and not bfloat16.
It imports nothing of the program; it reads the benchmark's weights.

``lowp=True`` is the control: every matmul operand rounded to fp8
(e4m3, per-tensor scale), one step below the bfloat16 the configuration
states for activations.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

PATCH_SEED = 11          # the frontend stub's fixed projection seed
RMS_EPS = 1e-5
PAD, BOS, EOS, RESERVED = 0, 1, 2, 3


def patch_embed(frames: np.ndarray, patch: int, d_vision: int
                ) -> np.ndarray:
    """(B,H,W,3) frames -> (B, P, d_vision) patch embeddings through the
    fixed seeded projection of raw patches (the configured frontend),
    accumulated in float64."""
    b, h, w, c = frames.shape
    ph, pw = h // patch, w // patch
    x = np.asarray(frames, np.float32)[:, :ph * patch, :pw * patch]
    x = x.reshape(b, ph, patch, pw, patch, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, ph * pw, patch * patch * c)
    proj = np.random.default_rng(PATCH_SEED).normal(
        0, 1.0 / np.sqrt(x.shape[-1]), (x.shape[-1], d_vision)
    ).astype(np.float32)
    return (x.astype(np.float64) @ proj.astype(np.float64)).astype(
        np.float32)


def tokenize(text: str, vocab: int, max_len: int) -> np.ndarray:
    """Word-hash tokens: BOS, blake2s-4 of each lower-cased word mod the
    non-reserved vocabulary, EOS, zero padding to ``max_len``."""
    import hashlib
    ids = [BOS]
    for word in text.lower().split():
        h = int.from_bytes(hashlib.blake2s(word.encode(),
                                           digest_size=4).digest(), "big")
        ids.append(RESERVED + h % (vocab - RESERVED))
    ids = (ids + [EOS])[:max_len]
    out = np.zeros((max_len,), np.int32)
    out[:len(ids)] = ids
    return out


def _mm(a, b, lowp: bool):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * w


def _rope(x, theta: float = 10000.0):
    """x (B,S,H,D): rotate the halves (x1, x2) by position angles."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _tower(p, x, heads: int, rope: bool, lowp: bool):
    b, s, d = x.shape
    hd = d // heads
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        h = _rms(x, w["ln1"]["w"])
        a = w["attn"]
        q = _mm(h, a["wq"], lowp).reshape(b, s, heads, hd)
        k = _mm(h, a["wk"], lowp).reshape(b, s, heads, hd)
        v = _mm(h, a["wv"], lowp).reshape(b, s, heads, hd)
        if rope:
            q, k = _rope(q), _rope(k)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        logits = jnp.where(causal, logits, -1e30)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v,
                         precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(ctx.reshape(b, s, d), a["wo"], lowp)
        h = _rms(x, w["ln2"]["w"])
        m = w["mlp"]
        x = x + _mm(jax.nn.gelu(_mm(h, m["w_up"], lowp), approximate=True),
                    m["w_down"], lowp)
        return x, None

    x, _ = jax.lax.scan(layer, x, p["dense_blocks"])
    return _rms(x, p["final_norm"]["w"])


def _pool_project(h, mask, proj, lowp):
    m = mask.astype(jnp.float32)[..., None]
    pooled = jnp.sum(h * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)
    e = _mm(pooled, proj, lowp)
    return e / jnp.sqrt(jnp.sum(e * e, -1, keepdims=True) + 1e-12)


@functools.partial(jax.jit,
                   static_argnames=("heads", "lowp"))
def encode_image(params, patches, *, heads: int, lowp: bool = False):
    p = params["vision"]
    x = patches + p["pos_embed"][None, :patches.shape[1]]
    h = _tower(p, x, heads, rope=False, lowp=lowp)
    mask = jnp.ones(h.shape[:2], bool)
    return _pool_project(h, mask, params["vision_proj"], lowp)


@functools.partial(jax.jit,
                   static_argnames=("heads", "lowp"))
def encode_text(params, tokens, *, heads: int, lowp: bool = False):
    p = params["text"]
    x = p["embed"][tokens]
    h = _tower(p, x, heads, rope=True, lowp=lowp)
    return _pool_project(h, tokens != PAD, params["text_proj"], lowp)


def embed_texts(params, mem: Mapping, texts, *, lowp: bool = False
                ) -> np.ndarray:
    toks = np.stack([tokenize(t, mem["text"]["vocab_size"],
                              mem["text_max_len"]) for t in texts])
    return np.asarray(encode_text(params, jnp.asarray(toks),
                                  heads=mem["text"]["num_heads"],
                                  lowp=lowp), np.float64)


def embed_frames(params, mem: Mapping, frames: np.ndarray, *,
                 lowp: bool = False, block: int = 8) -> np.ndarray:
    """Reference image embeddings, ``block`` frames at a time."""
    out = []
    for i in range(0, len(frames), block):
        pe = patch_embed(frames[i:i + block], mem["patch"],
                         mem["vision"]["d_model"])
        out.append(np.asarray(encode_image(
            params, jnp.asarray(pe), heads=mem["vision"]["num_heads"],
            lowp=lowp), np.float64))
    return np.concatenate(out)


def cosine_gap(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """1 - cos between rows of a and b, in float64."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    num = np.sum(a * b, -1)
    den = np.linalg.norm(a, axis=-1) * np.linalg.norm(b, axis=-1)
    return 1.0 - num / np.maximum(den, 1e-300)
