"""Scene-score Pallas kernel — Eq. 1 fused per-frame pipeline.

φ(fᵢ) = ‖w ⊙ (vᵢ − vᵢ₋₁)‖₁ / (‖w‖₁ · H·W),  v = [hue, sat, light, edge]

This runs on *every captured frame* (25–60 FPS × pixels), making it the
ingestion hot spot. TPU-native design: a **sequential grid over frames**
with the previous frame's feature maps carried in VMEM scratch — each
frame is read from HBM exactly once, features are computed and diffed
against the carried maps in a single fused VPU pass, and only the scalar
φ goes back to HBM. (The GPU/OpenCV original recomputes features per
frame on the CPU; see DESIGN.md §3.)

Layout: the wrapper moves channels ahead of the pixel grid — (T, 3, H,
W), H padded to a multiple of 8 and W of 128 — so every feature map is
a plain (H, W) tile of the vector unit (an (H, W, 3) block would put
the 3 channels on the 128-wide lane axis and waste 97% of each tile).
Padded pixels are masked out of the sum; the edge map's neighbour
differences are lane/sublane rolls.

VMEM budget: 2 × 3·H·W input + 4·H·W carried f32 maps ≈ 2.3 MB at 224²,
9 MB at 448². Larger frames would take a row-blocked variant;
ingestion-side Venus frames are embedding-model resolution (≤448²).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _features(r, g, b):
    """(H, W) f32 channels in [0,1] -> [hue, sat, light, edge] maps,
    the same arithmetic as ``ref._hsle`` (the hue's ``mod 6`` is a
    select: (g − b)/c lies in [−1, 1] wherever it is taken)."""
    mx = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    c = mx - mn
    light = 0.5 * (mx + mn)
    sat = c / (1.0 - jnp.abs(2.0 * light - 1.0) + 1e-6)
    safe_c = jnp.where(c > 0, c, 1.0)
    gb = (g - b) / safe_c
    hue = jnp.where(
        mx == r, jnp.where(gb < 0, gb + 6.0, gb),
        jnp.where(mx == g, (b - r) / safe_c + 2.0,
                  (r - g) / safe_c + 4.0)) / 6.0
    hue = jnp.where(c > 0, hue, 0.0)
    row = jax.lax.broadcasted_iota(jnp.int32, light.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, light.shape, 1)
    dx = jnp.where(col == 0, 0.0,
                   jnp.abs(light - pltpu.roll(light, 1, 1)))
    dy = jnp.where(row == 0, 0.0,
                   jnp.abs(light - pltpu.roll(light, 1, 0)))
    return hue, sat, light, dx + dy


def _scene_kernel(f_ref, phi_ref, prev_ref, *, weights, h, w):
    t = pl.program_id(0)
    feats = _features(f_ref[0, 0], f_ref[0, 1], f_ref[0, 2])

    @pl.when(t == 0)
    def _seed():                 # first frame diffs against itself -> φ=0
        for c, f in enumerate(feats):
            prev_ref[c] = f

    row = jax.lax.broadcasted_iota(jnp.int32, feats[0].shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, feats[0].shape, 1)
    inside = jnp.logical_and(row < h, col < w)
    num = jnp.zeros((1, 1), jnp.float32)
    for c, (f, wc) in enumerate(zip(feats, weights)):
        diff = jnp.where(inside, jnp.abs(f - prev_ref[c]), 0.0)
        num = num + wc * jnp.sum(jnp.sum(diff, 0, keepdims=True), 1,
                                 keepdims=True)
        prev_ref[c] = f
    phi = num / (sum(weights) * h * w)
    phi_ref[0] = jnp.broadcast_to(phi, (1, _LANES))


@functools.partial(jax.jit, static_argnames=("weights", "interpret"))
def scene_score(frames: jnp.ndarray,
                weights: Tuple[float, float, float, float],
                *, interpret: bool = True) -> jnp.ndarray:
    """frames: (T,H,W,3) float in [0,1] -> φ (T,) f32; φ[0] = 0."""
    t, h, w, _ = frames.shape
    hp, wp = -(-h // 8) * 8, -(-w // _LANES) * _LANES
    x = jnp.transpose(frames.astype(jnp.float32), (0, 3, 1, 2))
    x = jnp.pad(x, ((0, 0), (0, 0), (0, hp - h), (0, wp - w)))
    kernel = functools.partial(_scene_kernel,
                               weights=tuple(float(v) for v in weights),
                               h=h, w=w)
    phi = pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[pl.BlockSpec((1, 3, hp, wp), lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1, _LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((4, hp, wp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)
    return phi[:, 0, 0]
