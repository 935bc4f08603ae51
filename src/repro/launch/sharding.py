"""Sharding rules: pytree paths → PartitionSpecs.

One rule table covers every architecture because param names are uniform
across families (DESIGN.md §5):

* **TP (model axis)** — attention heads (wq/wk/wv out, wo in), FFN hidden
  (w_up/w_gate out, w_down in), MoE experts (leading E dim), MLA
  up-projections, Mamba2 head-dim projections (in_x/in_dt/conv_x,
  out_proj in), RWKV head projections, vocab (embed rows / lm_head cols).
* **FSDP (data axes, train mode only)** — the remaining large dim of each
  weight is sharded over ("pod",)+("data",); serving replicates weights
  over data (no optimizer state; keeps all-gathers off the decode path).
* **Caches** — batch over data; KV heads over model when divisible, else
  the cache *sequence* over model (glm4's kv=2 < 16; also the long_500k
  context-parallel path). SSM/RWKV states shard heads over model.
* Any dim not divisible by its axis size falls back to replication
  (sanitiser), so odd vocabs (whisper 51865, minicpm3 73448) still lower.

``logical`` specs are right-aligned: stacked layer dims (leading L) are
padded with None automatically.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# sentinel for "the FSDP axis" — resolved per mode/mesh
FSDP = "__fsdp__"
MODEL = "model"


def mesh_axis_size(mesh, axis: str = MODEL) -> int:
    """Shard count of ``axis`` on ``mesh`` (1 when mesh is None or the
    axis is absent) — the K every sharded-memory path branches on."""
    if mesh is None:
        return 1
    return dict(mesh.shape).get(axis, 1)


def memory_sharding(mesh, ndim: int, axis: str = MODEL) -> NamedSharding:
    """Placement of a ``(S, …)`` memory super-buffer: the leading slot
    axis is split into contiguous per-device slabs over ``axis``, every
    trailing dim replicated. The arena places its ``(S, capacity, ·)``
    buffers with this, and the shard_map scan entries consume the same
    spec — slot slabs never move between placement and scan."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))

# (path regex, right-aligned logical spec)
_PARAM_RULES: Sequence[Tuple[str, Tuple]] = (
    # embeddings / heads — vocab-parallel with REPLICATED d (§Perf iter E):
    # sharding d over data makes the (tied) LM head a partial-sum
    # contraction, all-reducing full f32 (B,S,V) logits (observed 38 GB
    # per step on minicpm3). Replicating d keeps the head matmul local
    # with logits sharded over model; the optimizer-state cost is only
    # V·d/|model| per device.
    (r"embed$", (MODEL, None)),
    (r"pos_embed$", (None, FSDP)),
    (r"lm_head$", (None, MODEL)),
    # MoE experts: (E, d, ff) — expert parallel over model
    (r"moe/w_(gate|up)$", (MODEL, FSDP, None)),
    (r"moe/w_down$", (MODEL, None, FSDP)),
    (r"moe/router$", (FSDP, None)),
    (r"moe/shared/w_(gate|up)$", (FSDP, MODEL)),
    (r"moe/shared/w_down$", (MODEL, FSDP)),
    # MLA
    (r"w_dq$", (FSDP, None)),
    (r"w_dkv$", (FSDP, None)),
    (r"w_kr$", (FSDP, None)),
    (r"w_uq$", (FSDP, MODEL)),
    (r"w_uk$", (FSDP, MODEL)),
    (r"w_uv$", (FSDP, MODEL)),
    # attention + generic MLP (also whisper cross-attn)
    (r"(wq|wk|wv)$", (FSDP, MODEL)),
    (r"wo$", (MODEL, FSDP)),
    (r"w_(gate|up)$", (FSDP, MODEL)),
    (r"w_down$", (MODEL, FSDP)),
    # Mamba2
    (r"in_(z|x)$", (FSDP, MODEL)),
    (r"in_dt$", (FSDP, MODEL)),
    (r"in_bc$", (FSDP, None)),
    (r"conv_x_w$", (None, MODEL)),
    (r"conv_x_b$", (MODEL,)),
    (r"out_proj$", (MODEL, FSDP)),
    # RWKV6
    (r"(wr|wg)$", (FSDP, MODEL)),
    (r"cm_wk$", (FSDP, MODEL)),
    (r"cm_wv$", (MODEL, FSDP)),
    (r"cm_wr$", (FSDP, None)),
    (r"decay_w1$", (FSDP, None)),
    (r"decay_w2$", (None, MODEL)),
    (r"maa_w1$", (FSDP, None)),
    (r"ln_scale$", (MODEL, None)),
    (r"bonus_u$", (MODEL, None)),
)

_CACHE_RULES: Sequence[Tuple[str, Tuple]] = (
    # decided dynamically for k/v/ckv/krope (head vs sequence sharding)
    (r"(^|/)pos$", ("__batch__",)),
    (r"mrope_delta$", ("__batch__",)),
    (r"enc_out$", ("__batch__", None, None)),
    (r"ssm$", ("__batch__", MODEL, None, None)),
    (r"conv_x$", ("__batch__", None, MODEL)),
    (r"conv_bc$", ("__batch__", None, None)),
    (r"wkv$", ("__batch__", MODEL, None, None)),
    (r"shift_(tm|cm)$", ("__batch__", None)),
)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _sanitize(spec: Tuple, shape: Tuple[int, ...], mesh) -> P:
    """Right-align the logical spec to the shape's rank and drop axes that
    do not divide the dim size."""
    spec = tuple(spec)
    pad = len(shape) - len(spec)
    full = (None,) * pad + spec
    sizes = dict(mesh.shape)
    out = []
    for dim, ax in zip(shape, full):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = int(np.prod([sizes[a] for a in axes]))
        out.append(ax if dim % n == 0 and dim >= n else None)
    return P(*out)


def _resolve(spec: Tuple, fsdp_axes: Optional[Tuple[str, ...]],
             batch_axes: Tuple[str, ...]) -> Tuple:
    def one(s):
        if s == FSDP:
            return tuple(fsdp_axes) if fsdp_axes else None
        if s == "__batch__":
            return tuple(batch_axes) if batch_axes else None
        if isinstance(s, tuple):       # combined axes, e.g. (FSDP, MODEL)
            flat = []
            for t in s:
                r = one(t)
                if r is None:
                    continue
                flat.extend(r if isinstance(r, tuple) else (r,))
            return tuple(flat) if flat else None
        return s
    return tuple(one(s) for s in spec)


# §Perf iteration F: ZeRO-3-style FSDP placement. The base rules put the
# FSDP axes on the weights' contraction dim, which GSPMD resolves as
# partial-sum ALL-REDUCES of full activations (observed ~8 GB/layer f32 on
# minicpm3 train). Co-sharding FSDP *with* the model axis on the already-
# TP-sharded dim turns that into small per-use weight all-gathers
# (37 MB/layer) — the classic ZeRO-3 trade. Enabled via mode="train_zero3".
_ZERO3_OVERRIDES: Sequence[Tuple[str, Tuple]] = (
    (r"moe/", None),                      # keep expert-parallel rules
    (r"(wq|wk|wv|wr|wg)$", (None, (FSDP, MODEL))),
    (r"w_(gate|up)$", (None, (FSDP, MODEL))),
    (r"w_u(q|k|v)$", (None, (FSDP, MODEL))),
    (r"in_(z|x)$", (None, (FSDP, MODEL))),
    (r"in_dt$", (None, (FSDP, MODEL))),
    (r"cm_wk$", (None, (FSDP, MODEL))),
    (r"decay_w2$", (None, (FSDP, MODEL))),
    (r"wo$", ((MODEL, FSDP), None)),
    (r"w_down$", ((MODEL, FSDP), None)),
    (r"out_proj$", ((MODEL, FSDP), None)),
    (r"cm_wv$", ((MODEL, FSDP), None)),
)


def param_specs(params_shape, mesh, *, mode: str) -> Any:
    """mode: "train" (FSDP×TP), "train_zero3" (iter F), or "serve"
    (TP only, replicated over data)."""
    from repro.launch.mesh import data_axes
    daxes = data_axes(mesh)
    fsdp = daxes if mode.startswith("train") else None
    zero3 = mode == "train_zero3"

    def one(path, leaf):
        ps = path_str(path)
        if zero3:
            for pat, spec in _ZERO3_OVERRIDES:
                if re.search(pat, ps):
                    if spec is None:
                        break            # fall through to base rules
                    return NamedSharding(
                        mesh, _sanitize(_resolve(spec, fsdp, daxes),
                                        leaf.shape, mesh))
        for pat, spec in _PARAM_RULES:
            if re.search(pat, ps):
                return NamedSharding(
                    mesh, _sanitize(_resolve(spec, fsdp, daxes),
                                    leaf.shape, mesh))
        return NamedSharding(mesh, P())          # norms, scalars: replicate

    return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_specs(cache_shape, mesh) -> Any:
    """Decode caches: batch over data; KV heads over model if divisible,
    else sequence over model (context parallelism)."""
    from repro.launch.mesh import data_axes
    daxes = data_axes(mesh)
    msize = dict(mesh.shape)[MODEL]

    def one(path, leaf):
        ps = path_str(path)
        for pat, spec in _CACHE_RULES:
            if re.search(pat, ps):
                return NamedSharding(
                    mesh, _sanitize(_resolve(spec, None, daxes),
                                    leaf.shape, mesh))
        if re.search(r"(^|/)(k|v)$", ps):
            # (L, B, C, Hkv, D)
            hkv = leaf.shape[-2]
            if hkv % msize == 0:
                spec = (None, daxes, None, MODEL, None)
            else:
                spec = (None, daxes, MODEL, None, None)  # seq sharding
            return NamedSharding(mesh, _sanitize(spec, leaf.shape, mesh))
        if re.search(r"(ckv|krope)$", ps):
            # (L, B, C, R): latent cache — shard the sequence
            spec = (None, daxes, MODEL, None)
            return NamedSharding(mesh, _sanitize(spec, leaf.shape, mesh))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def batch_specs(batch_shape, mesh) -> Any:
    """Input batches: leading batch dim over data axes."""
    from repro.launch.mesh import data_axes
    daxes = data_axes(mesh)

    def one(path, leaf):
        spec = (tuple(daxes),) + (None,) * (len(leaf.shape) - 1)
        if len(leaf.shape) == 0:
            spec = ()
        return NamedSharding(mesh, _sanitize(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, batch_shape)


def opt_specs(opt_shape, pspecs) -> Any:
    """AdamW state: count replicated; mu/nu follow the param specs."""
    mesh = jax.tree.leaves(pspecs)[0].mesh
    return type(opt_shape)(
        NamedSharding(mesh, P()), pspecs, pspecs)
