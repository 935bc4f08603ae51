"""Sharding rule tests on an abstract 16×16 production mesh (no devices
needed) + a real 1-device lowering of the serve path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import registry
from repro.launch import sharding as shd
from repro.launch.specs import adapt_config, input_specs, params_shape
from repro.configs.base import get_shape


def _mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _spec_of(specs, *path_parts):
    node = specs
    for p in path_parts:
        node = node[p]
    return node.spec


def test_attention_tp_fsdp_layout():
    cfg = registry.get_config("glm4-9b")
    ps = params_shape(cfg)
    specs = shd.param_specs(ps, _mesh(), mode="train")
    s = _spec_of(specs, "dense_blocks", "attn", "wq")
    assert s == P(None, ("data",), "model")      # (L, d, H*hd)
    s = _spec_of(specs, "dense_blocks", "attn", "wo")
    assert s == P(None, "model", ("data",))
    s = _spec_of(specs, "embed")
    assert s == P("model", None)     # vocab-parallel, d replicated (iter E)


def test_serve_mode_drops_fsdp():
    cfg = registry.get_config("glm4-9b")
    ps = params_shape(cfg)
    specs = shd.param_specs(ps, _mesh(), mode="serve")
    assert _spec_of(specs, "dense_blocks", "attn", "wq") == P(None, None,
                                                              "model")


def test_moe_expert_parallel():
    cfg = registry.get_config("olmoe-1b-7b")
    ps = params_shape(cfg)
    specs = shd.param_specs(ps, _mesh(), mode="train")
    s = _spec_of(specs, "moe_blocks", "moe", "w_gate")   # (L, E, d, ff)
    assert s == P(None, "model", ("data",), None)


def test_nondivisible_vocab_falls_back():
    cfg = registry.get_config("whisper-base")            # vocab 51865
    ps = params_shape(cfg)
    specs = shd.param_specs(ps, _mesh(), mode="train")
    assert _spec_of(specs, "embed") == P(None, None)


def test_multipod_fsdp_spans_pod_and_data():
    cfg = registry.get_config("deepseek-7b")
    ps = params_shape(cfg)
    specs = shd.param_specs(ps, _mesh(multi=True), mode="train")
    assert _spec_of(specs, "dense_blocks", "attn", "wq") == \
        P(None, ("pod", "data"), "model")


def test_kv_cache_head_vs_sequence_sharding():
    shape = get_shape("decode_32k")
    # glm4: kv=2 < 16 ⇒ sequence sharding
    cfg = adapt_config(registry.get_config("glm4-9b"), shape)
    cache = input_specs(cfg, shape)["cache"]
    specs = shd.cache_specs(cache, _mesh())
    assert specs["dense"]["k"].spec == P(None, ("data",), "model", None,
                                         None)
    # deepseek-7b: kv=32 ⇒ head sharding
    cfg = adapt_config(registry.get_config("deepseek-7b"), shape)
    cache = input_specs(cfg, shape)["cache"]
    specs = shd.cache_specs(cache, _mesh())
    assert specs["dense"]["k"].spec == P(None, ("data",), None, "model",
                                         None)


def test_long500k_policy():
    shape = get_shape("long_500k")
    # dense GQA gets the sliding-window variant
    cfg = adapt_config(registry.get_config("deepseek-7b"), shape)
    assert cfg.sliding_window == 8192
    # MLA keeps the full latent cache
    cfg = adapt_config(registry.get_config("deepseek-v2-lite-16b"), shape)
    assert cfg.sliding_window == 0
    cache = input_specs(cfg, shape)["cache"]
    assert cache["moe"]["ckv"].shape[2] == shape.seq_len
    # SSM native
    cfg = adapt_config(registry.get_config("rwkv6-1.6b"), shape)
    assert cfg.sliding_window == 0


def test_batch_specs_long500k_batch1_replicated():
    shape = get_shape("long_500k")
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    spec = shd.batch_specs(tok, _mesh())
    assert spec.spec == P(None, None)    # batch 1 cannot shard over 16


def test_serve_step_lowers_on_host_mesh():
    """End-to-end plumbing: serve_step lowers + compiles on the real
    (1-device) host mesh with the same sharding code path."""
    from repro.launch.mesh import make_host_mesh
    from repro.serving.engine import make_serve_step
    cfg = registry.get_smoke_config("qwen2-vl-7b")
    mesh = make_host_mesh()
    from repro.models.transformer import Transformer
    m = Transformer(cfg)
    pshape = jax.eval_shape(m.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: m.init_cache(4, 64, jnp.bfloat16))
    pspec = shd.param_specs(pshape, mesh, mode="serve")
    cspec = shd.cache_specs(cache, mesh)
    tspec = shd.batch_specs(jax.ShapeDtypeStruct((4, 1), jnp.int32), mesh)
    with mesh:
        step = make_serve_step(cfg)
        compiled = jax.jit(step, in_shardings=(pspec, tspec, cspec)).lower(
            pshape, jax.ShapeDtypeStruct((4, 1), jnp.int32), cache
        ).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):      # jax ≤0.4.x: one dict per device
        ca = ca[0]
    assert ca.get("flops", 0) > 0
