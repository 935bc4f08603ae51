"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode accepts block shapes and ops that the chip's compiler
refuses (the (8, 128) tiling rule, ops Mosaic cannot lower, scoped VMEM
overruns). These tests hand each kernel to the TPU compiler for a
described — not attached — v5e chip, so such a refusal fails here
instead of on the chip. Nothing runs; only shapes are passed.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import ops
from repro.kernels.decode_attention import gqa_decode, mla_decode
from repro.kernels.scene_score import scene_score
from repro.kernels.similarity import (fused_retrieve_scan_stack,
                                      similarity_scan_stack)

# the served path's scan shapes: MEM's 768-d space, the default
# memory_capacity, n_max queries per group, n_max draws per query
D, N, Q, T = 768, 8192, 32, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("valid_kind", ["windows", "sizes"])
def test_similarity_scan_stack_compiles(one_chip, dtype, valid_kind):
    vshape = (4, 2) if valid_kind == "windows" else (4,)
    fn = lambda q, x, v: similarity_scan_stack(q, x, v, tau=0.1,
                                               interpret=False)
    _compile(fn, one_chip, ((4, Q, D), jnp.float32), ((4, N, D), dtype),
             (vshape, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("n_topk,n_targets", [(1, T), (T, 1)],
                         ids=["draws", "topk"])
def test_fused_retrieve_scan_stack_compiles(one_chip, dtype, n_topk,
                                            n_targets):
    """The AKR/sampling shape (T draws, top-1) and the top-k shape (one
    dummy target, K = n_max). Mosaic refuses a kernel whose blocks and
    in-kernel values overrun scoped VMEM, so compiling is the VMEM
    check; around the kernel, no (S, Q, N) tensor may reach HBM."""
    fn = lambda q, x, v, t: fused_retrieve_scan_stack(
        q, x, v, t, tau=0.1, n_topk=n_topk, interpret=False)
    compiled = _compile(fn, one_chip, ((4, Q, D), jnp.float32),
                        ((4, N, D), dtype), ((4, 2), jnp.int32),
                        ((4, Q, n_targets), jnp.float32))
    mem = compiled.memory_analysis()
    dense = 4 * Q * N * 4
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < dense // 16


def test_scene_score_compiles(one_chip):
    """One ingest chunk of 224² frames (8 frames plus the carried one)."""
    fn = lambda f: scene_score(f, (1.0, 1.0, 1.0, 2.0), interpret=False)
    _compile(fn, one_chip, ((9, 224, 224, 3), jnp.float32))


@pytest.mark.parametrize("streams,frames", [(16, 8), (64, 2)])
def test_scene_score_streams_compiles(one_chip, streams, frames):
    """One ingest tick's scene scores in one program: the ingest cell's
    16 streams of 8 frames and the query cells' 64 of 2, 224² float32.
    Chunks and carried frames enter as 128-wide float32 rows, which the
    chip lays out with no padding: the arguments are the frames' raw
    bytes."""
    rows = 224 * 224 * 3 // 128
    fn = lambda prev, chunks: ops._scene_score_streams(
        prev, chunks, shape=(frames, 224, 224),
        weights=(1.0, 1.0, 1.0, 2.0), backend="pallas", interpret=False)
    args = [tuple(jax.ShapeDtypeStruct((k * rows, 128), jnp.float32,
                                       sharding=one_chip)
                  for _ in range(streams)) for k in (1, frames)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    raw = streams * (frames + 1) * 224 * 224 * 3 * 4
    assert compiled.memory_analysis().argument_size_in_bytes == raw


def test_fused_retrieve_sharded_compiles(topo):
    """The sharded arena's fan-out on a 4-chip host: each chip runs the
    fused kernel over its contiguous slab of slots, and only the
    epilogue outputs leave it — no collective in the program."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    slab = NamedSharding(mesh, P("model"))
    local = lambda q, x, v, t: tuple(fused_retrieve_scan_stack(
        q, x, v, t, tau=0.1, n_topk=1, interpret=False))
    sp = P("model", None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(sp, sp, P("model", None), sp),
                       out_specs=(sp,) * 7, check_vma=False)
    text = _compile(fn, slab, ((8, Q, D), jnp.float32),
                    ((8, N, D), jnp.int8), ((8, 2), jnp.int32),
                    ((8, Q, T), jnp.float32)).as_text()
    assert "all-gather" not in text and "all-reduce" not in text


def test_fused_retrieve_sharded_compiles_at_fleet_size(topo):
    """The four-chip fleet's fused scan: 64 slots of 65,536 float32 rows
    at 768 dimensions, 16 per chip, an AKR group of 8 queries with 32
    draw targets each. Each chip holds its 3.2 GB slab and nothing
    crosses chips inside the program."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    slab = NamedSharding(mesh, P("model"))
    local = lambda q, x, v, t: tuple(fused_retrieve_scan_stack(
        q, x, v, t, tau=0.1, n_topk=1, interpret=False))
    sp = P("model", None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(sp, sp, P("model", None), sp),
                       out_specs=(sp,) * 7, check_vma=False)
    slots, rows, q = 64, 65536, 8
    compiled = _compile(fn, slab, ((slots, q, D), jnp.float32),
                        ((slots, rows, D), jnp.float32),
                        ((slots, 2), jnp.int32), ((slots, q, T), jnp.float32))
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    slab_bytes = slots // 4 * rows * D * 4
    assert slab_bytes <= per_chip < 1.01 * slab_bytes


def test_gqa_decode_compiles(one_chip):
    """The smoke Qwen2-VL decode step: its GQA head layout against the
    serving engine's default cache (4 slots × 1024 positions, bf16 as
    the engine stores it)."""
    cfg = registry.get_smoke_config("qwen2-vl-7b")
    b, c = 4, 1024
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    fn = lambda q, k, v, m: gqa_decode(q, k, v, m, scale=hd ** -0.5,
                                       q_per_kv=h // hkv, blk_s=512,
                                       interpret=False)
    _compile(fn, one_chip, ((b, 1, h, hd), jnp.bfloat16),
             ((b, c, hkv, hd), jnp.bfloat16),
             ((b, c, hkv, hd), jnp.bfloat16), ((b, c), jnp.bool_))


def test_mla_decode_compiles(one_chip):
    cfg = registry.get_smoke_config("deepseek-v2-lite-16b")
    b, c, h = 4, 1024, cfg.num_heads
    r, dr = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    fn = lambda qa, qr, ckv, kr, m: mla_decode(qa, qr, ckv, kr, m,
                                               scale=0.1, blk_s=512,
                                               interpret=False)
    _compile(fn, one_chip, ((b, 1, h, r), jnp.float32),
             ((b, 1, h, dr), jnp.float32), ((b, c, r), jnp.float32),
             ((b, c, dr), jnp.float32), ((b, c), jnp.bool_))
