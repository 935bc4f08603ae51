"""Kernel dispatch layer.

Every hot-spot op has two implementations: the Pallas TPU kernel and the
pure-jnp oracle (``ref.py``). The platform picks between them: on a TPU
the kernels compile natively through Mosaic; on the CPU the oracle runs
(XLA fuses it well there). Any other platform is an error — there is no
silent fallback. ``set_backend`` is the test hook that pins one side:
the tests run the kernels on the CPU in interpret mode against the
oracle, and the chip smoke check runs the oracle on the TPU as its
reference.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.launch.sharding import mesh_axis_size

_PLATFORM_BACKEND = {"tpu": "pallas", "cpu": "jnp"}
_LANES = 128
_OVERRIDE: Optional[str] = None


def _platform() -> str:
    plat = jax.default_backend()
    if plat not in _PLATFORM_BACKEND:
        raise RuntimeError(
            f"no kernel backend for platform {plat!r}: the Pallas kernels "
            f"target the TPU and the jnp oracle the CPU")
    return plat


def backend() -> str:
    """The backend every dispatch below uses: the ``set_backend``
    override when one is set, else the platform's own."""
    return _OVERRIDE or _PLATFORM_BACKEND[_platform()]


def set_backend(name: Optional[str]) -> Optional[str]:
    """Pin the backend ("jnp" or "pallas"); ``None`` restores the
    platform's choice. Returns the previous pin, for restoring it."""
    global _OVERRIDE
    assert name in (None, "jnp", "pallas"), name
    prev, _OVERRIDE = _OVERRIDE, name
    return prev


def _interpret() -> bool:
    """Kernels run natively on a TPU and interpreted on the CPU."""
    return _platform() == "cpu"


# Dispatch-level launch accounting: every similarity scan entering the
# kernel layer is counted here, independent of backend, so the query-plan
# executor's "ONE similarity_scan_stack launch per execution group"
# invariant is assertable at the layer that actually launches the scan
# (manager/memory io_stats only see their own call sites).
#
# The fusion/quantisation savings are measurable, not anecdotal:
# ``scan_bytes`` accumulates the index bytes streamed by every scan
# (int8 indices count 1 byte/element — the 4× bandwidth lever);
# ``fused_draw_launches`` counts scans whose draws/top-k were resolved
# in the fused epilogue (no (S,Q,N) score tensor materialised);
# ``dense_score_launches`` counts scans that DID materialise dense
# scores (the BOLT/MDF/AKS fallback and every legacy ``search`` call).
#
# Sharded-arena accounting: ``sharded_stack_launches`` counts stack
# scans fanned out per shard via shard_map (K > 1 — the K == 1 mesh
# short-circuits to the single-device path, bit-identically);
# ``shard_gather_bytes`` accumulates the bytes the sharded launches'
# OUTPUTS move across shard boundaries — O(S·Q·(T+K)) for the fused
# scan, O(S·Q·N) for a dense sharded scan — computed from the actual
# output arrays, so the "only the epilogue crosses" contract is a
# counter assertion, not a claim. (Counters are host-side: they bump at
# the dispatch call site, never inside a traced shard_map body.)
#
# Two-stage (hierarchical-tier) accounting: ``coarse_scan_bytes`` is the
# subset of ``scan_bytes`` streamed by stage-1 scans over the coarse
# summary tier; ``fine_gather_rows`` counts the candidate fine rows
# stage 2 gathers into its per-query scan operand (winner blocks ×
# block rows, padding slots included — the honest operand size);
# ``two_stage_scans`` counts completed coarse→fine retrievals. Together
# they pin the tier's bandwidth claim: coarse_scan_bytes + the gathered
# candidate bytes must undercut the flat 1×-capacity scan.
_scan_counts = {"similarity": 0, "similarity_stack": 0,
                "scan_bytes": 0, "fused_draw_launches": 0,
                "dense_score_launches": 0,
                "sharded_stack_launches": 0, "shard_gather_bytes": 0,
                "coarse_scan_bytes": 0, "fine_gather_rows": 0,
                "two_stage_scans": 0, "standing_scan_bytes": 0}


def _count_scan_bytes(index) -> None:
    _scan_counts["scan_bytes"] += index.size * index.dtype.itemsize


def count_fine_gather(n_rows: int) -> None:
    """Host-side stage-2 accounting hook for the tiering layer: the
    candidate rows gathered out of the fine arena for one two-stage
    retrieval (counted at dispatch, never inside a traced body)."""
    _scan_counts["fine_gather_rows"] += int(n_rows)
    _scan_counts["two_stage_scans"] += 1


def scan_counts() -> dict:
    return dict(_scan_counts)


def reset_scan_counts() -> None:
    for k in _scan_counts:
        _scan_counts[k] = 0


# ---------------------------------------------------------------------------


def decode_attention(q, k, v, valid, *, scale: float, softcap: float = 0.0,
                     q_per_kv: int = 1) -> jnp.ndarray:
    """q: (B,1,H,D); k/v: (B,C,Hkv,D); valid: (B or 1, C) -> (B,1,H,D)."""
    if backend() == "pallas":
        from repro.kernels import decode_attention as dk
        b, c = q.shape[0], k.shape[1]
        vmask = jnp.broadcast_to(valid, (b, c))
        blk = c if c <= dk.DEFAULT_BLK_S else _largest_divisor_blk(
            c, dk.DEFAULT_BLK_S)
        return dk.gqa_decode(q, k, v, vmask, scale=scale, softcap=softcap,
                             q_per_kv=q_per_kv, blk_s=blk,
                             interpret=_interpret())
    return ref.decode_attention_ref(q, k, v, valid, scale=scale,
                                    softcap=softcap, q_per_kv=q_per_kv)


def mla_decode_attention(q_abs, q_rope, ckv, krope, valid, *,
                         scale: float) -> jnp.ndarray:
    if backend() == "pallas":
        from repro.kernels import decode_attention as dk
        b, c = q_abs.shape[0], ckv.shape[1]
        vmask = jnp.broadcast_to(valid, (b, c))
        blk = c if c <= dk.DEFAULT_BLK_S else _largest_divisor_blk(
            c, dk.DEFAULT_BLK_S)
        return dk.mla_decode(q_abs, q_rope, ckv, krope, vmask, scale=scale,
                             blk_s=blk, interpret=_interpret())
    return ref.mla_decode_attention_ref(q_abs, q_rope, ckv, krope, valid,
                                        scale=scale)


def similarity(query, index, *, tau: float, valid
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """query (Q,d) × index (N,d) -> (sims (Q,N), probs (Q,N))."""
    _scan_counts["similarity"] += 1
    _scan_counts["dense_score_launches"] += 1
    _count_scan_bytes(index)
    if backend() == "pallas":
        from repro.kernels import similarity as sk
        sims, m, l = sk.similarity_scan(query, index, valid, tau=tau,
                                        interpret=_interpret())
        logits = jnp.where(valid[None, :], sims / tau, ref.NEG_INF)
        probs = jnp.exp(logits - m) / jnp.maximum(l, 1e-30)
        return sims.astype(query.dtype), probs
    return ref.similarity_ref(query, index, tau=tau, valid=valid)


def _similarity_stack_local(query, index, valid, *, tau: float,
                            backend: str):
    """The per-(shard-local) stack-scan body — every lane's math is
    per-session, so running it on an (S/K, …) slab inside shard_map is
    exactly the single-device computation restricted to that slab."""
    if backend == "pallas":
        from repro.kernels import similarity as sk
        sims, m, l = sk.similarity_scan_stack(query, index, valid, tau=tau,
                                              interpret=_interpret())
        vmask = ref.as_valid_mask(valid, index.shape[1])
        logits = jnp.where(vmask[:, None, :], sims / tau, ref.NEG_INF)
        probs = jnp.exp(logits - m) / jnp.maximum(l, 1e-30)
        return sims.astype(query.dtype), probs
    return ref.similarity_stack_ref(query, index, tau=tau, valid=valid)


def _valid_spec(valid, mesh_axis: str) -> P:
    """Partition spec of the canonical ``valid`` operand: the leading
    axis is always the session/slot axis, whatever the form (mask,
    sizes vector, or (S, 2) windows)."""
    return P(mesh_axis) if valid.ndim == 1 else P(mesh_axis, None)


@functools.partial(jax.jit,
                   static_argnames=("tau", "backend", "mesh", "mesh_axis"))
def _similarity_stack_sharded(query, index, valid, *, tau: float,
                              backend: str, mesh, mesh_axis: str):
    """Fan the stack scan out per shard: each device scans its
    contiguous slot slab with the identical kernel/oracle body; the
    out_specs stitch the per-shard (S/K, Q, N) outputs back together.
    ``check_vma=False``: a pallas_call's outputs carry no varying-axes
    annotation, and the bodies are per-shard pure, so every output
    varies over ``mesh_axis`` exactly as the out_specs say."""
    local = functools.partial(_similarity_stack_local, tau=tau,
                              backend=backend)
    sp = P(mesh_axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(sp, sp, _valid_spec(valid, mesh_axis)),
        out_specs=(sp, sp), check_vma=False)(query, index, valid)


def similarity_stack(query, index, *, tau: float, valid, mesh=None,
                     mesh_axis: str = "model"
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-session scan: query (S,Q,d) × index (S,N,d) + valid —
    a (S,N) bool mask, a (S,) int sizes vector, or a (S,2) int
    ``[start,size)`` ring-window array (arena/eviction paths: the
    per-session valid masks derive on device — ``ref.as_valid_mask``)
    -> (sims (S,Q,N), probs (S,Q,N)) in ONE kernel launch.

    With ``mesh`` carrying K > 1 shards on ``mesh_axis`` the launch runs
    as a shard_map over contiguous slot slabs (the sharded arena's
    placement); per-lane math makes the result bit-identical to the
    single-device scan. K == 1 (or mesh None) short-circuits to the
    plain path."""
    _scan_counts["similarity_stack"] += 1
    _scan_counts["dense_score_launches"] += 1
    _count_scan_bytes(index)
    if mesh is not None and mesh_axis_size(mesh, mesh_axis) > 1:
        assert query.shape[0] % mesh_axis_size(mesh, mesh_axis) == 0, \
            (query.shape, dict(mesh.shape))
        sims, probs = _similarity_stack_sharded(
            query, index, valid, tau=tau, backend=backend(), mesh=mesh,
            mesh_axis=mesh_axis)
        _scan_counts["sharded_stack_launches"] += 1
        _scan_counts["shard_gather_bytes"] += int(
            sims.size * sims.dtype.itemsize
            + probs.size * probs.dtype.itemsize)
        return sims, probs
    return _similarity_stack_local(query, index, valid, tau=tau,
                                   backend=backend())


class FusedRetrieval(NamedTuple):
    """Finalised fused-retrieval result — what the query-plan executor
    consumes. No (S, Q, N) tensor anywhere in the contract."""
    draws: jnp.ndarray          # (S, Q, T) int32 lane draws (clipped)
    drawn_p: jnp.ndarray        # (S, Q, T) f32 probability of each draw
    topk_v: jnp.ndarray         # (S, Q, K) f32 top-k scores (desc)
    topk_i: jnp.ndarray         # (S, Q, K) int32 top-k lane indices
    m: jnp.ndarray              # (S, Q, 1) f32 online-softmax max
    l: jnp.ndarray              # (S, Q, 1) f32 online-softmax sum-exp
    p_max: jnp.ndarray          # (S, Q, 1) f32 max probability


def _fused_retrieve_local(query, index, valid, targets, *, tau: float,
                          n_topk: int, backend: str):
    """Per-(shard-local) fused-retrieval body: the raw 8-tuple
    ``(cnt, dp, p_last, tv, ti, m, l, p_max)``, every output with a
    leading session axis. All draw counts and top-k indices are
    SESSION-LOCAL lane indices, so a shard computes them for its slab
    without any global-id offset — the gather is a pure concatenation."""
    if backend == "pallas":
        from repro.kernels import similarity as sk
        cnt, dp, p_last, tv, ti, m, l = sk.fused_retrieve_scan_stack(
            query, index, valid, targets, tau=tau, n_topk=n_topk,
            interpret=_interpret())
        # the max-probability lane is exp(m − m)/l == 1/l, bitwise the
        # value a max over this backend's materialised probs would find
        p_max = 1.0 / jnp.maximum(l, 1e-30)
        return cnt, dp, p_last, tv, ti, m, l, p_max
    # plain tuple (not the NamedTuple): shard_map matches out_specs
    # against the pytree STRUCTURE, which must be backend-independent
    return tuple(ref.fused_retrieve_stack_ref(query, index, valid,
                                              targets, tau=tau,
                                              n_topk=n_topk))


@functools.partial(jax.jit, static_argnames=("tau", "n_topk", "backend",
                                             "mesh", "mesh_axis"))
def _fused_retrieve_sharded(query, index, valid, targets, *, tau: float,
                            n_topk: int, backend: str, mesh,
                            mesh_axis: str):
    """Per-shard fused launches: each device runs the full fused scan on
    its contiguous slot slab; only the O(S·Q·(T+K)) epilogue outputs are
    stitched across shards (the top-M candidate gather — no recall loss
    because draws/top-k are per-lane and lanes never span shards)."""
    local = functools.partial(_fused_retrieve_local, tau=tau,
                              n_topk=n_topk, backend=backend)
    sp = P(mesh_axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(sp, sp, _valid_spec(valid, mesh_axis), sp),
        out_specs=(sp,) * 8, check_vma=False)(query, index, valid, targets)


def fused_retrieve_stack(query, index, *, tau: float, valid, targets,
                         n_topk: int, mesh=None,
                         mesh_axis: str = "model",
                         tier: str = "fine") -> FusedRetrieval:
    """One-launch fused retrieval: query (S,Q,d) × index (S,N,d) fp32 or
    int8 + valid (any canonical mask form) + targets (S,Q,T) inverse-CDF
    draw targets -> draws, drawn probabilities, top-k, softmax stats.

    Draws are bit-identical to running the canonical chunked inverse-CDF
    (``draws.categorical_from_targets``) over this backend's materialised
    probabilities, and topk_i to ``lax.top_k`` over its masked scores —
    without ever materialising them on the fused (pallas) backend. The
    clip-to-cap-1 / p_last substitution for targets beyond the
    accumulated total mass happens here, identically for both backends.

    With ``mesh`` carrying K > 1 shards on ``mesh_axis``, the launch
    fans out per shard over contiguous slot slabs and only the epilogue
    outputs — O(S·Q·(T+K)) bytes, counted into ``shard_gather_bytes`` —
    cross shard boundaries; K == 1 (or mesh None) short-circuits to the
    single-device launch, bit-identically.

    ``tier="coarse"`` marks the launch as a stage-1 scan over the
    hierarchical summary tier: identical math, but the streamed bytes
    are additionally counted into ``coarse_scan_bytes`` so the
    two-stage bandwidth claim stays a counter assertion.
    ``tier="standing"`` marks the launch as a standing-query evaluation
    over the tick's new-row slab: the streamed bytes additionally count
    into ``standing_scan_bytes``, pinning the "no full-capacity
    re-scan" contract (the operand is the compact slab, so the counter
    is O(new_rows · d) by construction).
    """
    assert tier in ("fine", "coarse", "standing"), tier
    _scan_counts["similarity_stack"] += 1
    _scan_counts["fused_draw_launches"] += 1
    _count_scan_bytes(index)
    if tier == "coarse":
        _scan_counts["coarse_scan_bytes"] += int(
            index.size * index.dtype.itemsize)
    elif tier == "standing":
        _scan_counts["standing_scan_bytes"] += int(
            index.size * index.dtype.itemsize)
    n = index.shape[1]
    if mesh is not None and mesh_axis_size(mesh, mesh_axis) > 1:
        assert query.shape[0] % mesh_axis_size(mesh, mesh_axis) == 0, \
            (query.shape, dict(mesh.shape))
        r = _fused_retrieve_sharded(query, index, valid, targets, tau=tau,
                                    n_topk=n_topk, backend=backend(),
                                    mesh=mesh, mesh_axis=mesh_axis)
        _scan_counts["sharded_stack_launches"] += 1
        _scan_counts["shard_gather_bytes"] += int(
            sum(a.size * a.dtype.itemsize for a in r))
    else:
        r = _fused_retrieve_local(query, index, valid, targets, tau=tau,
                                  n_topk=n_topk, backend=backend())
    cnt, dp, p_last, tv, ti, m, l, p_max = r
    draws = jnp.clip(cnt, 0, n - 1).astype(jnp.int32)
    drawn_p = jnp.where(cnt >= n, p_last, dp)
    return FusedRetrieval(draws, drawn_p, tv, ti, m, l, p_max)


def scene_score(frames, weights) -> jnp.ndarray:
    """frames (T,H,W,3) in [0,1] -> φ (T,)."""
    if backend() == "pallas":
        from repro.kernels import scene_score as sk
        return sk.scene_score(frames, tuple(weights),
                              interpret=_interpret())
    return ref.scene_score_ref(frames, tuple(weights))


def lane_dense(frames: np.ndarray) -> np.ndarray:
    """(T, H, W, 3) frames -> float32 (T·R, 128), R = ⌈H·W·3 / 128⌉ rows
    a frame: row-major with a 128-wide minor axis, which the device lays
    out as it lies in host memory. A view of contiguous float32 frames
    whose H·W·3 is a multiple of 128; otherwise a copy with each frame
    zero-padded to whole rows."""
    flat = np.ascontiguousarray(frames, np.float32).reshape(len(frames), -1)
    pad = -flat.shape[1] % _LANES
    if pad:
        flat = np.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(-1, _LANES)


def scene_score_streams(prev: Sequence[jax.Array],
                        chunks: Sequence[jax.Array],
                        shape: Tuple[int, int, int],
                        weights) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """One program scores S streams' chunks of ``shape`` = (T, H, W).

    ``chunks[s]`` is stream s's T frames in ``lane_dense`` form, on the
    device; ``prev[s]`` is the frame before them in the same form (one
    frame's R rows). Returns φ (S, T), φ[s, i] scoring chunk s's frame i
    against the frame before it, and each chunk's last frame, the next
    tick's ``prev``. The frames run through ``scene_score`` laid end to
    end, each stream's ``prev`` before its chunk, so every φ comes from
    the same per-frame arithmetic as that stream's own ``scene_score``
    call; each stream's leading φ, scored against the stream before it,
    is dropped."""
    return _scene_score_streams(tuple(prev), tuple(chunks),
                                shape=tuple(shape), weights=tuple(weights),
                                backend=backend(), interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("shape", "weights", "backend",
                                             "interpret"))
def _scene_score_streams(prev, chunks, *, shape, weights, backend,
                         interpret):
    t, h, w = shape
    n = h * w * 3

    def frames(x, k):
        return x.reshape(k, -1)[:, :n].reshape(k, h, w, 3)

    seq = jnp.concatenate([f for p, x in zip(prev, chunks)
                           for f in (frames(p, 1), frames(x, t))])
    if backend == "pallas":
        from repro.kernels import scene_score as sk
        phi = sk.scene_score(seq, weights, interpret=interpret)
    else:
        phi = ref.scene_score_ref(seq, weights)
    rows = prev[0].shape[0]
    return (phi.reshape(len(chunks), t + 1)[:, 1:],
            tuple(x[(t - 1) * rows:] for x in chunks))


def _largest_divisor_blk(n: int, target: int) -> int:
    for b in range(target, 0, -1):
        if n % b == 0:
            return b
    return n
