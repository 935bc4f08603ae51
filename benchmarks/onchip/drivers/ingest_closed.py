"""Closed-loop ingest at capacity: every stream's next chunk, every tick.

Each tick hands all streams one chunk of video through
``VenusService.ingest_tick``; the next tick starts when it returns. The
memory is full before the window opens and evicts by sliding window, so
every tick runs the whole 24/7 path: segmentation, clustering, one MEM
image-tower call over the tick's keyframes, the deferred arena scatter,
and the archive trim. ``ingest_fps`` is the frames of every tick run
over the time they took, from the window's start to the end of its last
tick.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from vbench import build as vbuild
from vbench import mem as vmem
from vbench import ref_mem, ref_scene
from vbench.registry import load_module
from vbench.util import rng, sub_seed


def prepare(ctx) -> None:
    tr = ctx.cell.traffic
    ctx.video = load_module("traffic", tr["video"]["generator"], ctx.base)
    ctx.pools = ctx.video.make_pools(tr["video"], ctx.streams, ctx.seed)
    ctx.frame_ticks = 0
    ctx.ingest_ticks: List[dict] = []


def _tick(ctx) -> dict:
    n = ctx.cell.traffic["video"]["chunk_frames"]
    chunks = {s: ctx.video.chunk(ctx.pools[s], ctx.frame_ticks, n)
              for s in range(ctx.streams)}
    t0 = time.perf_counter()
    with ctx.spans.span("ingest_tick"):
        out = ctx.svc.ingest_tick(chunks)
    ctx.frame_ticks += 1
    return {"t0": t0, "t1": time.perf_counter(),
            "frames": n * len(chunks), **out}


def warmup(ctx) -> None:
    """Every shape the window reaches, whatever the seed: the image tower
    at every power-of-two batch up to the most keyframes a tick can close
    (each stream one partition of ``max_clusters_per_partition``), the
    cut of each batch's output to every count below it, the tick scatter
    at the same row counts, the clustering of a partition of every
    length up to ``max_partition_len`` (cuts fall anywhere, and soft
    cuts merge scenes up to that length), and two ticks (the first, with
    no previous frame, and a later one). The batches are powers of two
    up to ``pow2_bucket`` of the largest count, so they reach whatever
    power-of-two floor the program buckets at."""
    import jax
    import jax.numpy as jnp
    from repro.core.clustering import cluster_partition, frame_vectors
    from repro.core.pipeline import patchify
    from repro.util import pow2_bucket
    venus, mem = ctx.cell.config["venus"], ctx.cell.config["mem"]
    r = ctx.cell.traffic["video"]["resolution"]
    most = ctx.streams * venus["max_clusters_per_partition"]
    batches = sorted({pow2_bucket(n) for n in range(1, most + 1)})
    emb = ctx.embedder
    patches = patchify(np.zeros((1, r, r, 3), np.float32), mem["patch"],
                       mem["vision"]["d_model"])
    dev = jax.devices()[0]
    for k, b in enumerate(batches):
        emb.embed_frames(np.zeros((b, r, r, 3), np.float32))
        out = jax.eval_shape(emb.mem.encode_image, emb.params,
                             jax.ShapeDtypeStruct((b,) + patches.shape[1:],
                                                  patches.dtype))
        img = jax.device_put(jnp.zeros(out.shape, out.dtype), dev)
        for n in range(1, b + 1):
            np.asarray(img[:n])
        vbuild.insert_history(ctx.mgr, ctx.seed, k % ctx.streams, b,
                              part=k + 1)
    for t in range(1, venus["max_partition_len"] + 1):
        res = cluster_partition(
            frame_vectors(jnp.zeros((t, r, r, 3), jnp.float32),
                          venus["cluster_pool"]),
            threshold=venus["cluster_threshold"],
            max_clusters=venus["max_clusters_per_partition"])
        int(res.n_clusters)
        np.asarray(res.assignments)
        np.asarray(res.index_frames)
    for _ in range(2):
        _tick(ctx)


def window(ctx, seconds: float) -> Dict:
    t0 = time.perf_counter()
    end = t0 + seconds
    with ctx.spans.span("window"):
        while time.perf_counter() < end:
            ctx.ingest_ticks.append(_tick(ctx))
    t1 = ctx.ingest_ticks[-1]["t1"]
    ctx.window = (t0, t1)
    frames = sum(t["frames"] for t in ctx.ingest_ticks)
    emb = sum(t["embedded"] for t in ctx.ingest_ticks)
    ctx.log(f"ingest: {len(ctx.ingest_ticks)} ticks, {frames} frames, "
            f"{emb:g} keyframes embedded in {t1 - t0:.3f} s")
    return {"attempted": len(ctx.ingest_ticks), "failed": 0,
            "ingest_fps": frames / (t1 - t0)}


def collect(ctx) -> Dict:
    """Every row this process's frames produced (index frame >= 0; the
    history uses negative ids): its members, and for a seeded sample its
    stored embedding; and each stream's partition and frame counts."""
    import jax
    import jax.numpy as jnp
    mgr, arena = ctx.mgr, ctx.mgr.arena
    g = rng(ctx.seed, "check-sample")
    rows = []
    for s in range(ctx.streams):
        ifr = np.asarray(jax.device_get(arena.index_frame[s]))
        pos = np.nonzero(ifr >= 0)[0]
        cnt = np.asarray(jax.device_get(arena.member_count[s]))[pos]
        mem = np.asarray(jax.device_get(arena.members[s][jnp.asarray(pos)]))
        for p, f, c, m in zip(pos, ifr[pos], cnt, mem):
            rows.append({"sid": s, "pos": int(p), "ifr": int(f),
                         "members": m[:c].copy()})
    k = min(len(rows), ctx.cell.traffic["check"]["rows"])
    sample = sorted(g.choice(len(rows), k, replace=False).tolist())
    for i in sample:
        r = rows[i]
        r["emb"] = np.asarray(jax.device_get(arena.emb[r["sid"],
                                                       r["pos"]]))
    return {"rows": rows, "sample": sample,
            "partitions": [mgr[s].stats["partitions"]
                           for s in range(ctx.streams)],
            "frames": [mgr[s].stats["frames_seen"]
                       for s in range(ctx.streams)]}


def check(ctx, col: Dict, lowp: bool = False) -> Dict[str, float]:
    cfg = ctx.cell.config
    venus, mem = cfg["venus"], cfg["mem"]
    out: Dict[str, float] = {}
    # segmentation: partition counts, and every row inside one partition
    bad, margin = 0, np.inf
    for s in range(ctx.streams):
        phi = ref_scene.pool_scores(ctx.pools[s])
        margin = min(margin, ref_scene.margin(phi[1:],
                                              venus["scene_threshold"]))
        parts = ref_scene.partitions(phi, col["frames"][s],
                                     venus["scene_threshold"],
                                     venus["max_partition_len"])
        bad += abs(len(parts) - col["partitions"][s])
        mine = [r for r in col["rows"] if r["sid"] == s]
        bad += ref_scene.row_violations(parts, [r["members"] for r in mine],
                                        [r["ifr"] for r in mine])
        bad += ref_scene.empty_partitions(parts, [r["ifr"] for r in mine])
    ctx.log(f"segmentation: scores keep {margin:.6f} from the threshold")
    out["segment_mismatch"] = float(bad)
    # image tower: the stored row of each sampled keyframe
    sample = [col["rows"][i] for i in col["sample"]]
    if not sample:
        out["image_emb_gap"] = 1.0
        return out
    params = vmem.make_params(mem, sub_seed(ctx.seed, "weights"))
    frames = np.stack([ctx.pools[r["sid"]][r["ifr"] % len(
        ctx.pools[r["sid"]])] for r in sample])
    ref = ref_mem.embed_frames(params, mem, frames)
    got = (ref_mem.embed_frames(params, mem, frames, lowp=True) if lowp
           else np.stack([r["emb"] for r in sample]))
    out["image_emb_gap"] = float(np.max(ref_mem.cosine_gap(got, ref)))
    return out
