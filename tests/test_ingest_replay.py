"""Replay the ingest cell's video deep into a run, on the CPU.

The on-chip ingest cell (``bgevl-large.ingest``) checks, after its
window, that every stored row's member frames lie inside one closed
partition of a plain reference segmentation, that its index frame is
one of its members, and that every closed partition in the window is
indexed. A faster ingest reaches frames a slower one never does, so a
fault deep in a stream shows only on the faster side. This replay runs
the cell's own video generator (``traffic/scene_video.py`` with the
``ingest-8fps-scenes`` mix, frames cut to 32²) and its configuration
(``venus-bgevl-large.fp32``, the memory cut to 16 rows a stream,
prefilled as the cell's is, so that it slides past its history early)
through ``SessionManager.ingest_tick`` —
batched segmentation, the program's clustering, ``insert_batch`` into
the arena, the archive trim — with a stub embedder, to 2,560 frames a
stream, and applies the cell's checks from ``vbench/ref_scene.py``
along the way.
"""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "onchip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from vbench import build as vbuild, ref_scene  # noqa: E402
from vbench.registry import load_json, load_module  # noqa: E402

from repro.core.session import SessionManager, VenusConfig  # noqa: E402

STREAMS, FRAMES, DIM, CAPACITY = 4, 2560, 16, 16
# the seed on which the chip first found an index frame dropped from its
# reservoir (frame 940), and one past 32 signed bits; at four streams
# both close clusters over ``member_cap`` frames, which the reservoir cuts
SEEDS = (1851101895, 2**31 + 87109)


class StubEmbedder:
    """Unit rows that depend only on the frame id."""

    def embed_frames(self, frames, aux_texts=None, frame_ids=None):
        ids = np.asarray(frame_ids, np.float64)[:, None]
        v = np.cos((ids + 1.0) * np.arange(1, DIM + 1) * 0.37)
        return (v / np.linalg.norm(v, axis=1, keepdims=True)
                ).astype(np.float32)


def _rows(mgr, s):
    """Stream s's rows from its own frames: index frame and members."""
    arena = mgr.arena
    ifr = np.asarray(jax.device_get(arena.index_frame[s]))
    pos = np.nonzero(ifr >= 0)[0]
    cnt = np.asarray(jax.device_get(arena.member_count[s]))[pos]
    mem = np.asarray(jax.device_get(arena.members[s]))[pos]
    return ifr[pos].tolist(), [m[:c] for m, c in zip(mem, cnt)]


def _violations(mgr, pools, venus):
    bad = 0
    for s in range(STREAMS):
        phi = ref_scene.pool_scores(pools[s])
        parts = ref_scene.partitions(phi, mgr[s].stats["frames_seen"],
                                     venus["scene_threshold"],
                                     venus["max_partition_len"])
        bad += abs(len(parts) - mgr[s].stats["partitions"])
        ifr, members = _rows(mgr, s)
        bad += ref_scene.row_violations(parts, members, ifr)
        bad += ref_scene.empty_partitions(parts, ifr)
    return bad


@pytest.mark.parametrize("seed", SEEDS)
def test_replay_keeps_the_ingest_checks(seed):
    venus = dict(load_json("configs", "venus-bgevl-large.fp32")["venus"],
                 memory_capacity=CAPACITY)
    video = dict(load_json("traffic", "ingest-8fps-scenes")["video"],
                 resolution=32)
    gen = load_module("traffic", video["generator"])
    pools = gen.make_pools(video, STREAMS, seed)
    mgr = SessionManager(VenusConfig(**venus), StubEmbedder(), DIM)
    for s in range(STREAMS):
        mgr.create_session()
        vbuild.insert_history(mgr, seed, s, CAPACITY)
    n = video["chunk_frames"]
    ticks = FRAMES // n
    for k in range(ticks):
        mgr.ingest_tick({s: gen.chunk(pools[s], k, n)
                         for s in range(STREAMS)})
        if (k + 1) % (ticks // 4) == 0:
            assert _violations(mgr, pools, venus) == 0, (seed, k)
    assert mgr.io_stats["scene_score_launches"] == ticks
    assert mgr.io_stats["scene_score_streams"] == ticks * STREAMS
    assert all(mgr[s].stats["frames_seen"] == FRAMES
               for s in range(STREAMS))
    assert sum(mgr[s].stats["partitions"] for s in range(STREAMS)) > 80
    assert mgr.io_stats["archive_trimmed_frames"] > 0
    assert sum(mgr[s].memory.io_stats["reservoir_sampled"]
               for s in range(STREAMS)) > 0
