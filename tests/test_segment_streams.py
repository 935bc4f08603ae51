"""Stream-batched scene segmentation equals the per-stream path.

``segment_streams`` scores every stream's chunk of one tick in one
upload and one ``scene_score_streams`` launch per chunk shape. These
tests hold it to the path it replaced: φ bitwise equal to each stream's
own ``scene_score`` call on its carried frame and its chunk, on the
Pallas kernel (interpret mode) and the jnp oracle; partitions equal to
a per-frame loop of the boundary rule over random tick sequences in
which streams send chunks of mixed lengths, join late and skip ticks;
and one launch per distinct chunk length in a tick.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.scene import (StreamSegmenter,  # noqa: E402
                              segment_streams)
from repro.core.session import SessionManager, VenusConfig  # noqa: E402
from repro.data.video import PixelEmbedder  # noqa: E402
from repro.kernels import ops  # noqa: E402

W = (1.0, 1.0, 1.0, 2.0)


@pytest.fixture(params=["jnp", "pallas"])
def backend(request):
    old = ops.set_backend(request.param)
    yield request.param
    ops.set_backend(old)


def _own_scores(prev, chunk):
    """One stream's φ the per-stream way: its own ``scene_score`` launch
    over its carried frame and its chunk, the carried frame's φ dropped."""
    ext = np.concatenate([prev[None], chunk])
    return np.asarray(jax.jit(lambda x: ops.scene_score(x, W))(ext))[1:]


# (streams, frames, H, W): H·W·3 a multiple of 128, and one that is not
@pytest.mark.parametrize("s,t,h,w", [(3, 4, 16, 24), (2, 1, 8, 128),
                                     (4, 2, 5, 7)])
def test_batched_scores_equal_per_stream_bitwise(backend, s, t, h, w):
    rng = np.random.default_rng(s * 100 + t)
    prev = rng.random((s, h, w, 3), dtype=np.float32)
    chunks = rng.random((s, t, h, w, 3), dtype=np.float32)
    dense = lambda x: jnp.asarray(ops.lane_dense(x))
    phi, last = ops.scene_score_streams([dense(p[None]) for p in prev],
                                        [dense(c) for c in chunks],
                                        (t, h, w), W)
    assert phi.shape == (s, t)
    for i in range(s):
        np.testing.assert_array_equal(np.asarray(phi[i]),
                                      _own_scores(prev[i], chunks[i]))
        np.testing.assert_array_equal(np.asarray(last[i]),
                                      ops.lane_dense(chunks[i][-1:]))


def test_lane_dense_is_a_view_when_rows_are_whole():
    frames = np.zeros((3, 16, 16, 3), np.float32)
    assert np.shares_memory(ops.lane_dense(frames), frames)
    assert ops.lane_dense(frames).shape == (3 * 6, 128)
    odd = np.ones((2, 5, 7, 3), np.float32)
    rows = ops.lane_dense(odd).reshape(2, -1)
    assert rows.shape == (2, 128)
    assert rows[:, :105].all() and not rows[:, 105:].any()


def _loop_rule(phi, state, threshold, max_len):
    """The rule frame by frame, as one stream's host loop ran it."""
    since, start, pos, started = state
    closed = []
    for p in phi:
        if started and (p > threshold or since >= max_len):
            closed.append((start, pos))
            start, since = pos, 1
        else:
            since += 1
        started = True
        pos += 1
    return closed, (since, start, pos, started)


N_STREAMS, H, WD = 3, 8, 16
# each stream's frames: a few flat colours (hard cuts) with small noise,
# so the threshold and the maximum length both cut
COLOURS = np.random.default_rng(0).random((4, 3)).astype(np.float32)


@st.composite
def schedules(draw):
    """Ticks of {stream: (chunk length, colour per frame)}; a stream is
    absent from a tick it skips and from the ticks before it joins."""
    join = [draw(st.integers(0, 2)) for _ in range(N_STREAMS)]
    ticks = []
    for k in range(draw(st.integers(1, 6))):
        tick = {}
        for s in range(N_STREAMS):
            if k >= join[s] and draw(st.booleans() | st.just(True)):
                n = draw(st.integers(1, 3))
                tick[s] = draw(st.lists(st.integers(0, 3), min_size=n,
                                        max_size=n))
        ticks.append(tick)
    return ticks


def _frames(colours, rng):
    base = COLOURS[colours][:, None, None, :]
    noise = rng.normal(0, 0.01, (len(colours), H, WD, 3))
    return np.clip(base + noise, 0, 1).astype(np.float32)


@settings(max_examples=30, deadline=None)
@given(schedule=schedules(), max_len=st.integers(2, 5))
def test_partitions_equal_the_per_stream_rule(schedule, max_len):
    rng = np.random.default_rng(len(schedule))
    threshold = 0.05
    batched = [StreamSegmenter(threshold=threshold, max_partition_len=max_len)
               for _ in range(N_STREAMS)]
    alone = [StreamSegmenter(threshold=threshold, max_partition_len=max_len)
             for _ in range(N_STREAMS)]
    got = [[] for _ in range(N_STREAMS)]
    one = [[] for _ in range(N_STREAMS)]
    want = [[] for _ in range(N_STREAMS)]
    state = [(0, 0, 0, False)] * N_STREAMS
    last = [None] * N_STREAMS
    for tick in schedule:
        chunks = {s: _frames(c, rng) for s, c in tick.items()}
        sids = list(chunks)
        out = segment_streams([batched[s] for s in sids],
                              [chunks[s] for s in sids])
        for s, closed in zip(sids, out):
            got[s] += [(p.start, p.end) for p in closed]
            one[s] += [(p.start, p.end)
                       for p in alone[s].ingest(chunks[s])]
            prev = chunks[s][0] if last[s] is None else last[s]
            phi = _own_scores(prev, chunks[s])
            closed, state[s] = _loop_rule(phi, state[s], threshold, max_len)
            want[s] += closed
            last[s] = chunks[s][-1]
    for s in range(N_STREAMS):
        got[s] += [(p.start, p.end) for p in batched[s].flush()]
        one[s] += [(p.start, p.end) for p in alone[s].flush()]
        if state[s][3] and state[s][2] > state[s][1]:
            want[s].append((state[s][1], state[s][2]))
    assert got == want
    assert one == want


@pytest.mark.parametrize("lengths", [(2, 2, 2, 2), (2, 3, 2, 1),
                                     (1, 0, 3, 3)],
                         ids=["one-length", "three-lengths", "one-empty"])
def test_one_launch_per_chunk_length(lengths):
    mgr = SessionManager(VenusConfig(), PixelEmbedder(dim=16), embed_dim=16)
    sids = [mgr.create_session() for _ in lengths]
    rng = np.random.default_rng(7)
    for _ in range(2):
        mgr.ingest_tick({sid: rng.random((n, 16, 16, 3), dtype=np.float32)
                         for sid, n in zip(sids, lengths)})
    sent = {n for n in lengths if n}
    assert mgr.io_stats["scene_score_launches"] == 2 * len(sent)
    assert mgr.io_stats["scene_score_streams"] == \
        2 * sum(n > 0 for n in lengths)
    assert [mgr[s].stats["frames_seen"] for s in sids] == \
        [2 * n for n in lengths]
