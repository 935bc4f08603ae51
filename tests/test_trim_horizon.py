"""The archive-trim horizon kept per row equals the brute-force horizon.

``VenusMemory.min_live_frame`` reads ``_row_lo``, one int32 per row kept
in step with the index-frame, member and count tables. These properties
drive random ``insert_batch`` sequences through every eviction policy —
clusters of 1 to 3 × ``member_cap`` members, ring wrap-around, batches
larger than the memory, cluster_merge folds, consolidation into the
coarse tier, session close and arena slot recycling — and check after
every step that the horizon equals the min over the live window of the
index frames and the count-masked member tables, read straight from the
host mirrors.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.memory import VenusMemory  # noqa: E402
from repro.core.session import SessionManager, VenusConfig  # noqa: E402

DIM, CAP, MCAP = 8, 16, 4
POLICIES = ("sliding_window", "cluster_merge", "consolidate")
_settings = settings(max_examples=25, deadline=None)


def brute_horizon(mem: VenusMemory) -> int:
    """The horizon from the host mirrors: every live row's index frame
    and count-masked members, then the consolidated rows' frame lows."""
    lo = int(np.iinfo(np.int64).max)
    if mem.size:
        phys = (mem.head + np.arange(mem.size)) % mem.capacity
        lo = int(mem._index_frame[phys].min())
        cnt = mem._member_count[phys]
        live = np.arange(mem.member_cap)[None, :] < cnt[:, None]
        if live.any():
            lo = min(lo, int(mem._members[phys][live].min()))
    if mem.n_coarse and mem._coarse_csize:
        lo = min(lo, int(mem._coarse_fid_lo[:mem._coarse_csize].min()))
    return lo


def _memory_kw(policy):
    # a low merge threshold and a few shared directions make folds happen
    kw = dict(eviction=policy, merge_threshold=0.5)
    if policy == "consolidate":
        kw.update(coarse_capacity=4, coarse_block=4)
    return kw


@st.composite
def batches(draw, max_rows=CAP + 3):
    """One insert_batch's arguments: rows near a few shared directions,
    frame ids around 0 (the caller shifts them forward in time, so an
    evictee's members tend to be the oldest), clusters of 1 to 3 ×
    member_cap members or small ones with room for folds, the index
    frame sometimes left out of them."""
    n = draw(st.integers(1, max_rows))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    dirs = np.eye(DIM, dtype=np.float32)[:3]
    rows = (dirs[rng.integers(0, 3, n)]
            + 0.3 * rng.normal(size=(n, DIM))).astype(np.float32)
    index_frames, member_lists = [], []
    for _ in range(n):
        m = int(rng.integers(1, 3 * MCAP + 1) if rng.random() < 0.5
                else rng.integers(1, MCAP // 2 + 1))
        members = rng.choice(np.arange(-60, 60), m, replace=False)
        ifr = (int(rng.choice(members)) if rng.random() < 0.8
               else int(rng.integers(-60, 60)))
        index_frames.append(ifr)
        member_lists.append(members.tolist())
    return rows, index_frames, member_lists


def _insert(mem, batch, step):
    """Insert ``batch`` with its frame ids moved ``step`` ticks on."""
    rows, ifr, members = batch
    off = 40 * step
    mem.insert_batch(rows, scene_ids=[0] * len(rows),
                     index_frames=[f + off for f in ifr],
                     member_lists=[[f + off for f in m] for m in members])


@_settings
@pytest.mark.parametrize("policy", POLICIES)
@given(steps=st.lists(batches(), min_size=1, max_size=12))
def test_horizon_matches_brute_force(policy, steps):
    mem = VenusMemory(CAP, DIM, member_cap=MCAP, **_memory_kw(policy))
    assert mem.min_live_frame() == brute_horizon(mem)
    for step, batch in enumerate(steps):
        _insert(mem, batch, step)
        assert mem.min_live_frame() == brute_horizon(mem)


class _NoEmbedder:
    """The manager's embedder, unused: rows are inserted directly."""

    def embed_queries(self, texts):
        raise AssertionError("tests insert rows directly")

    def embed_frames(self, frames, aux=None, frame_ids=None):
        raise AssertionError("tests insert rows directly")


@settings(max_examples=10, deadline=None)
@pytest.mark.parametrize("policy", POLICIES)
@given(ops=st.lists(st.tuples(st.sampled_from(("insert", "close")),
                              st.integers(0, 2), batches()),
                    min_size=1, max_size=10))
def test_horizon_matches_brute_force_in_arena(policy, ops):
    """Arena-backed sessions: closing one detaches its memory (which
    must keep its horizon) and the next session recycles the slot."""
    cfg = VenusConfig(memory_capacity=CAP, member_cap=MCAP,
                      **_memory_kw(policy))
    mgr = SessionManager(cfg, _NoEmbedder(), embed_dim=DIM)
    sids = [mgr.create_session() for _ in range(3)]
    closed = []
    for step, (op, k, batch) in enumerate(ops):
        sid = sids[k]
        if op == "close":
            closed.append(mgr[sid].memory)
            mgr.close_session(sid)
            sids[k] = mgr.create_session()         # recycles the slot
        else:
            with mgr.arena.deferred_appends():
                _insert(mgr[sid].memory, batch, step)
        for mem in [mgr[s].memory for s in sids] + closed:
            assert mem.min_live_frame() == brute_horizon(mem)
    assert mgr.arena.io_stats["slot_reuses"] == len(closed)
