"""A cluster larger than ``member_cap`` keeps its index frame.

``insert_batch`` cuts such a cluster to a reservoir of ``member_cap``
members: the index frame, when it is a member, plus a uniform draw from
the others. A query expands a row through its reservoir, so a reservoir
without its index frame answers for a cluster whose keyframe it cannot
show.
"""

import numpy as np
import pytest

from repro.core.memory import VenusMemory

DIM = 4


def _insert(mem, index_frames, member_lists):
    n = len(index_frames)
    mem.insert_batch(np.ones((n, DIM), np.float32), scene_ids=[0] * n,
                     index_frames=index_frames, member_lists=member_lists)


def _rows(mem):
    """(index frame, members) of every live row, oldest first."""
    phys = (mem.head + np.arange(mem.size)) % mem.capacity
    return [(int(mem._index_frame[p]),
             mem._members[p, :mem._member_count[p]].tolist()) for p in phys]


def _violations(parts, rows):
    """Rows whose index frame is not among their members, or whose
    members do not all lie inside one partition [start, end)."""
    bad = 0
    for ifr, members in rows:
        inside = any(s <= min(members) and max(members) < e
                     for s, e in parts)
        bad += (ifr not in members) or not inside
    return bad


@pytest.mark.parametrize("seed", range(20))
def test_oversized_cluster_keeps_index_frame(seed):
    members = list(range(1000, 1177))               # 177 members
    ifr = 1000 + int(np.random.default_rng(seed).integers(177))
    mem = VenusMemory(8, DIM, member_cap=128, seed=seed)
    _insert(mem, [ifr], [members])
    ((got_ifr, kept),) = _rows(mem)
    assert got_ifr == ifr and ifr in kept
    assert len(kept) == 128 and len(set(kept)) == 128
    assert kept == sorted(kept) and set(kept) <= set(members)


def test_reservoir_draws_the_rest_uniformly():
    """Every member other than the index frame is kept with the same
    probability, (member_cap - 1) / (m - 1)."""
    m, cap, trials = 12, 4, 3000
    mem = VenusMemory(trials, DIM, member_cap=cap, seed=1)
    _insert(mem, [5] * trials, [list(range(m))] * trials)
    hits = np.zeros(m)
    for _ifr, kept in _rows(mem):
        hits[kept] += 1
    assert hits[5] == trials
    others = np.delete(hits, 5) / trials
    np.testing.assert_allclose(others, (cap - 1) / (m - 1), atol=0.04)


def test_cluster_without_index_frame_keeps_uniform_draw():
    """A member list that does not hold its index frame is drawn as
    before: member_cap positions uniformly from the whole list."""
    members = list(range(40))
    mem = VenusMemory(4, DIM, member_cap=8, seed=3)
    _insert(mem, [99], [members])
    want = np.asarray(members)[np.sort(
        np.random.default_rng(3).choice(40, 8, replace=False))]
    assert _rows(mem) == [(99, want.tolist())]


def test_clusters_at_or_under_cap_are_stored_whole():
    mem = VenusMemory(4, DIM, member_cap=8)
    lists = [[3, 1, 2], list(range(10, 18)), [7]]
    _insert(mem, [1, 14, 7], lists)
    assert [kept for _ifr, kept in _rows(mem)] == lists
    assert mem.io_stats["reservoir_sampled"] == 0


def test_reservoir_sampled_counts_reduced_clusters():
    mem = VenusMemory(8, DIM, member_cap=4)
    _insert(mem, [0, 10, 20, 30],
            [list(range(0, 4)), list(range(10, 15)), [20],
             list(range(30, 42))])
    assert mem.io_stats["reservoir_sampled"] == 2
    _insert(mem, [50], [list(range(44, 60))])
    assert mem.io_stats["reservoir_sampled"] == 3
    stats = mem.io_stats
    mem.reset_io_stats()
    assert stats is mem.io_stats and stats["reservoir_sampled"] == 0


def test_repeated_long_partition_keeps_every_index_frame():
    """A stream that replays one partition whose first cluster outgrows
    the reservoir. With the memory's own generator, default_rng(0), a
    uniform draw over the whole cluster drops the index frame within a
    few repeats; every stored row must still hold its index frame and
    lie inside its partition."""
    plen, big, cap = 20, 13, 8          # clusters of 13 and 7 members
    at = 6                              # index frame's place in the big one
    old = np.random.default_rng(0)      # what a uniform draw would keep
    dropped_at = None
    for r in range(40):
        if at not in old.choice(big, cap, replace=False):
            dropped_at = r
            break
    assert dropped_at is not None       # the case the replay must reach
    n_parts = dropped_at + 2
    mem = VenusMemory(2 * n_parts, DIM, member_cap=cap, seed=0)
    parts = []
    for r in range(n_parts):
        s = r * plen
        parts.append((s, s + plen))
        _insert(mem, [s + at, s + big + 3],
                [list(range(s, s + big)), list(range(s + big, s + plen))])
    assert mem.io_stats["reservoir_sampled"] == n_parts
    assert _violations(parts, _rows(mem)) == 0
