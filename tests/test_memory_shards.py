"""A configuration that states ``memory_shards`` reaches the sharded arena.

``VenusConfig(memory_shards=4)`` makes ``SessionManager`` build the
four-slab mesh itself. On four host-platform devices (a child process
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so that
these tests run in a one-device test session too) eight streams of
seeded rows go in through ``insert_batch``, and the AKR, top-k and
sampling answers of ``plan``/``execute`` are compared with the plain
flat scan of the benchmark's reference (``benchmarks/onchip/vbench/
ref_scan.py``) and with a one-device manager over the same rows. With
``memory_shards`` 1 no mesh is built, and a count the devices or the
mesh cannot hold raises.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.session import SessionManager, VenusConfig
from repro.launch.mesh import make_memory_mesh

REPO = Path(__file__).resolve().parents[1]
SHARDS = 4
STREAMS = 8
ROWS = 256
DIM = 64
PER_STREAM = 3                       # queries per stream and strategy
STRATEGIES = (("akr", 16), ("topk", 8), ("sampling", 8))
CFG = dict(memory_capacity=ROWS, index_dtype="float32", seed=0)


def _rows(stream: int) -> np.ndarray:
    x = np.random.default_rng([7, stream]).standard_normal((ROWS, DIM))
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def _members(stream: int):
    """(member counts, first member id, index frame id) per row: clusters
    of 1-16 contiguous frames, the index frame in the middle."""
    cnt = np.random.default_rng([8, stream]).permutation(
        np.arange(ROWS) % 16 + 1)
    first = np.concatenate([[0], np.cumsum(cnt)[:-1]]) + 100_000 * stream
    return cnt, first, first + cnt // 2


def _queries(stream: int) -> np.ndarray:
    """Queries near a few of the stream's rows, so that the softmax
    peaks and AKR stops early."""
    g = np.random.default_rng([9, stream])
    rows = _rows(stream)[g.choice(ROWS, PER_STREAM, replace=False)]
    q = rows + 0.3 * g.standard_normal(rows.shape)
    return (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)


def _fill(mgr) -> None:
    for s in range(STREAMS):
        mgr.create_session()
    for s in range(STREAMS):
        cnt, first, ifr = _members(s)
        with mgr.arena.deferred_appends():
            mgr[s].memory.insert_batch(
                _rows(s), scene_ids=list(np.arange(ROWS) // 8),
                index_frames=ifr,
                member_lists=[np.arange(f, f + c) for f, c in
                              zip(first, cnt)])


def _answers(mgr):
    from repro.core.queryplan import QuerySpec
    out = {}
    for strategy, budget in STRATEGIES:
        specs = [QuerySpec(sid=s, embedding=q, strategy=strategy,
                           budget=budget)
                 for s in range(STREAMS) for q in _queries(s)]
        res = mgr.execute(mgr.plan(specs))
        out[strategy] = [(sp.sid, r) for sp, r in zip(specs, res)]
    return out


def _reference(strategy: str, budget: int, stream: int, qi: int,
               chain: int) -> dict:
    """The plain flat scan's answer to one query, and its frame ids."""
    from vbench import ref_scan
    venus = dict(tau=0.1, theta=0.9, beta=1.0)
    s = ref_scan.scores(_rows(stream), _queries(stream))[qi]
    cnt, first, ifr = _members(stream)
    targets = None
    if strategy != "topk":
        key = ref_scan.chain_subkeys(CFG["seed"], chain)[-1]
        targets = ref_scan.draw_targets(key, budget)
    a = ref_scan.answer(s, {"strategy": strategy, "budget": budget},
                        targets, venus["tau"], venus)
    draws = np.asarray(a["draws"], np.int64)
    if strategy == "topk":
        fids = ifr[draws]
    else:
        fids = ref_scan.expand_members(
            draws, first, cnt, ref_scan.expand_u(CFG["seed"], budget))
    return {"draws": draws, "n_drawn": int(a.get("n_drawn", len(draws))),
            "frame_ids": fids}


def child() -> dict:
    """Runs on four host devices: the sharded manager against the
    reference and against one device."""
    import jax
    sys.path.insert(0, str(REPO / "benchmarks" / "onchip"))
    assert len(jax.devices()) == SHARDS, jax.devices()
    mgr = SessionManager(VenusConfig(memory_shards=SHARDS, **CFG), None,
                         DIM)
    one = SessionManager(VenusConfig(**CFG), None, DIM)
    _fill(mgr)
    _fill(one)
    got, want = _answers(mgr), _answers(one)
    report = {"n_shards": mgr.arena.n_shards,
              "emb_devices": len(mgr.arena.emb.sharding.device_set),
              "slots": [mgr[s].memory.slot for s in range(STREAMS)],
              "rows_by_slot": all(
                  np.array_equal(np.asarray(mgr.arena.emb[
                      mgr[s].memory.slot]), _rows(s))
                  for s in range(STREAMS)),
              "io": {k: mgr.io_stats[k] for k in
                     ("group_scans", "sharded_group_scans",
                      "shard_gather_bytes")},
              "one_device_io": {k: one.io_stats[k] for k in
                                ("sharded_group_scans",
                                 "shard_gather_bytes")},
              "gather_timed": all("shard_gather" in r.timings
                                  for v in got.values() for _, r in v),
              "one_device_timed": any("shard_gather" in r.timings
                                      for v in want.values()
                                      for _, r in v),
              "mismatch": {}, "differs_from_one_device": {}}
    chain = {s: 0 for s in range(STREAMS)}   # each session's PRNG chain
    for strategy, budget in STRATEGIES:
        bad, differ = [], 0
        per = {s: 0 for s in range(STREAMS)}
        for (sid, r), (_, w) in zip(got[strategy], want[strategy]):
            differ += int(not (np.array_equal(r.draws, w.draws)
                               and np.array_equal(r.frame_ids, w.frame_ids)
                               and r.n_drawn == w.n_drawn))
            if strategy != "topk":
                chain[sid] += 1
            ref = _reference(strategy, budget, sid, per[sid], chain[sid])
            per[sid] += 1
            n = r.n_drawn
            fids = np.sort(r.frame_ids) if strategy != "topk" \
                else r.frame_ids
            if not (n == ref["n_drawn"]
                    and np.array_equal(np.asarray(r.draws)[:n],
                                       ref["draws"][:n])
                    and np.array_equal(fids, ref["frame_ids"])):
                bad.append(sid)
        report["mismatch"][strategy] = bad
        report["differs_from_one_device"][strategy] = differ
    try:
        SessionManager(VenusConfig(memory_shards=SHARDS, **CFG), None, DIM,
                       mesh=make_memory_mesh(2))
        report["mesh_disagreement"] = "accepted"
    except ValueError as e:
        report["mesh_disagreement"] = str(e)
    return report


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, __file__], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_config_spreads_the_arena_over_four_slabs(sharded):
    assert sharded["n_shards"] == SHARDS
    assert sharded["emb_devices"] == SHARDS
    # streams land on the least-loaded slab, so a slot is not its sid,
    # and each stream's rows are where its own slot says
    assert sharded["slots"] != list(range(STREAMS))
    assert sharded["rows_by_slot"]


@pytest.mark.parametrize("strategy", [s for s, _ in STRATEGIES])
def test_sharded_answers_match_flat_reference(sharded, strategy):
    """Top-k lanes and frame ids, draws, AKR's stop and the expanded
    members equal the flat scan's; the one-device manager's answers are
    the same bit for bit."""
    assert sharded["mismatch"][strategy] == []
    assert sharded["differs_from_one_device"][strategy] == 0


def test_every_group_scan_fans_out_and_gathers(sharded):
    io = sharded["io"]
    assert io["group_scans"] == len(STRATEGIES)
    assert io["sharded_group_scans"] == io["group_scans"]
    assert io["shard_gather_bytes"] > 0
    assert sharded["gather_timed"]
    assert sharded["one_device_io"] == {"sharded_group_scans": 0,
                                        "shard_gather_bytes": 0}
    assert not sharded["one_device_timed"]


def test_a_mesh_that_disagrees_with_the_config_raises(sharded):
    assert "memory_shards=4" in sharded["mesh_disagreement"]


def test_one_shard_builds_no_mesh():
    mgr = SessionManager(VenusConfig(memory_shards=1), None, DIM)
    mgr.create_session()
    assert mgr.mesh is None and not mgr.double_buffer
    assert mgr.arena.n_shards == 1


def test_more_shards_than_devices_raises():
    import jax
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        SessionManager(VenusConfig(memory_shards=n + 1), None, DIM)
    with pytest.raises(ValueError, match="devices"):
        make_memory_mesh(n + 1)
    with pytest.raises(ValueError, match="memory_shards"):
        VenusConfig(memory_shards=0)


if __name__ == "__main__":
    print(json.dumps(child()))
