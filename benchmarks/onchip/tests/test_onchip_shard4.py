"""The four-chip query cell, driven past the TPU check at a tiny size on
four host-platform CPU devices (a child process under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the result
line's contract with the arena split over four slabs, and a fault on
one slab only that the check has to see."""

import contextlib
import io
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from onchip_testlib import REPO, SEED, _edit, tiny_checkout  # noqa: E402

CELL = "bgevl-large.shard4"
CHIPS = 4
STREAMS = 8


def _run(checkout: Path) -> dict:
    from vbench import harness
    from vbench.registry import Cell
    cell = Cell(CELL, base=checkout / "benchmarks" / "onchip")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = harness.run(cell, seed=SEED, seconds=2.0, trace=False,
                         t_start=time.perf_counter(), checkout=checkout)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _busiest_slab(checkout: Path) -> int:
    """The slab that holds the stream the window queries most: streams
    are placed as the program places them, the schedule is the mix's."""
    import numpy as np
    from repro.core.session import SessionManager, VenusConfig
    from vbench.registry import Cell
    cell = Cell(CELL, base=checkout / "benchmarks" / "onchip")
    sch = cell.generator().schedule(cell.traffic, STREAMS, SEED, 2.0)
    top = int(np.bincount(sch["sid"], minlength=STREAMS).argmax())
    mgr = SessionManager(VenusConfig(memory_capacity=8,
                                     memory_shards=CHIPS), None, 8)
    for _ in range(STREAMS):
        mgr.create_session()
    return mgr[top].memory.slot // (mgr.arena.n_sessions // CHIPS)


def child(tmp: Path) -> dict:
    """Both runs, on four host devices: as built, and with the draws and
    top-k lanes of one slab shifted by a row."""
    import jax
    assert len(jax.devices()) == CHIPS, jax.devices()
    checkout = tiny_checkout(tmp)
    bench = checkout / "benchmarks" / "onchip"
    _edit(bench / "configs" / "venus-bgevl-large.shard4.json",
          lambda c: c.update(streams=STREAMS))
    out = {"clean": _run(checkout)}
    from repro.kernels import ops as kops
    real = kops.fused_retrieve_stack
    bad = _busiest_slab(checkout)

    def altered(query, index, **kw):
        fr = real(query, index, **kw)
        slab = index.shape[0] // CHIPS
        n = index.shape[1]
        on = (jax.numpy.arange(index.shape[0]) // slab == bad)[:, None,
                                                               None]
        return fr._replace(
            draws=jax.numpy.where(on, (fr.draws + 1) % n, fr.draws),
            topk_i=jax.numpy.where(on, (fr.topk_i + 1) % n, fr.topk_i))

    kops.fused_retrieve_stack = altered
    try:
        out["one_slab_shifted"] = _run(checkout)
    finally:
        kops.fused_retrieve_stack = real
    return out


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    tmp = tmp_path_factory.mktemp("onchip-shard4")
    p = subprocess.run([sys.executable, __file__, str(tmp)], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_shard4_result_line(lines):
    line = lines["clean"]
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"query_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == CHIPS
    assert set(line["checks"]) == {"index_mismatch", "text_emb_gap",
                                   "topk_gap", "draw_gap",
                                   "answer_mismatch"}


def test_answers_altered_on_one_slab_are_not_correct(lines):
    line = lines["one_slab_shifted"]
    assert line["correct"] is False
    checks = line["checks"]
    assert checks["index_mismatch"]["value"] == 0
    assert any(checks[k]["value"] > checks[k]["limit"]
               for k in ("topk_gap", "draw_gap", "answer_mismatch"))


if __name__ == "__main__":
    print(json.dumps(child(Path(sys.argv[1]))))
