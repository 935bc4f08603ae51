#!/usr/bin/env python3
"""Record ``data/spans.xplane.pb.gz``: a small traced window of the
served path, for the reducer's tests of program spans.

    python3 benchmarks/onchip/tests/record_spans_trace.py --out <file.gz>

Four streams of 32x32 frames into the smoke MEM (64 wide, two layers)
and a 1,024-row int8 arena with a sliding window; every chunk closes
scenes, so each ingest tick embeds, inserts, scatters and trims. After
a warm-up that compiles every shape, the profiler records a window of
two rounds, each an ingest tick, a query tick of three groups (AKR,
top-k, sampling) and a 2 ms sleep, wrapped in the benchmark's own
``bench.*`` spans as the cells' loops wrap them. The ``.xplane.pb`` the
profiler wrote is kept gzipped. Meant for one TPU v5e; runs on any
platform.
"""

import argparse
import gzip
import itertools
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

STREAMS, RES, CHUNK = 4, 32, 8


def chunk(tick: int, sid: int):
    """Two flat colours, half a chunk each: every chunk closes a scene."""
    import numpy as np
    g = np.random.default_rng(1000 * tick + sid)
    cols = g.uniform(0, 1, (2, 3)).astype(np.float32)
    return np.repeat(cols, CHUNK // 2, axis=0)[:, None, None, :] * np.ones(
        (1, RES, RES, 3), np.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from repro.configs import venus_mem
    from repro.core.pipeline import MEMEmbedder
    from repro.core.session import SessionManager, VenusConfig
    from repro.models.mem import MEM
    from repro.serving.venus_service import StreamQuery, VenusService
    from vbench import program_spans
    from vbench import trace as vtrace
    from vbench.util import Spans

    cfg = venus_mem.smoke_config()
    mem = MEM(cfg)
    emb = MEMEmbedder(mem, jax.jit(mem.init)(jax.random.key(0)), patch=8,
                      text_max_len=16)
    mgr = SessionManager(
        VenusConfig(memory_capacity=1024, member_cap=8, n_max=8,
                    eviction="sliding_window", max_partition_len=8,
                    index_dtype="int8"), emb, embed_dim=cfg.embed_dim)
    svc = VenusService(mgr, engine=None)
    for _ in range(STREAMS):
        mgr.create_session()
    spans = Spans()
    ticks = itertools.count()

    def rounds(n: int, rid: int = 0) -> None:
        for _ in range(n):
            t = next(ticks)
            with spans.span("ingest_tick"):
                svc.ingest_tick({s: chunk(t, s) for s in range(STREAMS)})
            qs = [StreamQuery(rid=rid + i, sid=i % STREAMS,
                              text=f"event {i}", strategy=strat, budget=4,
                              prompt_tokens=np.zeros((0,), np.int32))
                  for i, strat in enumerate(("akr", "topk", "sampling"))]
            with spans.span("plan"):
                plan = svc.plan(qs)
            with spans.span("execute"):
                mgr.execute(plan)
            with spans.span("wait"):
                time.sleep(0.002)

    rounds(3)                                  # compiles every shape
    tmp = tempfile.mkdtemp()
    try:
        with vtrace.capture(tmp):
            with spans.span("window"):
                rounds(2, rid=100)
        path = vtrace.xplane_file(tmp)
        red = vtrace.reduce(path)
        idle = program_spans.reduce(path).idle_by_span
        with open(path, "rb") as src, gzip.open(args.out, "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes, "
          f"{jax.devices()[0].device_kind}, busy {red.busy_s:.6f} s of "
          f"{red.window_s:.6f} s; idle by span {idle}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
