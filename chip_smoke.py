#!/usr/bin/env python3
"""Bring-up check of Venus's served path on a TPU.

Drives the path a user calls — ``SessionManager`` + ``VenusService`` —
once, in this one process, on the chip:

* MEM at its published widths (``venus_mem.config()``: a 768-d shared
  space, a 12-layer 768-d text tower and a 12-layer 1024-d vision
  tower), from a seeded init. Pretrained BGE-VL weights are not part of
  the repository; every result below is checked against a reference,
  so random weights are enough to prove the path.
* A ``SessionManager`` at the default ``memory_capacity`` (8192 rows
  per stream) and ``n_max`` (32), with 4 streams of seeded 224²
  ``VideoWorld`` frames (784 patches per frame at patch 8, within the
  vision tower's 1024 positions), one standing spec, and a few ingest
  ticks of 8 frames per stream.
* Query ticks through ``VenusService.plan`` / ``SessionManager.execute``
  mixing AKR, top-k and budgeted sampling across streams.
* One ``VenusService.answer`` through a ``ServingEngine`` that hosts
  the smoke Qwen2-VL config. The VLM is the paper's cloud side: the
  real 7B model holds about 30 GB of float32 parameters and cannot fit
  one 16 GB chip.

Checks, each fatal: the fused scan agrees with the jnp oracle on the
same arena (top-k lanes and scores on the fp32 and int8 index, draws
draw for draw on the fp32 index); every served plan equals a replay of
the same plan, from the same PRNG state, through the oracle; the
standing spec's alert score equals the ad-hoc top-k score over the same
rows; the VLM answers every request.

``--chips 4`` runs only the sharded-arena check: 8 streams through a
manager whose arena is slabbed over 4 chips, against a single-device
manager fed the same ticks and queries in the same process.

Exits non-zero, printing no result, when JAX finds no TPU. The last
line of standard output is one JSON object naming the device.

Usage:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.util import enable_compile_cache  # noqa: E402

CHUNK = 8            # frames per stream per ingest tick
TICKS = 5            # ingest ticks
QUERY_TICKS = 3
TOPK = 8
SEED = 0


class CompileClock:
    """Seconds spent in backend compiles, from JAX's own monitoring
    events (a persistent-cache hit counts only its read)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    """Per-phase wall time of each step and the compile seconds inside
    it: the first step carries most compiles, later steps are steady."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.steps = {}

    def step(self, phase, fn, *args, **kwargs):
        import jax
        c0, t0 = self.clock.seconds, time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        self.steps.setdefault(phase, []).append(
            (time.perf_counter() - t0, self.clock.seconds - c0))
        return out

    def report(self):
        for phase, rows in self.steps.items():
            walls = [w for w, _ in rows]
            steady = min(walls[1:]) if len(walls) > 1 else float("nan")
            print(f"phase {phase}: steps={len(rows)} first_s={walls[0]:.3f}"
                  f" steady_s={steady:.3f}"
                  f" compile_s={sum(c for _, c in rows):.3f}"
                  f" total_s={sum(walls):.3f}")


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def build_embedder(mem_cfg, seed: int = SEED):
    import jax
    from repro.core.pipeline import MEMEmbedder
    from repro.models.mem import MEM
    mem = MEM(mem_cfg)
    return MEMEmbedder(mem, jax.jit(mem.init)(jax.random.key(seed)))


def make_worlds(n: int, resolution: int):
    from repro.data.video import VideoWorld, WorldConfig
    return [VideoWorld(WorldConfig(n_scenes=4, scene_len_min=10,
                                   scene_len_max=16, resolution=resolution,
                                   seed=100 + s)) for s in range(n)]


def chunks(worlds, tick: int):
    return {sid: w.frames[tick * CHUNK:(tick + 1) * CHUNK]
            for sid, w in enumerate(worlds)}


def query_ticks(n_streams: int, vocab: int):
    """Seeded query ticks mixing AKR (budget n_max), top-k and budgeted
    sampling, spread across the streams."""
    import numpy as np
    from repro.serving.venus_service import StreamQuery
    rng = np.random.default_rng(SEED)
    mix = (("akr", None), ("topk", TOPK), ("sampling", TOPK))
    out, rid = [], 0
    for t in range(QUERY_TICKS):
        tick = []
        for j in range(n_streams + 2):
            strategy, budget = mix[(t + j) % len(mix)]
            tick.append(StreamQuery(
                rid=rid, sid=int(rng.integers(n_streams)),
                text=f"find event{int(rng.integers(8))}",
                prompt_tokens=rng.integers(3, vocab, 12),
                strategy=strategy, budget=budget, max_new_tokens=4))
            rid += 1
        out.append(tick)
    return out


def same_results(got, want) -> bool:
    import numpy as np
    return all(np.array_equal(a.draws, b.draws)
               and np.array_equal(a.frame_ids, b.frame_ids)
               and a.n_drawn == b.n_drawn for a, b in zip(got, want))


def execute_vs_oracle(mgr, plan, served: dict):
    """Execute the plan on the platform's kernels (its launches count
    into ``served``), then replay it from the same PRNG state through
    the jnp oracle; both must agree draw for draw and leave the session
    chains in the same state."""
    import jax
    import numpy as np
    from repro.kernels import ops as kops
    before = {sid: st.key for sid, st in mgr.sessions.items()}
    c0 = kops.scan_counts()
    got = mgr.execute(plan)
    served_counts(c0, served)
    after = {sid: st.key for sid, st in mgr.sessions.items()}
    for sid, key in before.items():
        mgr.sessions[sid].key = key
    prev = kops.set_backend("jnp")
    try:
        want = mgr.execute(plan)
    finally:
        kops.set_backend(prev)
    if not same_results(got, want):
        fail("served plan results differ from the jnp oracle replay")
    for sid, key in after.items():
        if not np.array_equal(jax.random.key_data(key),
                              jax.random.key_data(mgr.sessions[sid].key)):
            fail(f"session {sid} PRNG chain differs after the replay")
    return got


def kernel_vs_oracle(mgr, q_emb):
    """The fused scan over the live arena (fp32, and an int8 copy)
    against ``ref.fused_retrieve_stack_ref`` on the same operands."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.memory import quantise_rows
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.kernels.draws import draw_targets
    arena, tau = mgr.arena, mgr.cfg.tau
    s, cap, d = arena.emb.shape
    q = jnp.broadcast_to(jnp.asarray(q_emb)[None], (s,) + q_emb.shape)
    keys = jax.random.split(jax.random.key(SEED + 1), s * q_emb.shape[0])
    targets = jax.vmap(lambda k: draw_targets(k, mgr.cfg.n_max))(
        keys).reshape(s, q_emb.shape[0], -1)
    windows = arena.device_windows()
    int8 = jnp.asarray(quantise_rows(
        np.asarray(arena.emb).reshape(-1, d))[0].reshape(s, cap, d))
    for name, index in (("fp32", arena.emb), ("int8", int8)):
        fr = kops.fused_retrieve_stack(q, index, tau=tau, valid=windows,
                                       targets=targets, n_topk=TOPK)
        want = ref.fused_retrieve_stack_ref(q, index, windows, targets,
                                            tau=tau, n_topk=TOPK)
        if not np.array_equal(np.asarray(fr.topk_i),
                              np.asarray(want.topk_i)):
            fail(f"{name} top-k lanes differ from the oracle")
        np.testing.assert_allclose(np.asarray(fr.topk_v),
                                   np.asarray(want.topk_v),
                                   rtol=1e-5, atol=1e-6)
        draws = np.asarray(fr.draws)
        want_draws = np.clip(np.asarray(want.counts), 0, cap - 1)
        agree = float(np.mean(draws == want_draws))
        print(f"oracle {name}: top-k lanes equal, scores within 1e-5, "
              f"draw agreement {agree:.6f} over {draws.size} draws")
        if name == "fp32" and agree != 1.0:
            fail("fp32 fused draws differ from the oracle")


def adhoc_topk_score(mgr, sid: int, emb) -> float:
    """Best cosine score an ad-hoc top-k scan over ``sid``'s rows gives
    for ``emb`` — one fused launch over the whole arena."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops
    arena = mgr.arena
    s, _, d = arena.emb.shape
    q = np.zeros((s, 1, d), np.float32)
    slot = mgr[sid].memory.slot
    q[slot, 0] = emb
    fr = kops.fused_retrieve_stack(
        jnp.asarray(q), arena.emb, tau=mgr.cfg.tau,
        valid=arena.device_windows(),
        targets=jnp.zeros((s, 1, 1), jnp.float32), n_topk=1)
    return float(fr.topk_v[slot, 0, 0])


def served_counts(before: dict, totals: dict) -> None:
    from repro.kernels import ops as kops
    for k, v in kops.scan_counts().items():
        totals[k] = totals.get(k, 0) + v - before[k]


def run_one_chip(cfg, mem_cfg, engine_cfg, *, n_streams: int = 4,
                 resolution: int = 224, clock: CompileClock):
    """The one-chip phases; raises SystemExit on any mismatch."""
    import jax
    import numpy as np
    from repro.core.queryplan import QuerySpec
    from repro.core.session import SessionManager
    from repro.kernels import ops as kops
    from repro.models.transformer import Transformer
    from repro.serving.engine import ServingEngine
    from repro.serving.venus_service import VenusService

    phases = Phases(clock)
    embedder = phases.step("build_mem", build_embedder, mem_cfg)
    print(f"mem: {mem_cfg.name} embed_dim={mem_cfg.embed_dim} "
          f"params={sum(x.size for x in jax.tree.leaves(embedder.params))}")
    print(f"kernels: backend={kops.backend()} "
          f"interpret={kops._interpret()}")
    engine = ServingEngine(engine_cfg, jax.jit(Transformer(engine_cfg).init)(
        jax.random.key(SEED)), batch_slots=4, max_len=512)
    mgr = SessionManager(cfg, embedder, embed_dim=mem_cfg.embed_dim)
    svc = VenusService(mgr, engine)
    worlds = make_worlds(n_streams, resolution)
    for _ in worlds:
        svc.create_stream()

    alerts, adhoc = [], []
    spec_id = svc.register_standing(
        0, QuerySpec(sid=0, text="find event3", strategy="topk",
                     budget=4), threshold=-1.0)
    spec_emb = mgr.standing.entries[spec_id].embedding
    svc.on_alert(alerts.append)

    served = {}
    # ingest ticks, then a flush that closes every open partition
    steps = [(svc.ingest_tick, chunks(worlds, t)) for t in range(TICKS)]
    for fn, *arg in steps + [(svc.flush,)]:
        c0 = kops.scan_counts()
        n_alerts = len(alerts)
        phases.step("ingest", fn, *arg)
        served_counts(c0, served)
        if len(alerts) > n_alerts:
            # the spec fires on stream 0's first committing tick, so the
            # tick's new rows are all of stream 0's rows right now
            adhoc.append(adhoc_topk_score(mgr, 0, spec_emb))
    rows = [mgr[s].memory.size for s in range(n_streams)]
    print(f"ingest: rows per stream {rows}")
    if not all(rows):
        fail("a stream committed no memory rows")
    if len(alerts) != 1 or len(adhoc) != 1:
        fail(f"expected one standing alert, got {len(alerts)}")
    print(f"standing: alert score {alerts[0].score!r} "
          f"ad-hoc top-k score {adhoc[0]!r}")
    if alerts[0].score != adhoc[0]:
        fail("standing alert score differs from the ad-hoc top-k score")

    ticks = query_ticks(n_streams, engine_cfg.vocab_size)
    for tick in ticks:
        res = phases.step("query", execute_vs_oracle, mgr, svc.plan(tick),
                          served)
        if not all(len(r.frame_ids) for r in res):
            fail("a query retrieved no frames")
    print(f"query: {sum(map(len, ticks))} queries in {len(ticks)} ticks "
          f"agree with the oracle replay")

    phases.step("oracle_scan", kernel_vs_oracle, mgr,
                embedder.embed_queries([q.text for q in ticks[0][:4]]))

    c0 = kops.scan_counts()
    reqs = phases.step("answer", svc.answer, ticks[0][:2])
    served_counts(c0, served)
    for r in reqs:
        toks = np.asarray(r.generated)
        if not (0 < len(toks) <= r.max_new_tokens
                and np.all((toks >= 0) & (toks < engine_cfg.vocab_size))):
            fail(f"request {r.rid} answered {toks}")
    print(f"answer: {len(reqs)} requests, tokens "
          f"{[list(map(int, r.generated)) for r in reqs]}")

    phases.report()
    print("counters: " + " ".join(
        f"kops_{k}={served[k]}" for k in
        ("fused_draw_launches", "standing_scan_bytes", "scan_bytes",
         "dense_score_launches")))
    if not (served["fused_draw_launches"] and served["standing_scan_bytes"]):
        fail("fused or standing launch counters are zero")
    io = svc.io_stats()
    print(f"io: stack_rebuilds={io['stack_rebuilds']} "
          f"alerts_fired={io['alerts_fired']} "
          f"group_scans={io['group_scans']}")


def run_sharded(cfg, mem_cfg, *, shards: int = 4, n_streams: int = 8,
                resolution: int = 224, clock: CompileClock):
    """A manager whose arena is slabbed over ``shards`` devices against
    a single-device manager: same ticks, same queries, identical
    answers draw for draw."""
    import jax
    from repro.core.session import SessionManager
    from repro.launch.mesh import make_memory_mesh

    phases = Phases(clock)
    embedder = phases.step("build_mem", build_embedder, mem_cfg)
    mesh = make_memory_mesh(shards)
    mgrs = {"sharded": SessionManager(cfg, embedder, mem_cfg.embed_dim,
                                      mesh=mesh),
            "single": SessionManager(cfg, embedder, mem_cfg.embed_dim)}
    worlds = make_worlds(n_streams, resolution)
    for m in mgrs.values():
        for _ in worlds:
            m.create_session()
    for t in range(TICKS):
        for name, m in mgrs.items():
            phases.step(f"ingest_{name}", m.ingest_tick, chunks(worlds, t))
    for name, m in mgrs.items():
        phases.step(f"ingest_{name}", m.flush)
    for tick in query_ticks(n_streams, 512):
        specs = [q.to_spec() for q in tick]
        got = {name: phases.step(f"query_{name}", m.query_specs, specs)
               for name, m in mgrs.items()}
        if not same_results(got["sharded"], got["single"]):
            fail("sharded answers differ from the single-device manager")
    phases.report()
    arena = mgrs["sharded"].arena
    print(f"arena_shards={arena.n_shards} "
          f"emb_device_set={len(arena.emb.sharding.device_set)} "
          f"slots={arena.n_sessions}")
    held = {d: 0 for d in jax.devices()}
    for shard in arena.emb.addressable_shards:
        held[shard.device] += shard.data.nbytes
    print("arena_emb_bytes: " + " ".join(
        f"{d.id}:{n}" for d, n in held.items()))
    print("bytes_in_use: " + " ".join(
        f"{d.id}:{(d.memory_stats() or {}).get('bytes_in_use')}"
        for d in jax.devices()))
    if arena.n_shards != shards or sum(n > 0 for n in held.values()) \
            != shards:
        fail("the arena is not spread over every shard")
    print(f"sharded: {n_streams} streams, answers identical to one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-arena check on 4 chips")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    clock = CompileClock()
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"cache={cache_dir}")

    from repro.configs import registry, venus_mem
    from repro.core.session import VenusConfig
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(VenusConfig(), venus_mem.config(), shards=4,
                    clock=clock)
    else:
        run_one_chip(VenusConfig(), venus_mem.config(),
                     registry.get_smoke_config("qwen2-vl-7b"), clock=clock)
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"compile_s={clock.seconds:.3f} compiles={clock.compiles} "
          f"cache_hits={clock.cache_hits} "
          f"wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
