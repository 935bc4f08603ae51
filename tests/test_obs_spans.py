"""Program spans in the profiler's trace: one ingest tick and one query
tick of a small ``SessionManager`` with the smoke MEM, profiled with
``jax.profiler`` on the CPU. The ``venus.*`` spans appear nested as the
layers nest, and the tick's stage times are the spans' durations."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

from repro.configs import venus_mem
from repro.core.pipeline import MEMEmbedder
from repro.core.queryplan import QuerySpec
from repro.core.session import SessionManager, VenusConfig
from repro.models.mem import MEM
from repro.obs import span

RES = 16
STREAMS = 2

# child -> parent, every span the two ticks open
TREE = {
    "venus.ingest.segment": "venus.ingest_tick",
    "venus.ingest.segment.archive": "venus.ingest.segment",
    "venus.ingest.segment.scores": "venus.ingest.segment",
    "venus.ingest.cluster": "venus.ingest_tick",
    "venus.ingest.embed": "venus.ingest_tick",
    "venus.ingest.embed.patchify": "venus.ingest.embed",
    "venus.ingest.embed.tower": "venus.ingest.embed",
    "venus.ingest.insert": "venus.ingest_tick",
    "venus.ingest.standing": "venus.ingest_tick",
    "venus.ingest.trim": "venus.ingest_tick",
    "venus.execute.embed_text": "venus.execute",
    "venus.execute.group": "venus.execute",
    "venus.execute.keys": "venus.execute.group",
    "venus.execute.scan": "venus.execute.group",
    "venus.execute.expand": "venus.execute.group",
}


def _chunk(tick, sid):
    """Two flat colours, four frames each: every chunk closes a scene."""
    g = np.random.default_rng(100 * tick + sid)
    cols = g.uniform(0, 1, (2, 3)).astype(np.float32)
    return np.repeat(cols, 4, axis=0)[:, None, None, :] * np.ones(
        (1, RES, RES, 3), np.float32)


def _specs():
    return [QuerySpec(sid=0, text="a red scene", strategy="akr", budget=4),
            QuerySpec(sid=1, text="a blue scene", strategy="topk",
                      budget=2)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = venus_mem.smoke_config()
    mem = MEM(cfg)
    emb = MEMEmbedder(mem, mem.init(jax.random.key(0)), patch=8,
                      text_max_len=16)
    mgr = SessionManager(
        VenusConfig(memory_capacity=16, member_cap=8,
                    eviction="sliding_window", max_partition_len=8),
        emb, embed_dim=cfg.embed_dim)
    for _ in range(STREAMS):
        mgr.create_session()
    mgr.register_standing(0, QuerySpec(sid=0, text="a red scene",
                                       strategy="topk", budget=2),
                          threshold=2.0)
    for t in range(2):                      # compile outside the trace
        mgr.ingest_tick({s: _chunk(t, s) for s in range(STREAMS)})
        mgr.execute(mgr.plan(_specs()))
    d = str(tmp_path_factory.mktemp("trace"))
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        stages = mgr.ingest_tick({s: _chunk(2, s) for s in range(STREAMS)})
        plan = mgr.plan(_specs(), rids=[7, 9])
        results = mgr.execute(plan)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name.split("#")[0], ev.start_ns, ev.end_ns,
                            {k: v for k, v in ev.stats})
                           for ev in line.events
                           if ev.name.startswith("venus.")]
    return stages, plan, results, events


def _parent(ev, events):
    """The shortest other span that encloses ``ev``."""
    outer = [o for o in events if o is not ev and o[1] <= ev[1]
             and ev[2] <= o[2] and (o[2] - o[1]) > (ev[2] - ev[1])]
    return min(outer, key=lambda o: o[2] - o[1])[0] if outer else None


def test_spans_nest_as_the_layers(traced):
    stages, _, _, events = traced
    assert stages["embedded"] > 0
    names = {e[0] for e in events}
    assert set(TREE) | {"venus.ingest_tick", "venus.plan",
                        "venus.execute"} <= names
    for ev in events:
        if ev[0] in TREE:
            assert _parent(ev, events) == TREE[ev[0]], ev[0]
    for top in ("venus.ingest_tick", "venus.plan", "venus.execute"):
        assert all(_parent(ev, events) is None for ev in events
                   if ev[0] == top)
    per = {n: sum(e[0] == n for e in events) for n in names}
    assert per["venus.ingest.segment.scores"] == 1       # one per tick
    assert per["venus.ingest.segment.archive"] == STREAMS
    assert per["venus.execute.group"] == 2


def test_spans_carry_their_args(traced):
    _, plan, _, events = traced
    by = {e[0]: e[3] for e in events}
    assert by["venus.ingest_tick"] == {"streams": STREAMS,
                                       "frames": 8 * STREAMS}
    assert plan.tick == by["venus.plan"]["tick"] == \
        by["venus.execute"]["tick"]
    assert by["venus.execute"]["rids"] == "7;9"
    groups = [e[3] for e in events if e[0] == "venus.execute.group"]
    assert {g["strategy"] for g in groups} == {"akr", "topk"}
    assert all(g["lanes"] == STREAMS and g["qmax"] == 1 for g in groups)


def test_stage_times_are_the_spans(traced):
    stages, _, results, events = traced

    def one(name):
        (ev,) = [e for e in events if e[0] == name]
        return ev

    ms = 1e6          # ns per ms
    for key, name in (("segment", "venus.ingest.segment"),
                      ("cluster", "venus.ingest.cluster"),
                      ("trim", "venus.ingest.trim")):
        ev = one(name)
        assert abs(stages[key] * 1e9 - (ev[2] - ev[1])) < ms, key
    span_ns = one("venus.ingest.trim")[2] - one("venus.ingest.embed")[1]
    assert abs(stages["embed_insert"] * 1e9 - span_ns) < ms
    groups = sorted((e for e in events if e[0] == "venus.execute.group"),
                    key=lambda e: e[1])
    for key, name in (("keys", "venus.execute.keys"),
                      ("similarity", "venus.execute.scan"),
                      ("sample_expand", "venus.execute.expand")):
        # the first group is the first spec's
        ev = min((e for e in events if e[0] == name), key=lambda e: e[1])
        assert groups[0][1] <= ev[1] <= groups[0][2]
        assert abs(results[0].timings[key] * 1e9 - (ev[2] - ev[1])) < ms, \
            key
    text = one("venus.execute.embed_text")
    assert all(abs(r.timings["embed_query"] * 1e9 - (text[2] - text[1]))
               < ms for r in results)
    assert set(results[0].timings) == {"embed_query", "keys",
                                       "similarity", "sample_expand"}


def test_span_measures_itself_without_a_profiler():
    with span("test.outer") as outer:
        with span("test.inner", k=1) as inner:
            pass
    assert 0 <= inner.seconds <= outer.seconds
    assert outer.start <= inner.start <= inner.end <= outer.end
