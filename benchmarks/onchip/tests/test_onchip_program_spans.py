"""The program's ``venus.*`` spans as the benchmark reads them: the
reduction of a trace recorded on a TPU v5e by ``record_spans_trace.py``,
the same reduction on a trace with no program spans, the split
``attribute_idle.py`` writes, and the archive-trim readers, alone and in
each cell's traced run at a tiny size on the CPU."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from onchip_testlib import BENCH, run_cell, tiny_checkout

from vbench import program_spans, registry, trace as vtrace

DATA = Path(__file__).resolve().parent / "data"

# what vbench.trace.reduce names the idle of small.xplane.pb (a trace
# with no program spans) by benchmark span, to the last bit
SMALL_IDLE = {"(none)": 2.868100000000262e-05,
              "execute": 0.008213024999999978,
              "wait": 0.005434354000000002}

# (span, per) of the spans inside a tick that name its layers' host time
PER_TICK = [("venus.ingest.segment.scores", "venus.ingest_tick"),
            ("venus.ingest.embed.patchify", "venus.ingest_tick"),
            ("venus.ingest.insert", "venus.ingest_tick"),
            ("venus.ingest.trim", "venus.ingest_tick"),
            ("venus.execute.keys", "venus.execute"),
            ("venus.execute.expand", "venus.execute")]


@pytest.fixture(scope="module")
def spans_trace(tmp_path_factory):
    """Two rounds of an ingest tick of four streams that closes scenes,
    a query tick of three groups and a 2 ms sleep, inside
    ``bench.window``, on one TPU v5e (kept gzipped)."""
    path = tmp_path_factory.mktemp("spans") / "spans.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "spans.xplane.pb.gz").read_bytes()))
    return str(path)


def _attribute_idle():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "attribute_idle", BENCH / "attribute_idle.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_trace_without_program_spans_reduces_as_before():
    path = str(DATA / "small.xplane.pb")
    assert vtrace.reduce(path).idle_by_span == SMALL_IDLE
    prog = program_spans.reduce(path)
    assert prog.spans == []
    assert prog.idle_by_span == SMALL_IDLE
    assert prog.ms_per("venus.ingest.trim", "venus.ingest_tick") is None


def test_recorded_program_spans(spans_trace):
    prog = program_spans.reduce(spans_trace)
    assert prog.count("venus.ingest_tick") == 2
    assert prog.count("venus.execute") == 2
    assert prog.count("venus.execute.group") == 6
    assert prog.count("venus.ingest.segment.scores") == 8
    for name, per in PER_TICK:
        v = prog.ms_per(name, per)
        assert v is not None and v > 0, name
    assert prog.seconds("venus.ingest_tick") >= prog.seconds(
        "venus.ingest.segment") + prog.seconds("venus.ingest.trim")


def test_program_spans_name_the_idle_gaps(spans_trace):
    """Program spans name the idle innermost first; what no program span
    covers keeps its benchmark name, and the idle adds up to
    ``vbench.trace``'s."""
    prog = program_spans.reduce(spans_trace)
    bench = vtrace.reduce(spans_trace).idle_by_span
    named = {k for k in prog.idle_by_span if k.startswith("venus.")}
    assert {"venus.ingest.segment.scores", "venus.ingest.trim",
            "venus.execute.keys", "venus.execute.expand"} <= named
    assert prog.idle_by_span["wait"] == pytest.approx(bench["wait"])
    assert prog.idle_by_span["wait"] >= 0.002 * 2 * 0.9
    assert sum(prog.idle_by_span.values()) == pytest.approx(
        sum(bench.values()))


def test_attribution_splits_each_benchmark_span(spans_trace):
    ai = _attribute_idle()
    out = ai.attribution(vtrace.reduce(spans_trace).idle_by_span,
                         program_spans.reduce(spans_trace))
    for parent, kids in (("ingest_tick", "venus.ingest."),
                         ("execute", "venus.execute.")):
        p = out["parents"][parent]
        assert p["idle_s"] > 0 and 0.8 <= p["share"] <= 1.0 + 1e-9
        assert all(k.startswith(kids) for k in p["children"])
        assert p["children_s"] == pytest.approx(sum(p["children"].values()))
    assert out["ms_per_tick"]["venus.ingest.trim"] > 0
    empty = ai.attribution(SMALL_IDLE, program_spans.reduce(
        str(DATA / "small.xplane.pb")))
    assert empty["parents"]["execute"]["share"] == 0
    assert empty["parents"]["ingest_tick"]["share"] is None
    assert empty["ms_per_tick"] == {}


def _run(ticks, window=(10.0, 20.0)):
    return SimpleNamespace(records={"window": window, "ingest_ticks": ticks},
                           cell=SimpleNamespace(base=BENCH))


@pytest.mark.parametrize("metric", ["ingest.trim_ms", "query.trim_ms"])
def test_trim_readers(metric):
    """The mean ``trim`` of the ingest ticks that start in the window, in
    ms; None on a program whose ``ingest_tick`` returns no ``trim``."""
    read = registry.load_module("metrics", metric).read
    ticks = [{"t0": 9.0, "trim": 5.0},            # warm-up, before it
             {"t0": 10.0, "trim": 0.25}, {"t0": 15.0, "trim": 0.75}]
    assert read(_run(ticks)) == pytest.approx(500.0)
    assert read(_run([{"t0": 10.0, "segment": 1.0}])) is None
    assert read(_run([])) is None
    assert read(SimpleNamespace(records={},
                                cell=SimpleNamespace(base=BENCH))) is None


@pytest.mark.parametrize("cell,metric", [
    ("bgevl-large.ingest", "ingest.trim_ms"),
    ("bgevl-base.query", "query.trim_ms")])
def test_trim_reads_in_the_cells_traced_run(tmp_path, capsys, cell, metric):
    line = run_cell(tiny_checkout(tmp_path), cell, capsys, trace=True)
    assert line["correct"] is True
    assert line["metrics"][metric]["value"] > 0
