"""The query cells' run, driven past the TPU check at a tiny size on the
CPU: the result line's contract, the comparison, and a fault."""

import json

import pytest

from onchip_testlib import run_cell, tiny_checkout

QUERY_CELLS = ["bgevl-base.query"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("onchip-query"))


@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_query_cell_result_line(checkout, cell, capsys):
    line = run_cell(checkout, cell, capsys)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"query_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert set(line["checks"]) == {"index_mismatch", "text_emb_gap",
                                   "topk_gap", "draw_gap",
                                   "answer_mismatch"}


def test_altered_answers_are_not_correct(checkout, capsys, monkeypatch):
    """An answer altered where it is produced: the fused scan's draws
    and top-k lanes shifted by one row."""
    from repro.kernels import ops as kops
    real = kops.fused_retrieve_stack

    def altered(query, index, **kw):
        fr = real(query, index, **kw)
        n = index.shape[1]
        return fr._replace(draws=(fr.draws + 1) % n,
                           topk_i=(fr.topk_i + 1) % n)

    monkeypatch.setattr(kops, "fused_retrieve_stack", altered)
    line = run_cell(checkout, "bgevl-base.query", capsys)
    assert line["correct"] is False
    assert line["checks"]["topk_gap"]["value"] > \
        line["checks"]["topk_gap"]["limit"]


def test_a_new_cell_is_only_a_file(checkout, capsys):
    """A cell added as one workload file (and its BENCHMARK.json entry)
    runs with no other file edited, traced."""
    bench = checkout / "benchmarks" / "onchip"
    spec = json.loads((bench / "workloads" / "bgevl-base.query.json")
                      .read_text())
    (bench / "workloads" / "throwaway.query.json").write_text(
        json.dumps(spec))
    bj = json.loads((checkout / "BENCHMARK.json").read_text())
    for m in bj["end_to_end"] + bj["per_layer"]:
        if "bgevl-base.query" in m.get("workloads", []):
            m["workloads"].append("throwaway.query")
    (checkout / "BENCHMARK.json").write_text(json.dumps(bj))
    line = run_cell(checkout, "throwaway.query", capsys, trace=True)
    assert line["correct"] is True
    # the host-side per-layer metrics read on the CPU; device ones are
    # left out because the CPU trace has no device plane
    assert "query.exec_ms" in line["metrics"]
    assert line["metrics"]["query.p50_ms"]["value"] > 0
    assert "query.device_idle" not in line["metrics"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
