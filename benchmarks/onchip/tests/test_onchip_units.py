"""The benchmark's yardstick pieces on their own: the counting functions
against hand arithmetic, the trace reduction against a trace recorded
on a TPU v5e, the files a cell is found by, the entry point's refusal
without a TPU, and imports that touch no device."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from onchip_testlib import BENCH, REPO

from vbench import counting, registry, trace as vtrace

DATA = Path(__file__).resolve().parent / "data"


def _config(name):
    return registry.load_json("configs", name)


# ------------------------------------------------------------- counting

def test_vision_flops_vit_l14_per_frame():
    # 24 layers x 256 tokens x (4*1024^2*2 + 2*1024*4096*2 + 4*256*1024)
    # = 161.06 GFLOP, plus the 1024x768 projection
    want = 24 * (2 * 256 * 1024 * 4096 + 4 * 256 * 1024 * 4096
                 + 4 * 256 * 256 * 1024) + 2 * 1024 * 768
    got = counting.vision_flops_per_frame(
        _config("venus-bgevl-large.fp32")["mem"])
    assert got == want
    assert got == pytest.approx(161.06e9, rel=1e-3)


def test_vision_flops_vit_b16_per_frame():
    # 12 layers x 196 tokens at width 768, FFN 3072: 34.72 GFLOP
    want = 12 * (2 * 196 * 768 * 3072 + 4 * 196 * 768 * 3072
                 + 4 * 196 * 196 * 768) + 2 * 768 * 512
    got = counting.vision_flops_per_frame(
        _config("venus-bgevl-base.int8")["mem"])
    assert got == want
    assert got == pytest.approx(34.72e9, rel=1e-3)


@pytest.mark.parametrize("name,tokens,want", [
    # 12 layers x (2*4*768*3072 + 4*4*768*3072 + 4*16*768) + 2*768*768
    ("venus-bgevl-large.fp32", 4, 681_246_720),
    # 12 layers x (2*4*512*2048 + 4*4*512*2048 + 4*16*512) + 2*512*512
    ("venus-bgevl-base.int8", 4, 302_907_392),
])
def test_text_flops_count_real_tokens(name, tokens, want):
    assert counting.text_flops(_config(name)["mem"], tokens) == want


def test_scan_work_counts_targeted_rows_once():
    # two streams of 1000 valid rows, 3 and 1 queries, d = 512, int8
    flops, nbytes = counting.scan_work(512, 1, [(1000, 3), (1000, 1)], 10)
    assert flops == 2 * 512 * (1000 * 3 + 1000 * 1)
    assert nbytes == 2 * 1000 * 512 + (3 + 1) * (512 + 10) * 4
    t, bound = counting.least_seconds(flops, nbytes, 393e12, 819e9)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_least_scan_seconds_over_a_tick():
    cfg = _config("venus-bgevl-base.int8")
    peaks = json.loads((BENCH / "peaks.json").read_text())[
        "devices"]["TPU v5 lite"]
    tick = {"groups": [("topk", 8, [(3, 2)]), ("akr", 32, [(0, 1),
                                                           (5, 1)])]}
    t = counting.least_scan_seconds(cfg, peaks, [tick])
    rows = 32768 * 512
    want = ((rows + 2 * (512 + 3 + 16 + 3) * 4)
            + (2 * rows + 2 * (512 + 96 + 2 + 3) * 4)) / 819e9
    assert t == pytest.approx(want)


# -------------------------------------------------------------- traffic

def test_query_arrivals_are_the_same_for_every_seed():
    mix = registry.load_json("traffic", "fleet-queries.base")
    gen = registry.load_module("traffic", mix["generator"])
    a, b = (gen.schedule(mix, 64, seed, 50.0) for seed in (7, 2**31 + 5))
    assert len(a["t"]) == round(mix["rate_qps"] * 50.0)
    np.testing.assert_array_equal(a["t"], b["t"])
    for key in ("sid", "kind", "text"):
        assert not np.array_equal(a[key], b[key])
        assert sorted(np.bincount(a[key], minlength=64)) == \
            sorted(np.bincount(b[key], minlength=64))


def test_query_mix_cameras_show_a_still_frame():
    """No frame-to-frame change for segmentation to cut on: the
    reference holds the history prefill and nothing new."""
    video = registry.load_json("traffic", "fleet-queries.base")["video"]
    gen = registry.load_module("traffic", video["generator"])
    for pool in gen.make_pools(dict(video, resolution=32), 4, 2**31 + 3):
        assert (pool == pool[:1]).all()


# ---------------------------------------------------------------- trace

def test_union_and_idle_naming():
    busy = vtrace._union(np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0]]))
    assert busy.tolist() == [[0.0, 2.0], [3.0, 4.0]]
    spans = [("bench.ingest_tick", 1.5, 3.5), ("bench.wait", 4.0, 5.0)]
    gaps = vtrace._name_gaps(busy, spans, 0.0, 5.0)
    assert gaps == pytest.approx({"ingest_tick": 1.0, "wait": 1.0})


def test_reduce_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: two rounds of a text-tower call
    and a fused scan inside ``bench.execute``, then a 2 ms sleep inside
    ``bench.wait``, all inside ``bench.window``."""
    red = vtrace.reduce(str(DATA / "small.xplane.pb"))
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert red.seconds_matching("encode_text", modules=True) > 0
    assert red.count_matching("fused_retrieve_scan_stack") == 2
    assert red.seconds_matching("fused_retrieve_scan_stack") > 0
    assert red.idle_by_span.get("wait", 0) >= 0.002 * 2 * 0.9
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


# ------------------------------------------------------------ registry

def test_benchmark_json_matches_the_files():
    bench = registry.benchmark_json()
    assert bench["paths"] == ["benchmarks/onchip"]
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
        assert _config(c["name"])["name"] == c["name"]
        assert _config(c["name"])["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        cell = registry.Cell(w["name"])
        assert (cell.spec["config"], cell.spec["traffic"], cell.chips,
                cell.spec["why"]) == (w["config"], w["traffic"],
                                      w["chips"], w["why"])
        cell.driver()
        cell.generator()
    for m in bench["per_layer"]:
        assert hasattr(registry.load_module("metrics", m["name"]), "read")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_and_a_layer():
    bench = registry.benchmark_json()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in registry.cell_metrics(bench, w["name"],
                                                        False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.cell_metrics(bench, w["name"], True)


def test_unknown_device_kind_is_an_error():
    from vbench.util import peaks
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v99")


# ----------------------------------------------------------- entry point

def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_entry_point_without_a_tpu_prints_no_result(tmp_path):
    args = ["--workload", "bgevl-base.query", "--seed", "1",
            "--seconds", "1", "--trace", "0"]
    env = dict(_env(), JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(BENCH / "run_cell.py"), *args],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "benchmarks/onchip/run_cell.py",
                        "--workload", "bgevl-base.query", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_imports_touch_no_device():
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "from vbench import registry, harness, build, ref_mem, ref_scan, "
        "ref_scene, trace, counting\n"
        "for w in ('bgevl-large.ingest', 'bgevl-base.query'):\n"
        "    c = registry.Cell(w); c.driver(); c.generator()\n"
        "for m in registry.benchmark_json()['per_layer']:\n"
        "    registry.load_module('metrics', m['name'])\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends))\n" % (str(BENCH),
                                               str(REPO / "src")))
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip().splitlines()[-1] == "0"
