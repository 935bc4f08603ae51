"""Tiny copies of the benchmark for CPU tests.

``tiny_checkout`` copies ``benchmarks/onchip`` and ``BENCHMARK.json``
into a temporary checkout and shrinks every configuration and mix to a
size a test holds (64-wide MEM towers of two layers, a few streams of a
few hundred rows, 32x32 frames); the harness then runs there on the CPU
exactly as on the chip, past the entry point's look for a TPU.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2**31 + 12345          # larger than 32 signed bits hold


def _edit(path: Path, fn) -> None:
    data = json.loads(path.read_text())
    fn(data)
    path.write_text(json.dumps(data))


def _shrink_config(c: dict) -> None:
    m = c["mem"]
    m.update(embed_dim=64, image_size=32, patch=8, text_max_len=16)
    m["vision"] = {"num_layers": 2, "d_model": 64, "num_heads": 2,
                   "d_ff": 128, "max_seq_len": 17}
    m["text"] = {"num_layers": 2, "d_model": 64, "num_heads": 2,
                 "d_ff": 128, "vocab_size": 512, "max_seq_len": 16}
    c["venus"]["memory_capacity"] = 512
    c["venus"]["max_partition_len"] = 64
    c["streams"] = 3


def _shrink_traffic(t: dict) -> None:
    t["video"]["resolution"] = 32
    if "rate_qps" in t:
        t["rate_qps"] = 8
        t["max_batch"] = 2
        t["check"]["queries"] = 12
        # as many planted rows as top-k serves: at 64 dimensions over a
        # few hundred rows only near-ties let the control reorder lanes
        t["history"]["planted_rows"] = [8, 8]


def tiny_checkout(tmp: Path) -> Path:
    """A shrunk copy of the benchmark; returns its checkout root."""
    bench = tmp / "benchmarks" / "onchip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for f in (bench / "configs").glob("*.json"):
        _edit(f, _shrink_config)
    for f in (bench / "traffic").glob("*.json"):
        _edit(f, _shrink_traffic)

    def cpu_peaks(p):
        p["devices"]["cpu"] = p["devices"]["TPU v5 lite"]
    _edit(bench / "peaks.json", cpu_peaks)
    return tmp


def run_cell(checkout: Path, cell: str, capsys, *, seconds: float = 2.0,
             trace: bool = False) -> dict:
    """Drive one run past the TPU check; returns the result line."""
    import time
    from vbench import harness
    from vbench.registry import Cell
    c = Cell(cell, base=checkout / "benchmarks" / "onchip")
    capsys.readouterr()
    rc = harness.run(c, seed=SEED, seconds=seconds, trace=trace,
                     t_start=time.perf_counter(), checkout=checkout)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
