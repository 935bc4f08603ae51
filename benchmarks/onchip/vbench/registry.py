"""Find every piece of a cell by its name.

A cell ``<cell>`` is ``workloads/<cell>.json``; it names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``),
and the mix names its generator (``traffic/<generator>.py``) and the
driver loop (``drivers/<driver>.py``). A per-layer metric ``<metric>``
is read by ``metrics/<metric>.py``. ``BENCHMARK.json`` at the root of
the checkout says which metrics each cell reports. New cells, mixes,
drivers and metrics are new files: nothing here changes for them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/onchip
CHECKOUT = BENCH_DIR.parents[1]                         # repository root

_MODULES: Dict[str, ModuleType] = {}


def load_json(kind: str, name: str, base: Optional[Path] = None) -> dict:
    path = (base or BENCH_DIR) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: Optional[Path] = None
                ) -> ModuleType:
    """Import ``<kind>/<name>.py`` by path (names may hold dots)."""
    path = (base or BENCH_DIR) / kind / f"{name}.py"
    key = str(path)
    if key not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(f"no {kind[:-1]} named {name!r} "
                                    f"({path})")
        spec = importlib.util.spec_from_file_location(
            f"vbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


class Cell:
    """One workload entry resolved to its configuration and traffic."""

    def __init__(self, name: str, base: Optional[Path] = None):
        self.base = base or BENCH_DIR
        self.name = name
        self.spec = load_json("workloads", name, self.base)
        self.config = load_json("configs", self.spec["config"], self.base)
        self.traffic = load_json("traffic", self.spec["traffic"], self.base)
        self.chips = int(self.spec.get("chips", 1))

    def driver(self) -> ModuleType:
        return load_module("drivers", self.traffic["driver"], self.base)

    def generator(self) -> ModuleType:
        return load_module("traffic", self.traffic["generator"], self.base)


def benchmark_json(checkout: Optional[Path] = None) -> dict:
    with open((checkout or CHECKOUT) / "BENCHMARK.json") as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries this cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
