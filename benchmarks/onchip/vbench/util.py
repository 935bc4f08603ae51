"""Clocks, host spans, seeds and the table of peaks."""

from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from vbench.registry import BENCH_DIR


def sub_seed(seed: int, *labels) -> int:
    """A 31-bit seed derived from the run's seed and a label path, so
    every generator draws from its own stream; any whole number works
    as ``seed``, however large."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    for lab in labels:
        words.append(int.from_bytes(str(lab).encode()[:8].ljust(8, b"\0"),
                                    "little") & 0xFFFFFFFF)
    return int(np.random.SeedSequence(words).generate_state(1)[0]
               & 0x7FFFFFFF)


def rng(seed: int, *labels) -> np.random.Generator:
    return np.random.default_rng(sub_seed(seed, *labels))


class CompileClock:
    """Backend compiles seen through JAX's own monitoring events (a
    persistent-cache hit counts only its read)."""

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


class Spans:
    """Host spans of the benchmark's own calls into the program: kept in
    memory as (name, start, end) on ``time.perf_counter``, and written
    into the profiler's trace as ``TraceAnnotation``s so that idle gaps
    on the device can be named by what the host was doing."""

    def __init__(self):
        self.rows: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.rows.append((name, t0, time.perf_counter()))


def peaks(device_kind: str, base=BENCH_DIR) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    with open(base / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
