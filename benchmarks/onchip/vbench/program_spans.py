"""The program's own host spans in a profiler trace.

``repro.obs.span`` writes ``venus.<name>`` annotations (their arguments
follow a ``#`` in the event's name) into the trace beside the
benchmark's ``bench.*`` spans and the device ops, on the same clock.
``reduce`` reads an ``.xplane.pb``:

* each program span summed and counted by name, over its events that
  start inside ``bench.window``;
* the first device's idle time in the window, named by the innermost
  span of either kind open over it: a program span by its whole
  ``venus.<name>``, a benchmark span by ``<name>`` as ``vbench.trace``
  names it. On a trace with no program spans the split is
  ``vbench.trace.reduce``'s ``idle_by_span``.

``vbench.trace.reduce`` reads only the ``bench.*`` spans, and the
harness removes the trace once it has reduced it, so this is for tools
that keep the trace: ``attribute_idle.py`` and the tests.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from vbench import trace as vtrace

PREFIX = "venus."

Span = Tuple[str, float, float]          # name, start, end (seconds)


class ProgramSpans:
    """The program spans of one traced window (times in seconds)."""

    def __init__(self, spans: List[Span], window: Tuple[float, float],
                 idle_by_span: Dict[str, float]):
        self.spans = spans
        self.window = window
        self.idle_by_span = idle_by_span

    def _durations(self, name: str) -> List[float]:
        lo, hi = self.window
        return [e - s for n, s, e in self.spans
                if n == name and lo <= s < hi]

    def seconds(self, name: str) -> float:
        return float(sum(self._durations(name)))

    def count(self, name: str) -> int:
        return len(self._durations(name))

    def ms_per(self, name: str, per: str) -> Optional[float]:
        """Milliseconds of span ``name`` per event of span ``per``; None
        where either is missing from the window."""
        times, n = self._durations(name), self.count(per)
        if not times or not n:
            return None
        return sum(times) / n * 1e3


def reduce(path: str) -> ProgramSpans:
    from jax.profiler import ProfileData
    bench: List[Span] = []
    program: List[Span] = []
    device = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    sp = (ev.name.split("#", 1)[0], ev.start_ns * 1e-9,
                          ev.end_ns * 1e-9)
                    if sp[0].startswith("bench."):
                        bench.append(sp)
                    elif sp[0].startswith(PREFIX):
                        program.append(sp)
        elif plane.name.startswith("/device:TPU:") and device is None:
            device = plane
    win = [s for s in bench if s[0] == vtrace.WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = win[0][1], win[0][2]
    idle: Dict[str, float] = {}
    if device is not None:
        ivs = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
               for line in device.lines if line.name == vtrace.OPS_LINE
               for ev in line.events]
        busy = vtrace._clip(vtrace._union(
            np.asarray(ivs, np.float64).reshape(-1, 2)), lo, hi)
        # _name_gaps drops a "bench." prefix from every name it returns,
        # so the program spans go in under one to come out whole
        open_ = [s for s in bench + [("bench." + n, s, e)
                                     for n, s, e in program]
                 if s[0] != vtrace.WINDOW and s[2] > lo and s[1] < hi]
        idle = vtrace._name_gaps(busy, open_, lo, hi)
    return ProgramSpans(program, (lo, hi), idle)

