"""Profiler trace of the measured window, reduced to metrics.

``capture`` wraps the window in ``jax.profiler`` tracing (no Python
tracer; host annotations kept). ``reduce`` reads the ``.xplane.pb``:

* device busy time: the union of the op intervals on each device's op
  line, averaged over the devices; idle is the rest of the window;
* device time by program, and by op as ``<program>/<op>`` (the HLO
  instruction's name; a Pallas kernel's custom call is named after the
  jitted function that launches it), summed over events;
* idle gaps named by the benchmark's host span (``bench.<name>``
  annotations) that was open while the device had nothing to run.

Everything is on the trace's own clock; the window is the
``bench.window`` annotation.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(directory: str):
    import jax
    from jax.profiler import ProfileOptions
    shutil.rmtree(directory, ignore_errors=True)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (N, 2) [start, end) intervals into disjoint sorted ones."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not len(iv):
        return iv
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


class Reduced:
    """The numbers a traced window yields (times in seconds)."""

    def __init__(self):
        self.window_s = 0.0
        self.busy_s = 0.0                      # mean over devices
        self.devices = 0
        self.op_seconds: Dict[str, float] = {}       # by op name
        self.module_seconds: Dict[str, float] = {}   # by program name
        self.op_counts: Dict[str, int] = {}          # events by op name
        self.idle_by_span: Dict[str, float] = {}

    def seconds_matching(self, needle: str, modules: bool = False) -> float:
        """Device seconds of programs (or of ops, matched on the op's own
        name) whose name holds ``needle``."""
        if modules:
            return float(sum(v for k, v in self.module_seconds.items()
                             if needle in k))
        return float(sum(v for k, v in self.op_seconds.items()
                         if needle in k.split("/", 1)[-1]))

    def count_matching(self, needle: str) -> int:
        return sum(v for k, v in self.op_counts.items()
                   if needle in k.split("/", 1)[-1])

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.name, ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    out = Reduced()
    win = [s for s in host_spans if s[0] == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = win[0][1], win[0][2]
    out.window_s = hi - lo
    spans = [s for s in host_spans if s[0] != WINDOW
             and s[2] > lo and s[1] < hi]
    busy = []
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                       ev.name.split("(")[0])
                      for ev in lines.get(MODULES_LINE, ()))
        for s, e, name in mods:
            if s < hi and e > lo:
                out.module_seconds[name] = out.module_seconds.get(
                    name, 0.0) + min(e, hi) - max(s, lo)
        starts = np.asarray([m[0] for m in mods])
        ivs = []
        for ev in lines.get(OPS_LINE, ()):
            s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
            if e <= lo or s >= hi:
                continue
            k = int(np.searchsorted(starts, s, side="right")) - 1
            mod = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
            key = mod + "/" + ev.name.split(" = ")[0].lstrip("%")
            out.op_seconds[key] = out.op_seconds.get(key, 0.0) + min(
                e, hi) - max(s, lo)
            out.op_counts[key] = out.op_counts.get(key, 0) + 1
            ivs.append((s, e))
        busy.append(_clip(_union(np.asarray(ivs, np.float64).reshape(-1, 2)),
                          lo, hi))
    out.devices = len(devices)
    if devices:
        out.busy_s = float(np.mean([np.sum(b[:, 1] - b[:, 0]) if len(b)
                                    else 0.0 for b in busy]))
        out.idle_by_span = _name_gaps(busy[0], spans, lo, hi)
    return out


def _name_gaps(busy: np.ndarray, spans, lo: float, hi: float
               ) -> Dict[str, float]:
    """Idle time of one device in [lo, hi), split by the innermost host
    span open over each part of it ("(none)" where no span was open)."""
    edges = [lo, hi]
    for _, s, e in spans:
        edges += [max(s, lo), min(e, hi)]
    for s, e in busy:
        edges += [s, e]
    edges = np.unique(np.clip(edges, lo, hi))
    mids = 0.5 * (edges[:-1] + edges[1:])
    widths = np.diff(edges)
    idle = np.ones(len(mids), bool)
    if len(busy):
        k = np.searchsorted(busy[:, 0], mids, side="right") - 1
        idle = ~((k >= 0) & (mids < busy[np.maximum(k, 0), 1]))
    # paint spans longest first, so each segment ends up owned by the
    # innermost span open over it
    names = ["(none)"]
    owner = np.zeros(len(mids), np.int64)
    for n, s, e in sorted(spans, key=lambda sp: sp[1] - sp[2]):
        a = np.searchsorted(edges, max(s, lo))
        b = np.searchsorted(edges, min(e, hi))
        if b > a:
            names.append(n[len("bench."):])
            owner[a:b] = len(names) - 1
    out: Dict[str, float] = {}
    for i in np.nonzero(idle)[0]:
        name = names[owner[i]]
        out[name] = out.get(name, 0.0) + float(widths[i])
    return out
