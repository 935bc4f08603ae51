"""Host spans of the program, written into the profiler's own trace.

``span(name, **args)`` marks one stretch of host work as a
``jax.profiler.TraceAnnotation`` named ``venus.<name>`` (its ``args``
become the event's stats), so that it shares the trace's clock with the
device ops, and measures its own duration with ``time.perf_counter``.
The program's stage times (``SessionManager.ingest_tick``'s dict,
``QueryResult.timings``) are read off these spans. With no profiler
running a span costs the annotation's inert check and two clock reads,
about 2 us on a CPU core.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "venus."


class span:
    """``with span("ingest.trim") as s: ...`` then ``s.seconds``;
    ``s.start`` and ``s.end`` are the ``perf_counter`` readings."""

    __slots__ = ("_ann", "start", "end")

    def __init__(self, name: str, **args):
        self._ann = TraceAnnotation(PREFIX + name, **args)
        self.start = self.end = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return self.end - self.start
