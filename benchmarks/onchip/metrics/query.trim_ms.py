"""Host time of the archive trim per ingest tick of a query cell, in ms:
``ingest.trim_ms``'s reading, here where queries that arrive during a
tick wait for it."""

from vbench.registry import load_module


def read(run):
    return load_module("metrics", "ingest.trim_ms", run.cell.base).read(run)
