#!/usr/bin/env python3
"""Run one cell traced, as ``run_cell.py --trace 1`` does, and split the
device's idle time by the program's own spans as well.

    python3 benchmarks/onchip/attribute_idle.py --workload <cell> \\
        --seed <n> --seconds <s> --out <file.json>

The run and its result line are ``run_cell.py``'s. Before the harness
removes the trace, ``vbench.program_spans`` reads it again; ``--out``
gets, as JSON: the idle seconds by innermost span of either kind
(``venus.<name>`` for the program's), each benchmark span's idle as
``vbench.trace`` names it beside the share its program children
(``venus.ingest.*`` under ``ingest_tick``, ``venus.execute.*`` under
``execute``) hold of it when the program's spans are painted too, and
each program span's milliseconds per ``venus.ingest_tick`` or
``venus.execute`` in the window. A program that writes no spans gets
empty splits.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run_cell  # noqa: E402  (sets the paths, times the process start)

# benchmark span -> the prefix of its program children
PARENTS = {"ingest_tick": "venus.ingest.", "execute": "venus.execute."}
PER = {"venus.ingest.": "venus.ingest_tick", "venus.execute.": "venus.execute"}


def attribution(bench_idle, prog) -> dict:
    """The split ``--out`` holds, from ``vbench.trace``'s idle by span
    and ``vbench.program_spans``' reading of the same trace."""
    idle = prog.idle_by_span
    parents = {}
    for name, kids in PARENTS.items():
        total = bench_idle.get(name, 0.0)
        split = {k: v for k, v in idle.items() if k.startswith(kids)}
        parents[name] = {
            "idle_s": total, "children_s": sum(split.values()),
            "share": sum(split.values()) / total if total else None,
            "children": dict(sorted(split.items(), key=lambda kv: -kv[1]))}
    names = sorted({n for n, _, _ in prog.spans})
    per_tick = {}
    for n in names:
        per = next((p for k, p in PER.items() if n.startswith(k)), None)
        if per is not None:
            per_tick[n] = prog.ms_per(n, per)
    return {"idle_by_span": dict(sorted(idle.items(),
                                        key=lambda kv: -kv[1])),
            "parents": parents,
            "counts": {n: prog.count(n) for n in names},
            "ms_per_tick": per_tick}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args, rest = ap.parse_known_args(argv)
    from vbench import program_spans
    from vbench import trace as vtrace
    reduce_bench = vtrace.reduce

    def reduce_both(path):
        red = reduce_bench(path)
        out = attribution(red.idle_by_span, program_spans.reduce(path))
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"attribute_idle: {json.dumps(out['parents'])}",
              file=sys.stderr, flush=True)
        return red

    vtrace.reduce = reduce_both
    return run_cell.main(rest + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
