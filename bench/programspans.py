"""The program's own host spans in a traced window.

The generator opens ``repro.*`` spans (``src/repro/runtime/spans.py``) on
the host thread that calls it, so they sit on the window's host line,
on the device planes' clock: ``repro.generate`` once per graph, and
inside it ``repro.build``, ``repro.dispatch``, ``repro.wait`` and the
host sink's ``repro.sink.*``; ``repro.plan`` once per plan. A window with
no ``repro.generate`` span was recorded from a program without them, and
the readers built on this module return None there.
"""
from __future__ import annotations

from bench import tracereduce

GENERATE = "repro.generate"


def graphs(run) -> int:
    """How many graphs the program generated in the window: its
    ``repro.generate`` spans; 0 without a trace."""
    if run.trace is None:
        return 0
    lo, hi = run.span
    return tracereduce.host_count(run.trace, GENERATE, lo, hi)


def union(run, names: tuple) -> list:
    """Sorted, disjoint (start, end) stretches of the window covered by
    host spans named in ``names``; a name ending in ``.`` stands for every
    span whose name starts with it."""
    lo, hi = run.span
    prefixes = tuple(n for n in names if n.endswith("."))
    return tracereduce.merged(
        (e for e in run.trace.host
         if e.name in names or (prefixes and e.name.startswith(prefixes))),
        lo, hi)


def overlap_ns(a: list, b: list) -> float:
    """Nanoseconds covered by both of two sorted, disjoint interval
    lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            total += t - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def union_ms_per_graph(run, names: tuple):
    """Milliseconds per graph of :func:`union`, or None where the window
    holds no program spans."""
    n = graphs(run)
    if not n:
        return None
    return sum(t - s for s, t in union(run, names)) / n / 1e6
