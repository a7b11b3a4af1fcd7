"""Reduction of a profiler trace to the numbers the benchmark reports.

A JAX profiler trace (``*.xplane.pb``, read with
``jax.profiler.ProfileData``) of a TPU run has, per chip, a plane named
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per operation
run on the chip and whose line ``XLA Modules`` holds one event per
compiled program run (named ``jit_<function>(<fingerprint>)``). The host
plane ``/host:CPU`` has one line per host thread, named after it; the
main thread's line (the one with the harness's ``window`` span) holds the
spans the harness records (``window``, ``graph``, ``plan``,
``generate``) and the spans JAX records itself (``PjitFunction(...)``,
``backend_compile_and_load``, ``np.asarray(jax.Array)``, ...). Host and
device events share one clock, in nanoseconds from the start of the
trace. Nothing else of the trace is read.

Everything below works on :class:`Trace`, a plain copy of those lines, so
that the tests can check the arithmetic on hand-made traces as well as on
a trace recorded on the chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """ops / modules: per device plane name, that chip's events, sorted by
    start. host: the events of the host thread that ran the window, sorted
    by start."""

    ops: dict
    modules: dict
    host: list


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the only one under a directory."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"expected one .xplane.pb under {path}, "
                             f"found {len(found)}")
        path = found[0]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line.events)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = _events(line.events)
                if any(e.name == WINDOW for e in events):
                    host = events
    for name in modules:
        ops.setdefault(name, [])
    return Trace(ops=ops, modules=modules, host=host)


def _events(events: Iterable) -> list:
    return sorted((Event(e.name, float(e.start_ns), float(e.end_ns))
                   for e in events), key=lambda e: (e.start_ns, -e.end_ns))


def window(trace: Trace, name: str = WINDOW) -> tuple[float, float]:
    """(start, end) of the host span ``name``, which must occur once."""
    spans = [e for e in trace.host if e.name == name]
    if len(spans) != 1:
        raise ValueError(f"trace has {len(spans)} host spans named "
                         f"{name!r}, expected 1")
    return spans[0].start_ns, spans[0].end_ns


def merged(events: Iterable[Event], lo: float, hi: float) -> list:
    """Union of the events' intervals clipped to [lo, hi], as sorted,
    disjoint (start, end) pairs."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def busy_ns(trace: Trace, plane: str, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran on ``plane``."""
    return sum(t - s for s, t in merged(trace.ops[plane], lo, hi))


def mean_busy_ns(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Busy nanoseconds of [lo, hi], averaged over the traced chips."""
    if not trace.ops:
        return None
    return sum(busy_ns(trace, p, lo, hi) for p in trace.ops) / len(trace.ops)


def idle_gaps(trace: Trace, plane: str, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] in which ``plane`` ran no
    operation."""
    gaps, cur = [], lo
    for s, t in merged(trace.ops[plane], lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = t
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def module_ns(trace: Trace, prefix: str, lo: float, hi: float) -> float:
    """Device nanoseconds in [lo, hi] of programs whose name starts with
    ``prefix``, summed over the traced chips."""
    return sum(min(e.end_ns, hi) - max(e.start_ns, lo)
               for events in trace.modules.values() for e in events
               if e.name.startswith(prefix)
               and min(e.end_ns, hi) > max(e.start_ns, lo))


def op_ns(trace: Trace, pattern: str, lo: float, hi: float) -> float:
    """Device nanoseconds in [lo, hi] of operations whose HLO text matches
    the regular expression ``pattern``, summed over the traced chips."""
    rx = re.compile(pattern)
    return sum(min(e.end_ns, hi) - max(e.start_ns, lo)
               for events in trace.ops.values() for e in events
               if rx.search(e.name)
               and min(e.end_ns, hi) > max(e.start_ns, lo))


def host_count(trace: Trace, name: str, lo: float, hi: float) -> int:
    """How many host spans named ``name`` lie wholly inside [lo, hi]."""
    return sum(1 for e in trace.host
               if e.name == name and e.start_ns >= lo and e.end_ns <= hi)


def host_doing(trace: Trace, t: float) -> str:
    """What the host's main thread was in at time ``t``: the innermost
    span covering it, prefixed by the outermost harness span inside the
    window (``plan`` or ``generate``) when that is a different one."""
    covering = [e for e in trace.host
                if e.start_ns <= t < e.end_ns and e.name != WINDOW]
    if not covering:
        return "outside any span"
    inner = min(covering, key=lambda e: e.duration_ns).name
    stage = [e.name for e in covering if e.name in ("plan", "generate")]
    if stage and stage[0] != inner:
        return f"{stage[0]} > {inner}"
    return inner


def op_label(name: str) -> str:
    """Short name of an operation's HLO text: ``%fusion.51 = s32[..] fusion(``
    becomes ``fusion.51 (fusion)``."""
    m = re.match(r"%?([^\s=]+)\s*=\s*\S+\s+([\w-]+)\(", name)
    if m:
        return f"{m.group(1)} ({m.group(2)})"
    m = re.match(r"%?([^\s=]+)\s*=\s*\(.*?\)\s+([\w-]+)\(", name)
    if m:
        return f"{m.group(1)} ({m.group(2)})"
    return name[:80]


def module_label(name: str) -> str:
    """``jit_round_body(1768...)`` becomes ``jit_round_body``."""
    return name.split("(", 1)[0]


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The ``breakdown`` of the result line: the ``top`` device operations
    by total time, each named ``<program>/<op>``, and the ``top`` longest
    idle gaps of the first chip, each named by what the host was doing
    at its middle. Seconds, unrounded."""
    totals: dict = {}
    for plane, events in trace.ops.items():
        mods = trace.modules.get(plane, [])
        for e in events:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t <= s:
                continue
            mod = next((module_label(m.name) for m in mods
                        if m.start_ns <= e.start_ns < m.end_ns), "?")
            key = f"{mod}/{op_label(e.name)}"
            totals[key] = totals.get(key, 0.0) + (t - s)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.ops:
        plane = sorted(trace.ops)[0]
        longest = sorted(idle_gaps(trace, plane, lo, hi),
                         key=lambda g: g[0] - g[1])[:top]
        gaps = [[host_doing(trace, (s + t) / 2), (t - s) / 1e9]
                for s, t in longest]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": gaps}
