"""pba_setup_ms: device milliseconds per graph in the streamed PBA's
set-up programs: ``setup_body`` (phase 1 and exchange 1) and
``pool_body`` (the phase-2 urn pools), both built in core/stream.py."""
from bench import tracereduce

PROGRAMS = ("jit_setup_body(", "jit_pool_body(")


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.span
    graphs = tracereduce.host_count(run.trace, "graph", lo, hi)
    ns = sum(tracereduce.module_ns(run.trace, p, lo, hi) for p in PROGRAMS)
    if not graphs or not ns:
        return None
    return ns / graphs / 1e6
