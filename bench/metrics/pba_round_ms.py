"""pba_round_ms: device milliseconds per graph in the streamed PBA's round
program, ``round_body`` (core/stream.py over
core/pba.pba_stream_round_block), summed over the graph's rounds."""
from bench import tracereduce

PROGRAM = "jit_round_body("


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.span
    graphs = tracereduce.host_count(run.trace, "graph", lo, hi)
    ns = tracereduce.module_ns(run.trace, PROGRAM, lo, hi)
    if not graphs or not ns:
        return None
    return ns / graphs / 1e6
