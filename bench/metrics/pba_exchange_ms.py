"""pba_exchange_ms: device milliseconds per graph and per chip in
collective operations: the all_to_all transposes of the streamed PBA's
set-up and round programs, and any all-reduce, all-gather or permute the
compiler emits.

An operation is found by its HLO opcode in the trace's ``XLA Ops`` name,
which on four v5e chips reads ``%all_to_all.11 = s32[4,1,241664]{...}
all-to-all(s32[4,1,241664]{...} %reshape.142), channel_id=1, ...``; the
asynchronous ``-start`` / ``-done`` forms count too. The intervals are merged per chip before they are summed, so an
operation that overlaps another collective is not counted twice. A
traced window with graphs but no collective reads 0.
"""
import re

from bench import tracereduce

COLLECTIVE = (r"\s(?:all-to-all|ragged-all-to-all|all-reduce|all-gather"
              r"|reduce-scatter|collective-permute|collective-broadcast)"
              r"(?:-start|-done)?\(")


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.span
    graphs = tracereduce.host_count(run.trace, "graph", lo, hi)
    if not graphs:
        return None
    rx = re.compile(COLLECTIVE)
    ns = sum(t - s for events in run.trace.ops.values()
             for s, t in tracereduce.merged(
                 (e for e in events if rx.search(e.name)), lo, hi))
    return ns / len(run.trace.ops) / graphs / 1e6
