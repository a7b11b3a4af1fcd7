"""pba_round_chip_ms: device milliseconds per graph and per chip in the
streamed PBA's round program: ``pba_round_ms``, which sums over chips,
divided by the chips the trace holds, so that cells on one and on four
chips read the time a chip spends in rounds."""
from bench.metrics import pba_round_ms


def read(run):
    ms = pba_round_ms.read(run)
    if ms is None:
        return None
    return ms / len(run.trace.modules)
