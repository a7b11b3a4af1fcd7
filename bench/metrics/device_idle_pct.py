"""device_idle_pct: share of the traced window in which no operation ran
on the chip: 100 * (1 - union of the chip's op intervals / window),
averaged over the chips the cell uses."""
from bench import tracereduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.span
    busy = tracereduce.mean_busy_ns(run.trace, lo, hi)
    if busy is None or hi <= lo:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
