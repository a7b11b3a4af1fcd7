"""sink_exposed_ms: milliseconds per graph in which the first chip ran no
operation while the host was in the host sink (a ``repro.sink.*`` span):
the part of the sink that no device work hides. None where the window
holds no program spans or no device plane."""
from bench import programspans, tracereduce

SPANS = ("repro.sink.",)


def read(run):
    n = programspans.graphs(run)
    if not n or not run.trace.ops:
        return None
    lo, hi = run.span
    gaps = tracereduce.idle_gaps(run.trace, sorted(run.trace.ops)[0], lo, hi)
    exposed = programspans.overlap_ns(gaps,
                                      programspans.union(run, SPANS))
    return exposed / n / 1e6
