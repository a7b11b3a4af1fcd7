"""programs_built: jitted programs the front door constructed per graph
inside the window: the program's ``repro.build`` spans over its
``repro.generate`` spans. Every shape is warmed up in set-up, so anything
above 0 is a program built again for each graph. None where the window
holds no program spans."""
from bench import programspans, tracereduce


def read(run):
    n = programspans.graphs(run)
    if not n:
        return None
    lo, hi = run.span
    return tracereduce.host_count(run.trace, "repro.build", lo, hi) / n
