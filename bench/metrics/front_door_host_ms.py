"""front_door_host_ms: host milliseconds per graph in the front door: the
union of the program's ``repro.plan``, ``repro.build`` (a jitted program
constructed) and ``repro.dispatch`` (a device program enqueued, with
JAX's trace, lowering and compile-cache load when it misses) spans. None
where the window holds no program spans."""
from bench import programspans

SPANS = ("repro.plan", "repro.build", "repro.dispatch")


def read(run):
    return programspans.union_ms_per_graph(run, SPANS)
