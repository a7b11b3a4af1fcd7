"""host_sink_ms: host milliseconds per graph in the host sink: the union
of the program's ``repro.sink.*`` spans (device-to-host copy of each
round, its compaction, the assembly of the edge list, shard writes).
Waits on the device (``repro.wait``) are not part of it. None where the
window holds no program spans."""
from bench import programspans

SPANS = ("repro.sink.",)


def read(run):
    return programspans.union_ms_per_graph(run, SPANS)
