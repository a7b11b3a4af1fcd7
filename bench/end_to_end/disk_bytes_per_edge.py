"""disk_bytes_per_edge: bytes of a graph's shard files over the edges read
back from them, the mean over the window's graphs: what a user's disk
holds per edge. Cells that sink into memory have no shards: None."""


def read(run):
    sizes = [g.disk_bytes / g.emitted for g in run.graphs
             if g.out_dir is not None and g.emitted]
    return sum(sizes) / len(sizes) if sizes else None
