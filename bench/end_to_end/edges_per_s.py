"""edges_per_s: emitted edges of every graph the window completed, over
the window's elapsed time (host clock)."""


def read(run):
    return sum(g.emitted for g in run.graphs) / run.window_s
