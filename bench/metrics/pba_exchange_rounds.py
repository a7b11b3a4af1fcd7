"""pba_exchange_rounds: exchange rounds per graph of the streamed PBA, the
mean over the window's graphs of ``GenStats.exchange_rounds``: the rounds
the busiest (requester, provider) pair's demand needs at the round
capacity C_r, which reaches past the configured R when the derived pair
capacity is short of that demand. None without graphs."""


def read(run):
    if not run.graphs:
        return None
    return sum(g.rounds for g in run.graphs) / len(run.graphs)
