"""pba_round_roofline: the round program's share of its memory roofline.

Bytes one round has to move, from its shapes (:func:`round_bytes`), over
the round program's device time, over the chip's HBM bandwidth. The round
does integer work only and no integer peak of the chip is published, so
the bound is the bytes alone. The count describes the round's work, not
one implementation of it, so a later rewrite of the round is read against
the same bytes.
"""
from bench import tracereduce

PROGRAM = "jit_round_body("


def round_bytes(lp: int, procs: int, edges: int, round_cap: int,
                block_cap: int) -> int:
    """int32 bytes a round moves on a chip hosting ``lp`` of ``procs``
    logical processors with ``edges`` local edges each: read the tags and
    request ranks (2 x (lp, E)); read the granted urn slots, then write and
    read the (lp, P, C_r) exchange buffer (3 x lp*P*C_r); write the
    compacted u and v (2 x (lp, block_cap))."""
    return 4 * lp * (2 * edges + 3 * procs * round_cap + 2 * block_cap)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.span
    ns = tracereduce.module_ns(run.trace, PROGRAM, lo, hi)
    rounds = sum(g.rounds for g in run.graphs)
    if not ns or not rounds:
        return None
    pl = run.plan
    edges = pl.config.edges_per_proc
    block_cap = min(edges, pl.num_procs * pl.round_capacity)
    moved = rounds * pl.topology.num_devices * round_bytes(
        pl.lp, pl.num_procs, edges, pl.round_capacity, block_cap)
    return 100.0 * moved / (ns / 1e9) / run.peaks["hbm_bytes_per_s"]
