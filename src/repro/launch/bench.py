"""Compile-only harness for the sharded PBA exchange programs.

Shared by the collective-bytes CI gate (scripts/collective_gate.py) and
the lp x topology sweep (benchmarks/hierarchical_exchange.py): both need
the *compiled* exchange for a resolved :class:`repro.api.GenPlan` — to
read cost analysis and HLO collective stats — without running it. One
definition keeps the gate and the benchmark measuring the same program.
:func:`compile_sharded_stream_round` does the same for one round of the
device-sharded stream (the out-of-core exchange-2 program), so the gate
can pin the streamed path's collective volume too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.pba import pba_logical_block
from repro.runtime import blocking, spmd


def compile_sharded_pba(pl):
    """(jitted_fn, example_args) for a sharded-execution PBA plan.

    ``fn.lower(*args).compile()`` yields the compiled program; calling
    ``fn(*args)`` runs it.
    """
    cfg, table, topo = pl.config, pl.table, pl.topology
    num_procs, lp, d = pl.num_procs, pl.lp, topo.num_devices
    mesh = topo.build_mesh()
    spec = topo.spec_axes

    def body(procs_blk, s_blk):
        ranks = blocking.logical_ranks(lp, topo)
        u, v, dropped, _, rounds = pba_logical_block(
            ranks, procs_blk[0], s_blk[0], cfg, num_procs,
            pl.pair_capacity, topo)
        return u[None], v[None], dropped[None], rounds[None]

    fn = jax.jit(spmd.shard_map(
        body, mesh=mesh,
        in_specs=(P(spec, None, None), P(spec, None)),
        out_specs=(P(spec, None, None), P(spec, None, None), P(spec),
                   P(spec)),
        check_vma=False))
    procs = jnp.asarray(table.procs).reshape(d, lp, table.max_s)
    s = jnp.asarray(table.s).reshape(d, lp)
    return fn, (procs, s)


def compile_sharded_stream_setup(pl):
    """(jitted_fn, example_args) for a streamed-execution plan's sharded
    setup program (phase 1 + exchange 1) — the program
    ``PBAShardedStream.__init__`` runs once per stream. The example args
    carry the plan's real faction table: the setup program is the one
    front-door program whose RNG draws and runtime inputs coexist, which
    is exactly what the flowcheck RNG-lineage pass wants to see.
    """
    from repro.core.stream import _sharded_setup_fn

    cfg, table, topo = pl.config, pl.table, pl.topology
    lp, d = pl.lp, topo.num_devices
    setup = _sharded_setup_fn(cfg, pl.num_procs, topo)
    procs = jnp.asarray(table.procs).reshape(d, lp, table.max_s)
    s = jnp.asarray(table.s).reshape(d, lp)
    return setup, (procs, s)


def compile_sharded_stream_round(pl):
    """(jitted_fn, example_args) for one round of a streamed-execution
    plan's device-sharded exchange-2 program (grant + blocked transpose +
    band compaction) — the program ``PBAShardedStream`` dispatches per
    block. The example state is zero-filled at the plan's static shapes;
    collective volume depends only on the shapes, not the values.
    """
    from repro.core.pba import stream_block_capacity
    from repro.core.stream import _sharded_grant_fns

    cfg, topo = pl.config, pl.topology
    p, lp, d = pl.num_procs, pl.lp, topo.num_devices
    e = cfg.edges_per_proc
    block_cap = stream_block_capacity(e, p, pl.round_capacity)
    _, round_fn = _sharded_grant_fns(cfg, p, topo, pl.urn_budget,
                                     pl.round_capacity, block_cap)
    z = jnp.zeros
    args = (jnp.int32(0), z((d, lp, e), jnp.int32),
            z((d, lp, e), jnp.int32), z((d, lp, p), jnp.int32),
            z((d, lp, e + pl.urn_budget), jnp.int32))
    return round_fn, args


def compile_sharded_cfree(pl):
    """(jitted_fn, example_args) for a communication-free plan's sharded
    expansion — the zero-collective front-door program the auditor pins
    to exactly 0 all_to_alls (core.cfree.sharded_expand_fn). The args are
    the mesh token and the plan's stream words, drawn by the words
    program (:func:`compile_cfree_words`)."""
    from repro.core import cfree

    fn, tok = cfree.sharded_expand_fn(pl.config, pl.num_procs, pl.topology)
    words, _ = compile_cfree_words(pl)
    return fn, (tok, words())


def compile_cfree_words(pl):
    """(jitted_fn, example_args) for a communication-free plan's stream
    words program — the one program of the path that draws randomness,
    with the seed as its literal (core.cfree.words_fn)."""
    from repro.core import cfree

    return cfree.words_fn(pl.config.model, pl.config.seed), ()
