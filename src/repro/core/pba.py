"""Parallel Barabási–Albert (PBA) generator — two-phase preferential attachment.

Faithful JAX/TPU re-derivation of the paper's MPI algorithm (DESIGN.md §2):

  phase 1 (local):  per-processor Pólya urn over *processor ids*, seeded with
                    the processor's faction members; resolved in O(log E)
                    vectorized pointer-doubling rounds instead of a serial loop.
  exchange 1:       dense (P,) counts all_to_all ("how many endpoints I need
                    from you").
  phase 2 (local):  per-processor Pólya urn over *local endpoint slots*
                    (uniform over slots == degree-proportional over vertices),
                    producing the requested endpoints in requester order.
  exchange 2:       fixed-capacity (P, C) endpoint all_to_all; overflow slots
                    are dropped and counted (static shapes — see DESIGN.md).
  substitution:     each local edge's processor tag is replaced by the next
                    endpoint received from that processor (occurrence-rank
                    gather).

Everything is deterministic given (seed, P): all randomness is counter-based
and keyed by (seed, stream, rank).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import rng as rng_lib
from repro.core.factions import FactionTable, validate_table
from repro.core.graph import EdgeList, GenStats
from repro.runtime import blocking, spmd, streaming
from repro.runtime import topology as topology_lib
from repro.runtime.topology import Topology


@dataclasses.dataclass(frozen=True)
class PBAConfig:
    """PBA generation parameters.

    vertices_per_proc: local vertex count V (global = V * P).
    edges_per_vertex: the BA ``k`` — edges attached per new vertex.
    interfaction_prob: probability that a phase-1 slot picks a uniformly
      random processor instead of copying an earlier slot (the paper's
      inter-faction edges).
    pair_capacity: static per-(sender, receiver) endpoint budget C. None ->
      heuristic from faction sizes.
    exchange_rounds: None -> legacy single fixed-capacity exchange 2 (pairs
      needing more than C endpoints overflow into counted drops). R >= 1 ->
      multi-round streaming exchange: per-round buffer C_r = ceil(C / R),
      rounds repeat (beyond R if demand requires, bounded by ceil(E / C_r))
      until every pair's residual is zero — dropped_edges from pair
      overflow is exactly 0 for any faction layout, and peak exchange
      memory shrinks from P*C to P*C_r.
    total_capacity_factor: phase-2 urn budget as a multiple of E_local.
    seed: global RNG seed.
    """

    vertices_per_proc: int
    edges_per_vertex: int
    interfaction_prob: float = 0.05
    pair_capacity: Optional[int] = None
    exchange_rounds: Optional[int] = None
    # §Perf G1: phase-2 urn budget. Expected requests == E_local; 2x headroom
    # keeps drops at zero for non-adversarial faction layouts while cutting
    # the dominant resolve cost ~40% (was 4x — see EXPERIMENTS.md §Perf-Gen).
    total_capacity_factor: int = 2
    seed: int = 0

    @property
    def edges_per_proc(self) -> int:
        return self.vertices_per_proc * self.edges_per_vertex


# Fraction of device memory the live exchange buffer may claim (1/16), and
# the per-round floor that keeps round count from being dominated by
# per-collective latency instead of bytes.
_EXCHANGE_MEM_DIVISOR = 16
_MIN_ROUND_CAPACITY = 16


def default_pair_capacity(edges_per_proc: int, min_s: int,
                          num_procs: int = 0,
                          exchange_rounds: Optional[int] = None,
                          memory_bytes: Optional[int] = None) -> int:
    """Static per-pair capacity heuristic, collective-latency/memory-aware.

    Base load term: the phase-1 urn is a Pólya urn over ~s initial colors;
    per-pair load concentrates like E/s with heavy upper tails, so budget a
    generous multiple, clipped to E_local (a pair can never need more).

    At pod scale (``num_procs`` given) the live exchange buffer becomes the
    binding constraint: the total capacity is clamped so each *logical
    processor's* (P, C_r) int32 round buffer fits 1/16 of device memory
    (probed via ``runtime.spmd.device_memory_bytes``: an accelerator's
    reported limit, a fixed budget on host devices). The budget is deliberately per logical
    processor, not per device: the derived capacity must be a pure function
    of (cfg, table) or the host (lp = P) and sharded (lp = P/D) runs of the
    same graph would disagree — a device hosting lp logical processors
    therefore materializes lp of these buffers, so at extreme lp set
    ``pair_capacity`` (or ``exchange_rounds``) explicitly. Streamed runs
    (``exchange_rounds`` set) recover any clamped capacity by running extra
    rounds — ``run_exchange`` repeats past R until the residual is zero —
    but keep C_r >= 16 so each round moves enough bytes to amortize the
    collective's latency rather than degenerating into thousands of tiny
    all_to_alls.

    Note the probed memory makes the *default* backend-dependent: a CPU
    host (fixed budget) and an accelerator (reported bytes_limit) can
    derive different capacities at large P, and the capacity is part of the
    graph's identity. Cross-backend validation runs should pin the budget
    explicitly — every generator logs the chosen value in
    ``GenStats.pair_capacity``, so a replay passes
    ``dataclasses.replace(cfg, pair_capacity=stats.pair_capacity)``.
    """
    c = 8 * edges_per_proc // max(min_s, 1)
    c = int(min(max(c, 64), edges_per_proc))
    if num_procs:
        mem = (memory_bytes if memory_bytes is not None
               else spmd.device_memory_bytes())
        budget = max(mem // _EXCHANGE_MEM_DIVISOR, 1)
        rounds = max(exchange_rounds or 1, 1)
        cap = (budget // (4 * num_procs)) * rounds
        if exchange_rounds is not None:
            cap = max(cap, _MIN_ROUND_CAPACITY * rounds)
        c = int(max(min(c, cap), 1))
    return c


def resolve_pointers(ptr: jax.Array, terminal: jax.Array,
                     max_rounds: int = 64) -> jax.Array:
    """Path-compress ``ptr`` until every entry lands on a terminal slot.

    ``ptr`` points strictly downward (ptr[j] < j for non-terminals) and
    terminal slots are fixed points, so ``ptr <- ptr[ptr]`` doubles chain
    progress per round; expected rounds = O(log log-chain) ~ 5-8.
    """

    def cond(state):
        i, p = state
        return (i < max_rounds) & ~jnp.all(terminal[p])

    def body(state):
        from repro.kernels import ops as kops
        i, p = state
        return i + 1, kops.resolve_step(p)

    _, out = jax.lax.while_loop(cond, body, (jnp.int32(0), ptr))
    return out


def occurrence_rank(a: jax.Array) -> jax.Array:
    """occ[j] = #{j' < j : a[j'] == a[j]} — rank within equal-value group."""
    n = a.shape[0]
    idx = jnp.argsort(a, stable=True)
    sa = a[idx]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sa[1:] != sa[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
    rank_sorted = pos - group_start
    occ = jnp.zeros((n,), jnp.int32).at[idx].set(rank_sorted)
    return occ


def _phase1(rank, faction_row, s, cfg: PBAConfig, num_procs: int):
    """Build the local processor-tag list A (E,) and per-target counts (P,)."""
    e_local = cfg.edges_per_proc
    max_s = faction_row.shape[0]
    j = jnp.arange(e_local, dtype=jnp.int32)

    urn_key = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PBA_URN, rank)
    r = rng_lib.uniform_slots(urn_key, e_local, jnp.maximum(j, 1))  # r_j ~ U[0, j)

    coin_key = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PBA_INTERFACTION_COIN, rank)
    inter = rng_lib.coin(coin_key, e_local, cfg.interfaction_prob) & (j >= s)
    proc_key = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PBA_INTERFACTION_PROC, rank)
    rand_proc = rng_lib.uniform_ints(proc_key, e_local, num_procs)

    seeded = j < s
    terminal = seeded | inter
    base = jnp.where(
        seeded,
        faction_row[jnp.minimum(j, max_s - 1)],
        jnp.where(inter, rand_proc, -1),
    )
    ptr = jnp.where(terminal, j, r)
    ptr = resolve_pointers(ptr, terminal)
    a = base[ptr]

    from repro.kernels import ops as kops
    counts = kops.histogram(a, num_procs)
    return a, counts


def _phase2_pool(rank, cfg: PBAConfig, t_cap: Optional[int] = None) -> jax.Array:
    """Resolve the phase-2 urn once: slot -> *global* vertex id pool.

    The pool depends only on (seed, rank, t_cap) — not on the demand — so
    the single-shot and streaming grant paths draw identical endpoints for
    the same slot index *at the same budget*. Note the budget is part of
    the draw: ``jax.random.bits`` blocks over the whole array, so pools
    drawn at different ``t_cap`` disagree even on shared slots (the stream
    driver's auto-capacity mode therefore defines its own deterministic
    graph rather than extending this one).
    """
    e_local = cfg.edges_per_proc
    k = cfg.edges_per_vertex
    if t_cap is None:
        t_cap = cfg.total_capacity_factor * e_local
    pool_n = e_local + t_cap

    # Urn over endpoint slots: first E slots are the k out-edges of each local
    # vertex (uniform slot == degree-proportional vertex); later slots copy a
    # uniformly chosen earlier slot (urn growth as endpoints are granted).
    jj = jnp.arange(pool_n, dtype=jnp.int32)
    key = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PBA_PHASE2_URN, rank)
    r = rng_lib.uniform_slots(key, pool_n, jnp.maximum(jj, 1))
    terminal = jj < e_local
    ptr = jnp.where(terminal, jj, r)
    ptr = resolve_pointers(ptr, terminal)
    local_vertex = (ptr // k).astype(jnp.int32)  # slot -> owning local vertex
    return rank * jnp.int32(cfg.vertices_per_proc) + local_vertex  # global ids


def _phase2(rank, recv_counts, cfg: PBAConfig, pair_capacity: int):
    """Generate requested endpoints by local preferential attachment.

    Legacy single-shot grant: per-pair demand is clipped to ``pair_capacity``
    up front. Returns out_buf (P, C) of *global* vertex ids; -1 marks unused
    slots.
    """
    e_local = cfg.edges_per_proc
    t_cap = cfg.total_capacity_factor * e_local
    pool = _phase2_pool(rank, cfg)

    cc = jnp.minimum(recv_counts, pair_capacity)
    offsets = jnp.cumsum(cc) - cc  # exclusive prefix
    c_idx = jnp.arange(pair_capacity, dtype=jnp.int32)
    flat_idx = offsets[:, None] + c_idx[None, :]
    valid = (c_idx[None, :] < cc[:, None]) & (flat_idx < t_cap)
    vals = pool[e_local + jnp.clip(flat_idx, 0, t_cap - 1)]
    out_buf = jnp.where(valid, vals, -1)
    granted = valid.sum(dtype=jnp.int32)
    return out_buf, granted


def _grant_round(pool, recv_counts, r, round_cap: int, e_local: int,
                 t_cap: int):
    """Round ``r`` of the streamed grant: ranks [r*C_r, (r+1)*C_r) per pair.

    Offsets come from the *unclipped* demand, so a pair's endpoints occupy
    one contiguous pool run across rounds and every request rank maps to a
    unique slot. Slots past the urn budget ``t_cap`` emit -1 (counted as
    drops by the requester).
    """
    from repro.kernels import ops as kops
    offsets = jnp.cumsum(recv_counts) - recv_counts  # exclusive prefix
    window = streaming.round_window(recv_counts, r, round_cap)
    c_idx = jnp.arange(round_cap, dtype=jnp.int32)
    flat_idx = offsets[:, None] + r * round_cap + c_idx[None, :]
    valid = (c_idx[None, :] < window[:, None]) & (flat_idx < t_cap)
    vals = kops.gather(pool, e_local + jnp.clip(flat_idx, 0, t_cap - 1))
    return jnp.where(valid, vals, -1)


def pba_logical_block(ranks, procs_blk, s_blk, cfg: PBAConfig,
                      num_procs: int, pair_capacity: int, topo: Topology):
    """Run this device's block of lp logical PBA processors.

    ranks: (lp,) global logical ids; procs_blk: (lp, max_s) faction rows;
    s_blk: (lp,) faction sizes. The two exchanges route through the shared
    blocking/streaming primitives — (lp, P) counts and (lp, P, C) or
    per-round (lp, P, C_r) endpoint buffers under the runtime's
    blocked-transpose contract for ``topo`` (flat 1-D all_to_all, 2-D pods
    hierarchical two-hop, or host swapaxes). Returns (u (lp, E), v (lp, E),
    dropped scalar over all procs, granted (lp,), rounds scalar).
    Host path: ``Topology.host()`` with lp == P.
    """
    a, counts = blocking.map_logical(
        lambda r, fr, ss: _phase1(r, fr, ss, cfg, num_procs),
        ranks, procs_blk, s_blk)                          # (lp, E), (lp, P)
    recv_counts = blocking.transpose_counts(counts, topo)
    lp = a.shape[0]
    occ = jax.vmap(occurrence_rank)(a)

    if cfg.exchange_rounds is None:
        # Legacy single fixed-capacity exchange: per-pair overflow (occ >= C)
        # is dropped and counted.
        out_buf, granted = blocking.map_logical(
            lambda r, rc: _phase2(r, rc, cfg, pair_capacity),
            ranks, recv_counts)                           # (lp, P, C), (lp,)
        in_buf = blocking.transpose_payload(out_buf, topo)
        from repro.kernels import ops as kops
        v = kops.gather(
            in_buf.reshape(lp, num_procs * pair_capacity),
            a * pair_capacity + jnp.minimum(occ, pair_capacity - 1))
        v = jnp.where(occ < pair_capacity, v, -1)
        rounds = jnp.int32(1)
    else:
        v, granted, rounds = _streamed_exchange2(
            a, occ, counts, recv_counts, ranks, cfg, pair_capacity,
            num_procs, topo)

    j = jnp.arange(cfg.edges_per_proc, dtype=jnp.int32)
    u = (ranks[:, None] * jnp.int32(cfg.vertices_per_proc)
         + (j // cfg.edges_per_vertex)[None, :])
    u = jnp.where(v >= 0, u, -1)
    dropped = blocking.all_reduce_sum(jnp.sum(v < 0, dtype=jnp.int32), topo)
    return u, v, dropped, granted, rounds


def _streamed_exchange2(a, occ, counts, recv_counts, ranks, cfg: PBAConfig,
                        pair_capacity: int, num_procs: int, topo: Topology):
    """Exchange 2 as a multi-round stream (see runtime/streaming.py).

    Round r serves request ranks [r*C_r, (r+1)*C_r) of every (sender,
    receiver) pair; the requester scatters the received band into its edge
    list by occurrence rank. Rounds repeat until the globally all-reduced
    residual is zero (statically bounded by ceil(E / C_r), the worst legal
    pair count), so no edge is ever dropped for pair-capacity reasons —
    only urn-budget exhaustion (t_cap) can still emit -1.
    """
    lp = a.shape[0]
    e_local = cfg.edges_per_proc
    t_cap = cfg.total_capacity_factor * e_local
    c_r = streaming.round_capacity(pair_capacity, cfg.exchange_rounds)
    max_rounds = streaming.rounds_needed(e_local, c_r)
    pool = blocking.map_logical(lambda r: _phase2_pool(r, cfg), ranks)

    # Drive termination by what the urn can actually grant, not raw demand:
    # once a provider's budget is exhausted every further slot is -1, and
    # requesters past the budget already hold -1 (the init value) — rounds
    # transposing pure padding would be wasted collectives.
    offsets = jnp.cumsum(recv_counts, axis=1) - recv_counts
    grantable = jnp.clip(jnp.minimum(recv_counts, t_cap - offsets), 0, None)

    def emit(r):
        return jax.vmap(
            lambda p, rc: _grant_round(p, rc, r, c_r, e_local, t_cap)
        )(pool, recv_counts)                              # (lp, P, C_r)

    def consume(r, recv, v):
        from repro.kernels import ops as kops
        band = (occ >= r * c_r) & (occ < (r + 1) * c_r)
        idx = a * c_r + jnp.clip(occ - r * c_r, 0, c_r - 1)
        vals = kops.gather(recv.reshape(lp, num_procs * c_r), idx)
        return jnp.where(band, vals, v)

    v0 = jnp.full((lp, e_local), -1, jnp.int32)
    v, rounds = streaming.run_exchange(
        grantable, c_r, max_rounds, emit, consume, v0, topo)

    # Provider-side grants, reconstructed post-loop: pair q was served
    # min(demand, rounds*C_r) ranks, of which those within the urn budget
    # (flat slot < t_cap) yielded real endpoints.
    served = jnp.minimum(recv_counts, rounds * c_r)
    granted = jnp.sum(
        jnp.clip(jnp.minimum(served, t_cap - offsets), 0, None),
        axis=1).astype(jnp.int32)
    return v, granted, rounds


def pba_stream_setup_block(ranks, procs_blk, s_blk, cfg: PBAConfig,
                           num_procs: int, topo: Topology):
    """Device block of the sharded stream's setup: phase 1 + exchange 1.

    Runs once per generation; the per-round grant
    (:func:`pba_stream_round_block`) replays the exchange-2 rounds against
    the returned state. Returns (a (lp, E) processor tags, occ (lp, E)
    request ranks, recv_counts (lp, P) provider-side demand) for this
    device's lp logical processors — all of which stay resident on the
    device across rounds; only the per-round compacted edge block ever
    travels to the host.
    """
    a, counts = blocking.map_logical(
        lambda r, fr, ss: _phase1(r, fr, ss, cfg, num_procs),
        ranks, procs_blk, s_blk)                          # (lp, E), (lp, P)
    recv_counts = blocking.transpose_counts(counts, topo)
    occ = jax.vmap(occurrence_rank)(a)
    return a, occ, recv_counts


def pba_stream_round_block(r, a, occ, recv_counts, pool, ranks,
                           cfg: PBAConfig, num_procs: int, round_cap: int,
                           urn_budget: int, block_cap: int, topo: Topology):
    """Round ``r`` of the device-sharded streamed exchange 2.

    The same round contract as :func:`_streamed_exchange2`, unrolled so a
    host driver can interleave rounds with shard write-back: grant request
    ranks [r*C_r, (r+1)*C_r) of every pair from the resident pool, route
    the (lp, P, C_r) buffer through the topology's blocked transpose
    (flat all_to_all or hierarchical two-hop — the round logic never looks
    at the device axes), and gather the received band into this round's
    edges. The per-round device work is the Pallas hot path: the band
    lookup is the resident/chunked gather kernel, the block compaction is
    the fused ``band_compact`` kernel (replacing the historical
    argsort/take_along_axis sequence — bit-identical, the kernels compute
    the same permutation of the same values), and the per-provider band
    counts come from the histogram kernel. Band edges move to the front
    in edge order (request ranks are unique per pair, so compaction is
    collision-free), and only the leading ``block_cap = min(E, P*C_r)``
    columns — a static bound on any round's band size — return to the
    host. Returns (u, v, counts): u, v of shape (lp, block_cap) with -1
    marking padding (and, in ``v``, urn-exhausted grants, which the host
    drops exactly like the host-path stream), and counts (lp, P) — this
    round's per-provider band sizes, the host-side consistency check on
    the compacted block.
    """
    from repro.kernels import ops as kops
    lp = a.shape[0]
    e_local = cfg.edges_per_proc
    out = jax.vmap(
        lambda p, rc: _grant_round(p, rc, r, round_cap, e_local, urn_budget)
    )(pool, recv_counts)                                  # (lp, P, C_r)
    recv = blocking.transpose_payload(out, topo)
    band = (occ >= r * round_cap) & (occ < (r + 1) * round_cap)
    idx = a * round_cap + jnp.clip(occ - r * round_cap, 0, round_cap - 1)
    vals = kops.gather(recv.reshape(lp, num_procs * round_cap), idx)
    v = jnp.where(band, vals, -1)
    j = jnp.arange(e_local, dtype=jnp.int32)
    u = (ranks[:, None] * jnp.int32(cfg.vertices_per_proc)
         + (j // cfg.edges_per_vertex)[None, :])
    u = jnp.where(band, u, -1)
    counts = jax.vmap(
        lambda row: kops.histogram(row, num_procs)
    )(jnp.where(band, a, -1))                             # (lp, P)
    u, v = kops.band_compact(u, v, band, block_cap)
    return u, v, counts


def stream_block_capacity(edges_per_proc: int, num_procs: int,
                          round_cap: int) -> int:
    """Static per-proc bound on a round's band size: every (requester,
    provider) pair contributes at most C_r request ranks per round, and a
    processor never has more than E edges in total."""
    return min(edges_per_proc, num_procs * round_cap)


def pba_shard_body(rank, faction_row, s, cfg: PBAConfig, num_procs: int,
                   pair_capacity: int, topo: Topology):
    """Per-device PBA program (one logical proc per device).

    ``Topology.host()`` => single-device (P must be 1). Thin lp=1 wrapper
    over :func:`pba_logical_block`.
    """
    ranks = jnp.reshape(jnp.asarray(rank, jnp.int32), (1,))
    s_blk = jnp.reshape(jnp.asarray(s, jnp.int32), (1,))
    u, v, dropped, granted, _ = pba_logical_block(
        ranks, faction_row[None], s_blk, cfg, num_procs, pair_capacity,
        topo)
    return u[0], v[0], dropped, granted[0]


def _derived_pair_capacity(cfg: PBAConfig, table: FactionTable) -> int:
    """The capacity every generator path uses for (cfg, table) — shared so
    host/sharded/stream runs of the same config agree on the budget."""
    return cfg.pair_capacity or default_pair_capacity(
        cfg.edges_per_proc, int(table.s.min()), num_procs=table.num_procs,
        exchange_rounds=cfg.exchange_rounds)


def generate_pba(cfg: PBAConfig, table: FactionTable,
                 mesh: Optional[Mesh] = None, axis_name: str = "proc",
                 topology: Optional[Topology] = None
                 ) -> tuple[EdgeList, GenStats]:
    """Generate a PBA graph with one processor per device of ``topology``.

    With mesh=None and topology=None, runs the P-processor program on a
    flat mesh over P real devices — P == table.num_procs must equal the
    topology's device count. ``Topology.pods(r, c)`` routes the two
    exchanges hierarchically (bit-identical output). For P logical
    processors on 1 device (testing), use :func:`generate_pba_host`.
    """
    validate_table(table)
    num_procs = table.num_procs
    topology, mesh = topology_lib.resolve(topology, mesh, axis_name,
                                          default_devices=num_procs)
    if topology.num_devices != num_procs:
        raise ValueError(
            f"generate_pba runs 1 proc per device: table has {num_procs} "
            f"procs but topology {topology.label} has "
            f"{topology.num_devices} devices; use generate_pba_sharded "
            "for P = lp * D")
    pair_capacity = _derived_pair_capacity(cfg, table)
    spec = topology.spec_axes

    procs = jnp.asarray(table.procs)
    s = jnp.asarray(table.s)

    def body(procs_blk, s_blk):
        ranks = blocking.logical_ranks(1, topology)
        u, v, dropped, granted, rounds = pba_logical_block(
            ranks, procs_blk, s_blk, cfg, num_procs, pair_capacity,
            topology)
        return u, v, dropped[None], granted, rounds[None]

    u, v, dropped, granted, rounds = jax.jit(
        spmd.shard_map(
            body, mesh=mesh,
            in_specs=(P(spec, None), P(spec)),
            out_specs=(P(spec, None), P(spec, None), P(spec), P(spec),
                       P(spec)),
            check_vma=False,
        )
    )(procs, s)

    n = num_procs * cfg.vertices_per_proc
    edges = EdgeList(src=u, dst=v, num_vertices=n)
    requested = num_procs * cfg.edges_per_proc
    dropped_n = int(dropped[0])
    stats = GenStats(requested_edges=requested,
                     emitted_edges=requested - dropped_n,
                     dropped_edges=dropped_n, num_vertices=n,
                     exchange_rounds=int(rounds[0]),
                     pair_capacity=pair_capacity)
    return edges, stats


def generate_pba_sharded(cfg: PBAConfig, table: FactionTable,
                         mesh: Optional[Mesh] = None,
                         axis_name: str = "proc",
                         topology: Optional[Topology] = None
                         ) -> tuple[EdgeList, GenStats]:
    """P *logical* processors sharded over a device topology (P = lp·D).

    The paper ran 1000 MPI ranks; a pod has 256 chips — production runs
    several logical processors per chip. Each device vmaps its local block
    of logical procs; the two exchanges become device-level distributed
    transposes of the (local, P)-blocked counts/endpoint tensors — one flat
    all_to_all on a 1-D topology, the hierarchical two-hop
    intra-pod/cross-pod exchange on ``Topology.pods(r, c)``. Bit-identical
    to generate_pba_host for the same table across every topology (tested).
    """
    validate_table(table)
    num_procs = table.num_procs
    topology, mesh = topology_lib.resolve(topology, mesh, axis_name)
    d = topology.num_devices
    lp = topology.lp(num_procs)  # logical procs per device
    pair_capacity = _derived_pair_capacity(cfg, table)
    spec = topology.spec_axes

    procs = jnp.asarray(table.procs).reshape(d, lp, table.max_s)
    s = jnp.asarray(table.s).reshape(d, lp)

    def body(procs_blk, s_blk):
        ranks = blocking.logical_ranks(lp, topology)
        u, v, dropped, _, rounds = pba_logical_block(
            ranks, procs_blk[0], s_blk[0], cfg, num_procs, pair_capacity,
            topology)
        return u[None], v[None], dropped[None], rounds[None]

    u, v, dropped, rounds = jax.jit(
        spmd.shard_map(body, mesh=mesh,
                       in_specs=(P(spec, None, None), P(spec, None)),
                       out_specs=(P(spec, None, None),
                                  P(spec, None, None), P(spec),
                                  P(spec)),
                       check_vma=False)
    )(procs, s)

    n = num_procs * cfg.vertices_per_proc
    requested = num_procs * cfg.edges_per_proc
    dropped_n = int(dropped[0])
    return (EdgeList(src=u, dst=v, num_vertices=n),
            GenStats(requested_edges=requested,
                     emitted_edges=requested - dropped_n,
                     dropped_edges=dropped_n, num_vertices=n,
                     exchange_rounds=int(rounds[0]),
                     pair_capacity=pair_capacity))


def generate_pba_host(cfg: PBAConfig, table: FactionTable,
                      topology: Optional[Topology] = None
                      ) -> tuple[EdgeList, GenStats]:
    """Run the P-logical-processor PBA program on a single device via vmap.

    Exchanges become transposes of the vmapped batch — bit-identical logical
    semantics to the distributed run (tested), handy for CPU validation of
    large P. When validating *across backends* with ``pair_capacity=None``,
    pin the budget from the distributed run's ``GenStats.pair_capacity``
    (the memory-aware default probes per-backend device memory — see
    :func:`default_pair_capacity`). ``topology``, if given, must be
    ``Topology.host()`` — device topologies belong to
    :func:`generate_pba_sharded`.
    """
    validate_table(table)
    if topology is not None and not topology.is_host:
        raise ValueError(
            f"generate_pba_host runs the host topology; pass "
            f"{topology.label} to generate_pba_sharded instead")
    topo = Topology.host()
    num_procs = table.num_procs
    pair_capacity = _derived_pair_capacity(cfg, table)
    procs = jnp.asarray(table.procs)
    s = jnp.asarray(table.s)
    ranks = jnp.arange(num_procs, dtype=jnp.int32)

    @jax.jit
    def run(procs, s, ranks):
        # lp == P on one "device": the exchanges degenerate to local
        # transposes under the same blocked contract as the sharded path.
        u, v, dropped, _, rounds = pba_logical_block(
            ranks, procs, s, cfg, num_procs, pair_capacity, topo)
        return u, v, dropped, rounds

    u, v, dropped, rounds = run(procs, s, ranks)
    n = num_procs * cfg.vertices_per_proc
    requested = num_procs * cfg.edges_per_proc
    dropped_n = int(dropped)
    return (EdgeList(src=u, dst=v, num_vertices=n),
            GenStats(requested_edges=requested,
                     emitted_edges=requested - dropped_n,
                     dropped_edges=dropped_n, num_vertices=n,
                     exchange_rounds=int(rounds),
                     pair_capacity=pair_capacity))


def serial_ba_reference(num_vertices: int, k: int, seed: int = 0) -> EdgeList:
    """Classic serial BA via the uniform-edge-endpoint urn (oracle for tests).

    Pure numpy, sequential — the ground truth the parallel algorithm
    approximates in the P=1 limit.
    """
    rng = np.random.default_rng(seed)
    e = num_vertices * k
    src = np.empty(e, np.int64)
    dst = np.empty(e, np.int64)
    # endpoint slot pool: 2 slots per edge
    pool = np.empty(2 * e, np.int64)
    n_slots = 0
    for v_new in range(num_vertices):
        for _ in range(k):
            i = v_new * k + (_)
            src[i] = v_new
            if n_slots == 0:
                tgt = 0
            else:
                tgt = pool[rng.integers(0, n_slots)]
            dst[i] = tgt
            pool[n_slots] = v_new
            pool[n_slots + 1] = tgt
            n_slots += 2
    return EdgeList(src=jnp.asarray(src, jnp.int32),
                    dst=jnp.asarray(dst, jnp.int32),
                    num_vertices=num_vertices)
