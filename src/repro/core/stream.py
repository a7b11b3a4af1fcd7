"""Out-of-core streaming generation: edge blocks from generator to disk.

Converts the generators from "the graph must fit on device" to "the graph
must fit on disk". Each stream exposes deterministic, independently
regenerable blocks:

  * :class:`PBAStream` — the multi-round exchange contract
    (runtime/streaming.py) driven from the host: block ``r`` is exactly the
    set of edges whose request rank falls in round r's window
    ``[r*C_r, (r+1)*C_r)``. The device resolves one processor's urn at a
    time; endpoints stream through host RAM (O(edges)) into per-round
    blocks.
  * :class:`PBAShardedStream` — the same round contract executed
    device-sharded over any :class:`~repro.runtime.topology.Topology`
    (flat or hierarchical pods): phase 1, the urn pools and every round's
    grant + blocked transpose stay resident across the P = lp * D device
    blocks, and only the compacted per-round edge block is gathered back
    to the host. Bit-identical blocks to :class:`PBAStream` on every
    topology, so the two streams are interchangeable mid-manifest.
  * :class:`PKStream` — closed-form expansion of contiguous index slabs
    (DESIGN.md §2): block ``i`` is edge indices [i*slab, (i+1)*slab), which
    come free because PK edge t depends only on the digits of t.

:func:`stream_to_shards` drives a stream into storage.ShardWriter. Blocks
are deterministic given (config, seed), so a preempted run restarts by
regenerating only the shards the manifest says are missing. Streams that
expose the async ``dispatch_block`` / ``gather_block`` pair (the sharded
stream) are driven double-buffered through
:func:`repro.runtime.streaming.drive_rounds`: round r+1's device grant is
dispatched while round r's block is being written back.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import storage
from repro.core.factions import FactionTable, validate_table
from repro.core.graph import GenStats
from repro.core.pba import (PBAConfig, _derived_pair_capacity, _phase1,
                            _phase2_pool, occurrence_rank,
                            pba_stream_round_block, pba_stream_setup_block,
                            stream_block_capacity)
from repro.core.pk import PKConfig, SeedGraph, decompose_base, pk_sizes
from repro.runtime import blocking, spans, spmd, streaming
from repro.runtime import topology as topology_lib
from repro.runtime.topology import Topology


@dataclasses.dataclass
class EdgeBlock:
    """One streamed block: compacted host-side edges of block ``index``."""

    index: int
    src: np.ndarray
    dst: np.ndarray


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def stream_urn_budget(cfg: PBAConfig, max_demand: int,
                      auto_capacity: bool) -> int:
    """The uniform phase-2 urn budget every stream pool is drawn at.

    The urn draws depend on the size the pool is drawn at
    (``jax.random.bits`` blocks over the whole array), so this budget is
    part of the graph's identity — host-driven and device-sharded streams
    of the same spec must derive the identical value. auto mode covers the
    worst per-processor demand (zero urn-exhaustion drops for any faction
    layout), rounded to a power of two to keep the budget — and therefore
    the graph — stable under small demand perturbations of resumed specs;
    parity mode is the static device budget, bit-compatible with
    ``generate_pba_host``.
    """
    if auto_capacity:
        return _next_pow2(max(max_demand, 1))
    return cfg.total_capacity_factor * cfg.edges_per_proc


def _warn_skewed_budget(cfg: PBAConfig, urn_budget: int,
                        mean_demand: float, resident_procs: int) -> None:
    """Warn when the uniform auto budget is dominated by a demand skew.

    Every resident pool is drawn at the *max* provider's demand, so a hub
    layout re-materializes ~max_demand ints per resident processor — the
    rectangular allocation the streams otherwise avoid. The run is still
    correct (and zero-drop); the warning exists so paper-scale skewed runs
    pin an explicit budget instead of discovering the pool memory cliff
    as a device OOM."""
    import warnings
    if urn_budget > 8 * max(mean_demand, 1):
        warnings.warn(
            f"auto_capacity urn budget {urn_budget} is "
            f"{urn_budget / max(mean_demand, 1):.0f}x the mean provider "
            f"demand: the faction layout is heavily skewed, and every "
            f"resident pool ({resident_procs} per device/host) is drawn "
            f"at the max-demand budget (~4*{urn_budget}B each). For "
            "large skewed runs pin pair_capacity/total_capacity_factor "
            "(auto_capacity=False) to bound pool memory.",
            RuntimeWarning, stacklevel=3)


def _pba_stream_meta(cfg: PBAConfig, table: FactionTable,
                     auto_capacity: bool, num_procs: int, round_cap: int,
                     urn_budget: int) -> dict:
    # Everything the generated graph depends on: resume validation
    # (storage._check_resume) compares this dict, so any omitted knob
    # would let shards of two different graphs interleave silently.
    # The faction table is fingerprinted (two tables with identical cfg
    # still generate different graphs), and spec_digest covers the
    # *full* (cfg, table, auto_capacity) spec — legacy fields can
    # collide on derived values (e.g. two (pair_capacity,
    # exchange_rounds) pairs with the same round_capacity), and a
    # collision must not let a resume silently accept a different spec.
    # Deliberately topology-free: host-driven and device-sharded streams
    # of one spec emit identical blocks (the parity suite pins it), so a
    # manifest started by either is resumable by the other.
    import hashlib
    from repro.core.spec import spec_digest
    digest = hashlib.sha256(
        table.procs.tobytes() + table.s.tobytes()
    ).hexdigest()[:16]
    return {"generator": "pba", "seed": cfg.seed,
            "procs": num_procs,
            "vertices_per_proc": cfg.vertices_per_proc,
            "edges_per_vertex": cfg.edges_per_vertex,
            "interfaction_prob": cfg.interfaction_prob,
            "total_capacity_factor": cfg.total_capacity_factor,
            "auto_capacity": auto_capacity,
            "table_digest": digest,
            "round_capacity": round_cap,
            "urn_budget": urn_budget,
            "spec_digest": spec_digest(cfg, table, auto_capacity)}


class PBAStream:
    """Per-round streaming PBA: generate hub-tail-complete graphs whose
    exchange would not fit on device in one shot.

    Memory shape: the device runs phase 1 plus *one processor's* urn
    resolution at a time — each pool is trimmed to that processor's own
    received demand after the draw, never the rectangular (P, max_demand)
    a vmapped pool would need, which on the hub layout would dwarf the
    edge list itself. The host keeps O(edges) state (tags, ranks, pools)
    and serves block ``r`` — exactly the edges whose request rank falls in
    round r's window [r*C_r, (r+1)*C_r) — as a banded gather, so the graph
    only has to fit on disk plus host RAM, not on device.

    auto_capacity=True (default) budgets every processor's urn at the
    *uniform* :func:`stream_urn_budget` — the maximum received demand over
    all processors, rounded up to a power of two — so no edge is ever
    dropped for urn exhaustion: ``dropped_edges == 0`` for any faction
    layout. The budget is deliberately uniform rather than per-processor
    (the urn draws depend on the size the pool is drawn at, so a uniform
    budget is what lets :class:`PBAShardedStream`'s SPMD pools — which
    must share one static shape across devices — reproduce this stream
    bit for bit; on heavily skewed layouts prefer an explicit
    ``total_capacity_factor`` if the max-demand pool is too large). With
    auto_capacity=False every pool is drawn at
    ``cfg.total_capacity_factor * E`` exactly as on-device generation
    draws it, and blocks concatenate to the bit-identical edge multiset of
    ``generate_pba_host`` with the same streaming config.
    """

    def __init__(self, cfg: PBAConfig, table: FactionTable,
                 auto_capacity: bool = True):
        validate_table(table)
        self.cfg = cfg
        self.table = table
        self._auto_capacity = auto_capacity
        self.num_procs = table.num_procs
        self.num_vertices = self.num_procs * cfg.vertices_per_proc
        self.requested_edges = self.num_procs * cfg.edges_per_proc
        # Same derivation as the on-device generators, so parity mode
        # reproduces generate_pba_host at the identical budget.
        pair_capacity = _derived_pair_capacity(cfg, table)
        self.pair_capacity = pair_capacity
        self.round_cap = streaming.round_capacity(
            pair_capacity, cfg.exchange_rounds or 1)

        cfg_ = cfg
        num_procs = self.num_procs
        e_local = cfg.edges_per_proc

        @jax.jit
        def prep(procs, s, ranks):
            a, counts = blocking.map_logical(
                lambda r, fr, ss: _phase1(r, fr, ss, cfg_, num_procs),
                ranks, procs, s)
            occ = jax.vmap(occurrence_rank)(a)
            return a, occ, counts

        ranks = jnp.arange(num_procs, dtype=jnp.int32)
        a, occ, counts = prep(jnp.asarray(table.procs),
                              jnp.asarray(table.s), ranks)
        self._a = np.asarray(a)
        self._occ = np.asarray(occ)
        counts_h = np.asarray(counts)          # (requester, provider)
        self.num_blocks = streaming.rounds_needed(
            max(int(counts_h.max()), 1), self.round_cap)

        demand = counts_h.sum(axis=0, dtype=np.int64)  # per-provider total
        self.urn_budget = stream_urn_budget(cfg, int(demand.max()),
                                            auto_capacity)
        if auto_capacity:
            _warn_skewed_budget(cfg, self.urn_budget, float(demand.mean()),
                                1)
        t_cap = np.full(num_procs, self.urn_budget, np.int64)
        self._t_cap = t_cap

        # Resolve one processor's urn at a time. The urn draws depend on
        # the pool length (threefry blocks over the whole array), so the
        # budget a pool is *drawn at* is part of the graph's identity:
        # every stream draws at the one uniform ``stream_urn_budget`` (and
        # parity mode's budget is exactly the static device budget, so
        # blocks reproduce ``generate_pba_host`` slot for slot). The rows
        # are trimmed to each processor's own demand after the draw, so
        # resident host memory stays O(edges).
        pool_fn = jax.jit(lambda r: _phase2_pool(r, cfg_, self.urn_budget))
        rows = []
        for p in range(num_procs):
            used = int(min(demand[p], self.urn_budget))
            rows.append(np.asarray(pool_fn(jnp.int32(p)))[: e_local + used])

        # Resolve every edge's endpoint once (host, vectorized): the edge
        # (i, j) with tag a[i,j]=p and occurrence rank occ[i,j] was granted
        # provider p's pool slot offsets[p, i] + occ[i,j] (offsets from the
        # unclipped demand — same addressing as _grant_round).
        recv = counts_h.T.astype(np.int64)     # (provider, requester)
        offsets = np.cumsum(recv, axis=1) - recv
        row_start = np.concatenate(
            [[0], np.cumsum([len(r) for r in rows[:-1]])]).astype(np.int64)
        pool_flat = np.concatenate(rows)
        prov = self._a
        slot = offsets[prov, np.arange(num_procs)[:, None]] + self._occ
        in_budget = slot < t_cap[prov]
        idx = row_start[prov] + e_local + np.where(in_budget, slot, 0)
        v = np.where(in_budget, pool_flat[idx], -1).astype(np.int32)
        u = (np.arange(num_procs, dtype=np.int32)[:, None]
             * np.int32(cfg.vertices_per_proc)
             + (np.arange(e_local, dtype=np.int32)
                // cfg.edges_per_vertex)[None, :])

        # Bucket edges by round once, so block(i) is a slice instead of a
        # full (P, E) band rescan per round (which would make streaming
        # O(E * num_blocks) in exactly the small-C_r regime it targets).
        block_id = (self._occ // self.round_cap).ravel()
        order = np.argsort(block_id, kind="stable")
        self._bounds = np.searchsorted(
            block_id[order], np.arange(self.num_blocks + 1))
        self._u_sorted = u.ravel()[order]
        self._v_sorted = v.ravel()[order]
        del self._a, self._occ  # only the sorted views are needed now

    @property
    def exchange_rounds(self) -> int:
        return self.num_blocks

    def meta(self) -> dict:
        return _pba_stream_meta(self.cfg, self.table, self._auto_capacity,
                                self.num_procs, self.round_cap,
                                self.urn_budget)

    def block(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """Edges resolved in round ``i``: request ranks [i*C_r, (i+1)*C_r)."""
        if not 0 <= i < self.num_blocks:
            raise ValueError(f"block {i} out of range [0, {self.num_blocks})")
        lo, hi = self._bounds[i], self._bounds[i + 1]
        u, v = self._u_sorted[lo:hi], self._v_sorted[lo:hi]
        keep = v >= 0
        return u[keep], v[keep]

    def iter_blocks(self) -> Iterator[EdgeBlock]:
        for i in range(self.num_blocks):
            src, dst = self.block(i)
            yield EdgeBlock(i, src, dst)


@functools.lru_cache(maxsize=None)
def _sharded_setup_fn(cfg: PBAConfig, num_procs: int, topo: Topology):
    """Compiled SPMD setup program (phase 1 + exchange 1) for a sharded
    stream, cached per (cfg, P, topology): repeated streams of one spec —
    resume legs, overlap benchmarks, shard + memory sinks of the same
    graph — reuse the jit traces instead of recompiling per instance."""
    with spans.span("repro.build", program="setup_body"):
        lp = num_procs // topo.num_devices
        mesh = topo.build_mesh()
        spec = topo.spec_axes

        def setup_body(procs_blk, s_blk):
            ranks = blocking.logical_ranks(lp, topo)
            a, occ, recv = pba_stream_setup_block(
                ranks, procs_blk[0], s_blk[0], cfg, num_procs, topo)
            return a[None], occ[None], recv[None]

        return jax.jit(spmd.shard_map(
            setup_body, mesh=mesh,
            in_specs=(PartitionSpec(spec, None, None),
                      PartitionSpec(spec, None)),
            out_specs=(PartitionSpec(spec, None, None),) * 3,
            check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_grant_fns(cfg: PBAConfig, num_procs: int, topo: Topology,
                       urn_budget: int, round_cap: int, block_cap: int):
    """Compiled SPMD (pool, round) programs for a sharded stream — keyed
    separately from setup because the urn budget is demand-derived in auto
    mode, so it is only known after setup has run. One round trace serves
    every round: the round index is a traced scalar."""
    with spans.span("repro.build", program="pool_body,round_body"):
        lp = num_procs // topo.num_devices
        mesh = topo.build_mesh()
        spec = topo.spec_axes

        def pool_body():
            ranks = blocking.logical_ranks(lp, topo)
            pool = blocking.map_logical(
                lambda r: _phase2_pool(r, cfg, urn_budget), ranks)
            return pool[None]

        pool_fn = jax.jit(spmd.shard_map(
            pool_body, mesh=mesh, in_specs=(),
            out_specs=PartitionSpec(spec, None, None), check_vma=False))

        def round_body(r, a_blk, occ_blk, recv_blk, pool_blk):
            ranks = blocking.logical_ranks(lp, topo)
            u, v, counts = pba_stream_round_block(
                r, a_blk[0], occ_blk[0], recv_blk[0], pool_blk[0], ranks,
                cfg, num_procs, round_cap, urn_budget, block_cap, topo)
            return u[None], v[None], counts[None]

        round_fn = jax.jit(spmd.shard_map(
            round_body, mesh=mesh,
            in_specs=(PartitionSpec(),)
            + (PartitionSpec(spec, None, None),) * 4,
            out_specs=(PartitionSpec(spec, None, None),) * 3,
            check_vma=False))
        return pool_fn, round_fn


class PBAShardedStream:
    """Device-sharded streaming PBA: the out-of-core round contract of
    :class:`PBAStream`, executed over a real device :class:`Topology`.

    The paper's headline run (1B vertices / 5B edges in 13 s) generates on
    the full machine while edges stream out-of-core — the exchange must
    use the devices *and* the edge list must never materialize anywhere.
    This stream keeps all O(P) state resident and device-sharded under the
    blocked layout (P = lp * D): phase 1 tags/ranks (lp, E), the
    transposed demand (lp, P) and each logical processor's urn pool live
    on their device across rounds, every round's grant routes through the
    topology's blocked transpose (flat all_to_all, or the hierarchical
    two-hop on ``Topology.pods`` — streaming rides the 2-D-mesh transpose
    with no new exchange code), and only the compacted per-round edge
    block — (P, min(E, P*C_r)) ints — is gathered back to the host for the
    shard writer. Per-device memory is O(lp * (E + urn budget + P*C_r)),
    independent of the round count; the graph has to fit on disk only.

    Bit-parity: blocks are bit-identical to :class:`PBAStream` for the
    same (cfg, table, auto_capacity) on every topology — both streams
    derive the same round windows, draw pools at the same uniform
    :func:`stream_urn_budget`, and address the same slots — so manifests
    written by either driver resume under the other, and parity mode
    (``auto_capacity=False``) reproduces ``generate_pba_host``'s edge
    multiset exactly like the host stream does.

    ``dispatch_block(i)`` / ``gather_block(handle)`` split each block into
    an async device dispatch and a blocking host gather, which is what
    lets :func:`stream_to_shards` double-buffer round r+1's grant against
    round r's write-back (``runtime.streaming.drive_rounds``).
    """

    def __init__(self, cfg: PBAConfig, table: FactionTable,
                 topology: Optional[Topology] = None,
                 auto_capacity: bool = True):
        validate_table(table)
        self.cfg = cfg
        self.table = table
        self._auto_capacity = auto_capacity
        self.num_procs = table.num_procs
        self.num_vertices = self.num_procs * cfg.vertices_per_proc
        self.requested_edges = self.num_procs * cfg.edges_per_proc
        pair_capacity = _derived_pair_capacity(cfg, table)
        self.pair_capacity = pair_capacity
        self.round_cap = streaming.round_capacity(
            pair_capacity, cfg.exchange_rounds or 1)

        topo, _ = topology_lib.resolve(topology, None)
        self.topology = topo
        d = topo.num_devices
        lp = topo.lp(self.num_procs)
        self.lp = lp
        num_procs = self.num_procs

        setup = _sharded_setup_fn(cfg, num_procs, topo)
        with spans.span("repro.dispatch", program="setup_body"):
            procs = jnp.asarray(table.procs).reshape(d, lp, table.max_s)
            s = jnp.asarray(table.s).reshape(d, lp)
            # Resident device state, blocked (d, lp, ...): tags, request
            # ranks and provider-side demand never leave the mesh.
            self._a, self._occ, self._recv = setup(procs, s)

        with spans.span("repro.wait"):
            recv_h = np.asarray(self._recv).reshape(num_procs, num_procs)
        demand = recv_h.sum(axis=1, dtype=np.int64)  # per-provider total
        self.num_blocks = streaming.rounds_needed(
            max(int(recv_h.max()), 1), self.round_cap)
        self.urn_budget = stream_urn_budget(cfg, int(demand.max()),
                                            auto_capacity)
        if auto_capacity:
            _warn_skewed_budget(cfg, self.urn_budget, float(demand.mean()),
                                lp)
        self.block_cap = stream_block_capacity(cfg.edges_per_proc,
                                               num_procs, self.round_cap)
        pool_fn, self._round = _sharded_grant_fns(
            cfg, num_procs, topo, self.urn_budget, self.round_cap,
            self.block_cap)
        with spans.span("repro.dispatch", program="pool_body"):
            self._pool = pool_fn()

    @property
    def exchange_rounds(self) -> int:
        return self.num_blocks

    def meta(self) -> dict:
        return _pba_stream_meta(self.cfg, self.table, self._auto_capacity,
                                self.num_procs, self.round_cap,
                                self.urn_budget)

    def dispatch_block(self, i: int):
        """Enqueue round ``i``'s device program; returns the in-flight
        handle, ``(i, (u, v, counts))``, without blocking on its
        completion."""
        if not 0 <= i < self.num_blocks:
            raise ValueError(f"block {i} out of range [0, {self.num_blocks})")
        with spans.span("repro.dispatch", program="round_body", round=i):
            return i, self._round(jnp.int32(i), self._a, self._occ,
                                  self._recv, self._pool)

    def gather_block(self, handle) -> tuple[np.ndarray, np.ndarray]:
        """Materialize a dispatched round on host and compact it: blocks
        until the device round finishes, then drops padding and
        urn-exhausted slots. Rank-major blocked layout + on-device
        edge-order compaction means the result is already in the host
        stream's block order. The round's kernel-counted per-provider band
        sizes (the histogram output) must equal the number of compacted
        band slots — a cheap cross-check that the fused compaction kernel
        and the gather agreed on the band."""
        i, block = handle
        with spans.span("repro.wait", round=i):
            jax.block_until_ready(block)
        with spans.span("repro.sink.d2h", round=i,
                        bytes=sum(a.nbytes for a in block)):
            u, v, counts = (np.asarray(a) for a in block)
        with spans.span("repro.sink.compact", round=i) as sp:
            u = u.reshape(-1)
            v = v.reshape(-1)
            band_slots = int((u >= 0).sum())
            counted = int(counts.sum())
            if band_slots != counted:
                raise AssertionError(
                    f"round block inconsistency: compaction kept "
                    f"{band_slots} band slots but the count kernel saw "
                    f"{counted}")
            keep = (u >= 0) & (v >= 0)
            u, v = u[keep], v[keep]
            sp.set_metadata(edges=len(u))
        return u, v

    def block(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """Edges resolved in round ``i``: request ranks [i*C_r, (i+1)*C_r)."""
        return self.gather_block(self.dispatch_block(i))

    def iter_blocks(self) -> Iterator[EdgeBlock]:
        for i in range(self.num_blocks):
            src, dst = self.block(i)
            yield EdgeBlock(i, src, dst)


class PKStream:
    """Per-slab streaming PK: contiguous index ranges, zero communication.

    Block ``i`` covers edge indices [i*slab_edges, (i+1)*slab_edges); the
    slab start is digit-decomposed exactly on host, so block generation
    needs only int32 device arithmetic regardless of global edge count.
    The slab index doubles as the RNG rank, so blocks are deterministic
    given (cfg.seed, slab_edges) — independent of how many were already
    written.
    """

    def __init__(self, seed: SeedGraph, cfg: PKConfig,
                 slab_edges: int = 1 << 20):
        SeedGraph.validate(seed)
        if slab_edges < 1:
            raise ValueError(f"slab_edges must be >= 1, got {slab_edges}")
        if slab_edges > 2**31 - 1:
            raise ValueError(f"slab_edges {slab_edges} exceeds int32")
        self.seed = seed
        self.cfg = cfg
        self.slab_edges = slab_edges
        n, e = pk_sizes(seed, cfg)
        if n > 2**31 - 1:
            raise ValueError(f"n0^L = {n} exceeds int32 vertex-id space")
        self.num_vertices = n
        self.requested_edges = e
        self.num_blocks = -(-e // slab_edges)
        self.exchange_rounds = 1

        su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
        n0, e0, levels = seed.num_vertices, seed.num_edges, cfg.levels

        @jax.jit
        def expand(t, base, rank):
            from repro.kernels import ops as kops
            return kops.pk_expand(t, base, su, sv, n0, e0, levels, cfg.noise,
                                  cfg.delete_prob, cfg.seed, rank)

        self._expand = expand
        self._t = jnp.arange(slab_edges, dtype=jnp.int32)

    def meta(self) -> dict:
        # spec_digest covers the seed graph's actual edge arrays: two seeds
        # with the same (n0, e0) but different edges produce the same
        # legacy meta and manifest shapes, and only the digest stops a
        # resume from interleaving their shards.
        from repro.core.spec import spec_digest
        return {"generator": "pk", "seed": self.cfg.seed,
                "levels": self.cfg.levels, "noise": self.cfg.noise,
                "delete_prob": self.cfg.delete_prob,
                "slab_edges": self.slab_edges,
                "spec_digest": spec_digest(self.seed, self.cfg,
                                           self.slab_edges)}

    def block(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        if not 0 <= i < self.num_blocks:
            raise ValueError(f"block {i} out of range [0, {self.num_blocks})")
        t0 = i * self.slab_edges
        base = jnp.asarray(decompose_base(t0, self.seed.num_edges,
                                          self.cfg.levels))
        u, v = self._expand(self._t, base, jnp.int32(i))
        m = min(self.slab_edges, self.requested_edges - t0)
        u = np.asarray(u)[:m]
        v = np.asarray(v)[:m]
        keep = (u >= 0) & (v >= 0)
        return u[keep], v[keep]

    def iter_blocks(self) -> Iterator[EdgeBlock]:
        for i in range(self.num_blocks):
            src, dst = self.block(i)
            yield EdgeBlock(i, src, dst)


def stream_stats(stream, emitted: int) -> GenStats:
    """The one stats contract for a drained stream (shards or memory)."""
    return GenStats(requested_edges=stream.requested_edges,
                    emitted_edges=emitted,
                    dropped_edges=stream.requested_edges - emitted,
                    num_vertices=stream.num_vertices,
                    exchange_rounds=stream.exchange_rounds,
                    pair_capacity=getattr(stream, "pair_capacity", 0))


def stream_to_shards(stream, out_dir: str, meta: Optional[dict] = None,
                     overlap: bool = True) -> tuple[dict, GenStats]:
    """Drive a stream's blocks into the resumable shard writer.

    Returns (manifest, stats). On restart only the blocks the manifest
    reports missing are regenerated — completed shards are never rewritten
    or even recomputed. Streams exposing the async
    ``dispatch_block`` / ``gather_block`` pair (the device-sharded stream)
    are driven double-buffered: block i+1's device round is dispatched
    before block i is gathered and written, so device compute overlaps the
    host's compress-and-write (``overlap=False`` serializes them).
    """
    writer = storage.ShardWriter(out_dir, stream.num_vertices,
                                 stream.num_blocks,
                                 meta={**stream.meta(), **(meta or {})})
    missing = writer.missing()
    if hasattr(stream, "dispatch_block"):
        streaming.drive_rounds(
            missing, stream.dispatch_block,
            lambda i, handle: writer.write_block(
                i, *stream.gather_block(handle)),
            overlap=overlap)
    else:
        for i in missing:
            src, dst = stream.block(i)
            writer.write_block(i, src, dst)
    return writer.manifest, stream_stats(stream, writer.edges_written)
