"""One front door: ``GraphSpec -> plan() -> generate()``.

The paper's scenario is a generator-as-a-service — callers describe the
graph they want and the cluster produces it. This module is that service's
single entry point over the internal executors (``core/pba.py``,
``core/pk.py``, ``core/stream.py``):

    from repro import api

    spec = api.GraphSpec(model="pba", procs=8, vertices_per_proc=100_000,
                         edges_per_vertex=5, seed=7)
    pl = api.plan(spec)        # inspectable, validated — no compilation
    print(pl.describe())
    res = api.generate(pl)     # EdgeList or shard manifest, with GenStats

``plan`` resolves everything up front — execution path, topology and the
P = lp * D factorization, the derived pair capacity, round budgets, and
rough device/host/disk byte estimates — and raises clear errors (e.g. a
logical-processor count that does not factor over the device topology)
*before* any JAX compilation. ``generate`` dispatches the plan to the
legacy entry points, which remain as thin internal executors; their public
names in ``repro.core`` are deprecation shims.

``preset(name)`` returns ready-made specs for the paper-table scenarios
(``paper_1b_5b``, ``pod_1000rank``, smoke sizes); see :data:`PRESETS`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import numpy as np

from repro.core import cfree as cfree_lib
from repro.core import factions as factions_lib
from repro.core import pba as pba_lib
from repro.core import pk as pk_lib
from repro.core import storage as storage_lib
from repro.core import stream as stream_lib
from repro.core.cfree import CFreeConfig
from repro.core.factions import FactionSpec, FactionTable, validate_table
from repro.core.graph import EdgeList, GenStats
from repro.core.pba import PBAConfig
from repro.core.pk import PKConfig, SeedGraph
from repro.core.spec import (CFREE_MODELS, EXECUTIONS, MODELS, SINKS,
                             GraphSpec)
from repro.runtime import spans, spmd, streaming
from repro.runtime.topology import Topology

__all__ = ["GraphSpec", "GenPlan", "GenResult", "plan", "generate",
           "preset", "PRESETS", "Topology", "FactionSpec"]


# --- plan ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class GenPlan:
    """A validated, inspectable compilation of a :class:`GraphSpec`.

    Everything ``generate`` needs is resolved here: the executor (which
    legacy entry point runs), the :class:`Topology` and its P = lp * D
    factorization, the derived exchange budgets, and byte estimates. Built
    without compiling anything, so ``plan`` + :meth:`describe` double as a
    ``--dry-run`` capacity-planning tool.
    """

    spec: GraphSpec
    model: str
    execution: str              # resolved: host | sharded | streamed
    sink: str
    executor: str               # internal entry point the plan dispatches to
    topology: Topology
    num_procs: int              # logical processors P (pba) / ranks (pk)
    lp: int                     # logical procs per device (P = lp * D)
    num_vertices: int
    requested_edges: int
    pair_capacity: int          # per-(sender, receiver) budget C (0 for pk)
    exchange_rounds: int        # configured rounds R (1 = single-shot)
    round_capacity: int         # C_r = ceil(C / R) (0 for pk)
    urn_budget: int             # phase-2 urn slots per proc (0 for pk)
    device_bytes: int           # rough per-device working set
    host_bytes: int             # rough host-RAM working set
    disk_bytes: int             # rough on-disk size (0 for memory sink)
    config: Union[PBAConfig, PKConfig, CFreeConfig]
    table: Optional[FactionTable] = None
    seed_graph: Optional[SeedGraph] = None
    block_bytes: int = 0        # streamed: per-round gathered block
    overlap_bytes: int = 0      # streamed: extra in-flight double-buffer

    def describe(self) -> str:
        """Human-readable resolved plan (the --dry-run output)."""
        d = self.topology.num_devices
        lines = [
            f"GraphSpec[{self.model}] seed={self.config.seed} -> "
            f"{self.num_vertices:,} vertices, "
            f"{self.requested_edges:,} edges",
            f"  executor:  {self.executor} "
            f"(execution={self.execution}, sink={self.sink}"
            + (f", out_dir={self.spec.out_dir}" if self.spec.out_dir
               else "") + ")",
            f"  topology:  {self.topology.label}  "
            f"P = lp*D = {self.lp} * {d} = {self.num_procs}",
        ]
        if self.model == "pba":
            lines.append(
                f"  exchange:  pair_capacity={self.pair_capacity}, "
                f"rounds={self.exchange_rounds}, "
                f"C_r={self.round_capacity}, "
                f"urn_budget={self.urn_budget}")
        elif self.model == "pk":
            lines.append(
                f"  expansion: levels={self.config.levels}, "
                f"seed {self.seed_graph.num_vertices}v/"
                f"{self.seed_graph.num_edges}e, zero communication")
        else:
            lines.append(
                f"  cfree:     edge t is a pure function of (seed, t) — "
                f"zero exchange rounds, any partition bit-identical")
        if self.execution == "streamed":
            lines.append(
                f"  stream:    block ~{_fmt_bytes(self.block_bytes)}/round"
                + (f", overlap buffer ~{_fmt_bytes(self.overlap_bytes)}"
                   if self.overlap_bytes else ", overlap off"))
            if self.model == "pba" and self.spec.auto_capacity:
                # The auto urn budget is pow2(max per-provider demand),
                # known only at run time; the static budget stands in for
                # the estimates below and can understate pool memory
                # badly on skewed (hub) layouts.
                lines.append(
                    "  caveat:    auto_capacity pools are demand-sized at "
                    "run time (worst case ~P*E on hub layouts); byte "
                    "estimates assume the static urn budget — pin "
                    "total_capacity_factor for exact planning")
        lines.append(
            f"  bytes:     device ~{_fmt_bytes(self.device_bytes)}, "
            f"host ~{_fmt_bytes(self.host_bytes)}, "
            f"disk ~{_fmt_bytes(self.disk_bytes)}")
        return "\n".join(lines)


@dataclasses.dataclass
class GenResult:
    """What ``generate`` returns: the plan it ran, stats, and the sink's
    product — an in-memory :class:`EdgeList` and/or a shard manifest."""

    plan: GenPlan
    stats: GenStats
    edges: Optional[EdgeList] = None
    manifest: Optional[dict] = None
    out_dir: Optional[str] = None


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


def _resolve_factions(spec: GraphSpec) -> FactionTable:
    f = spec.factions
    p = spec.procs
    if isinstance(f, FactionTable):
        table = f
    elif isinstance(f, FactionSpec):
        table = factions_lib.make_factions(p, f)
    elif isinstance(f, str):
        if f == "hub":
            table = factions_lib.hub_factions(p)
        elif f.startswith("block:"):
            table = factions_lib.block_factions(p, int(f.split(":", 1)[1]))
        else:
            raise ValueError(
                f"unknown faction layout {f!r}: use 'hub', 'block:<size>', "
                "a FactionSpec, or a FactionTable")
    elif f is None:
        table = factions_lib.make_factions(
            p, FactionSpec(max(p // 2, 1), min(2, p),
                           min(max(p // 2, 2), p), seed=1))
    else:
        raise ValueError(f"cannot build factions from {type(f).__name__}")
    validate_table(table)
    if table.num_procs != p:
        raise ValueError(
            f"faction table covers {table.num_procs} processors but the "
            f"spec asks for procs={p}")
    return table


def _resolve_execution(spec: GraphSpec, divisible: bool) -> str:
    """Pick the execution path for ``auto``; validate explicit requests."""
    ex = spec.execution
    if ex not in EXECUTIONS:
        raise ValueError(f"unknown execution {ex!r}: one of {EXECUTIONS}")
    topo = spec.topology
    if ex == "auto":
        if spec.sink == "shards":
            # streamed covers both drivers: the planner picks the
            # device-sharded stream whenever a device topology is usable.
            return "streamed"
        if topo is not None and topo.is_host:
            return "host"
        d = topo.num_devices if topo is not None else spmd.device_count()
        if d > 1 and divisible:
            return "sharded"
        return "host"
    if ex == "host" and topo is not None and not topo.is_host:
        raise ValueError(
            f"host execution cannot run over device topology "
            f"{topo.label}; use execution='sharded'")
    if ex == "sharded" and topo is not None and topo.is_host:
        raise ValueError(
            "sharded execution needs a device topology, got "
            "Topology.host(); use execution='host'")
    return ex


def _device_topology(spec: GraphSpec,
                     num_procs: Optional[int] = None) -> tuple[Topology, int]:
    """(topology, lp) for sharded execution — errors before compilation.

    ``num_procs=None`` skips the P = lp * D factorization (PK partitions
    the index space per device; there is no logical-processor count)."""
    topo = spec.topology or Topology.flat(spmd.device_count())
    # raises when P does not factor over D
    lp = topo.lp(num_procs) if num_procs is not None else 1
    avail = spmd.device_count()
    if topo.num_devices > avail:
        raise ValueError(
            f"topology {topo.label} needs {topo.num_devices} devices but "
            f"only {avail} are present")
    return topo, lp


def _streamed_pba_topology(spec: GraphSpec,
                           num_procs: int) -> tuple[Topology, int, str]:
    """(topology, lp, executor) for a streamed PBA plan.

    Streamed execution runs device-sharded (``PBAShardedStream``: the
    exchange on the mesh, edges out-of-core) whenever a device topology is
    usable — an explicit non-host topology, or D > 1 present devices that
    P divides. The host-driven stream remains the single-device fallback,
    and ``topology=Topology.host()`` requests it explicitly.
    """
    topo = spec.topology
    if topo is not None:
        if topo.is_host:
            return Topology.host(), num_procs, "pba_stream"
        topo, lp = _device_topology(spec, num_procs)
        return topo, lp, "pba_stream_sharded"
    d = spmd.device_count()
    if d > 1 and num_procs % d == 0:
        return Topology.flat(d), num_procs // d, "pba_stream_sharded"
    return Topology.host(), num_procs, "pba_stream"


def _plan_pba(spec: GraphSpec) -> GenPlan:
    if spec.procs < 1 or spec.vertices_per_proc < 1 \
            or spec.edges_per_vertex < 1:
        raise ValueError(
            "pba scale incomplete: procs, vertices_per_proc and "
            f"edges_per_vertex must all be >= 1, got ({spec.procs}, "
            f"{spec.vertices_per_proc}, {spec.edges_per_vertex})")
    table = _resolve_factions(spec)
    cfg = PBAConfig(vertices_per_proc=spec.vertices_per_proc,
                    edges_per_vertex=spec.edges_per_vertex,
                    interfaction_prob=spec.interfaction_prob,
                    pair_capacity=spec.pair_capacity,
                    exchange_rounds=spec.exchange_rounds,
                    total_capacity_factor=spec.total_capacity_factor,
                    seed=spec.seed)
    p = spec.procs
    execution = _resolve_execution(
        spec, divisible=p % max(spmd.device_count(), 1) == 0
        if spec.topology is None else True)
    executor = "generate_pba"
    if execution == "sharded":
        topo, lp = _device_topology(spec, p)
    elif execution == "streamed":
        topo, lp, executor = _streamed_pba_topology(spec, p)
    else:
        topo, lp = Topology.host(), p

    pair_capacity = pba_lib._derived_pair_capacity(cfg, table)
    rounds = cfg.exchange_rounds or 1
    c_r = streaming.round_capacity(pair_capacity, rounds)
    e = cfg.edges_per_proc
    t_cap = cfg.total_capacity_factor * e
    requested = p * e

    # Rough working sets (int32 everywhere). Sharded/host: each device
    # holds its lp-block of edges, counts, one round buffer, and pools.
    # (Streamed auto_capacity pools are demand-sized at run time; the
    # static budget stands in here — plan() never runs phase 1.)
    per_proc = 4 * (4 * e + p + p * c_r + (e + t_cap))
    block_bytes = overlap_bytes = 0
    if execution == "streamed":
        block_cap = pba_lib.stream_block_capacity(e, p, c_r)
        block_bytes = 8 * p * block_cap  # gathered (u, v) block per round
        if executor == "pba_stream_sharded":
            # Resident per-device state: tags + ranks (2E), pool
            # (E + t_cap), demand row (P), double round buffers
            # (emit + recv), and the compacted block output — per
            # *logical proc*, times the lp block the device hosts.
            device_bytes = 4 * lp * (3 * e + t_cap + p + 2 * p * c_r
                                     + 2 * block_cap)
            host_bytes = block_bytes
            if spec.overlap:
                # Double buffering keeps a second block in flight: its
                # device output plus the host copy being written back.
                overlap_bytes = 2 * block_bytes
                host_bytes += block_bytes
        else:
            # Host-driven stream: phase 1 runs vmapped over all P on one
            # device; urns resolve one proc at a time; the host keeps
            # O(edges) tags/ranks/pools.
            device_bytes = 4 * (2 * p * e + p * p) + 4 * (e + t_cap)
            host_bytes = 4 * 4 * p * e
    else:
        device_bytes = lp * per_proc
        host_bytes = 8 * requested if spec.sink == "memory" else 0
    disk_bytes = 8 * requested if spec.sink == "shards" else 0

    return GenPlan(spec=spec, model="pba", execution=execution,
                   sink=spec.sink, executor=executor, topology=topo,
                   num_procs=p, lp=lp,
                   num_vertices=p * cfg.vertices_per_proc,
                   requested_edges=requested, pair_capacity=pair_capacity,
                   exchange_rounds=rounds, round_capacity=c_r,
                   urn_budget=t_cap, device_bytes=device_bytes,
                   host_bytes=host_bytes, disk_bytes=disk_bytes,
                   config=cfg, table=table, block_bytes=block_bytes,
                   overlap_bytes=overlap_bytes)


def _plan_pk(spec: GraphSpec) -> GenPlan:
    if spec.levels < 1:
        raise ValueError(f"pk needs levels >= 1, got {spec.levels}")
    seed_graph = spec.seed_graph or pk_lib.star_clique_seed(5)
    SeedGraph.validate(seed_graph)
    cfg = PKConfig(levels=spec.levels, noise=spec.noise,
                   delete_prob=spec.delete_prob, seed=spec.seed)
    n, e = pk_lib.pk_sizes(seed_graph, cfg)
    if n > 2**31 - 1:
        raise ValueError(
            f"n0^L = {n} exceeds int32 vertex-id space "
            f"(n0={seed_graph.num_vertices}, L={cfg.levels})")
    execution = _resolve_execution(spec, divisible=True)
    if execution == "streamed" and spec.topology is not None \
            and not spec.topology.is_host:
        raise ValueError(
            f"pk streamed execution is host-driven (slabs are already "
            f"communication-free); it cannot run over device topology "
            f"{spec.topology.label} — use execution='sharded' for "
            "on-device expansion or drop the topology")
    if execution == "sharded":
        topo, lp = _device_topology(spec)
        num_procs = topo.num_devices
        chunk = -(-e // num_procs)
        executor = "generate_pk"
    else:
        topo, num_procs, lp = Topology.host(), 1, 1
        chunk = spec.slab_edges if execution == "streamed" else e
        executor = ("pk_stream" if execution == "streamed"
                    else "generate_pk_host")
    if chunk > 2**31 - 1:
        raise ValueError(
            f"per-device chunk {chunk} exceeds int32 — shard over more "
            "devices or use streamed execution with a smaller slab_edges")

    # Expansion materializes (L, m) digit planes plus the (m,) outputs.
    device_bytes = 4 * chunk * (2 * cfg.levels + 4)
    host_bytes = 8 * e if spec.sink == "memory" else 8 * chunk
    disk_bytes = 8 * e if spec.sink == "shards" else 0
    block_bytes = 8 * min(spec.slab_edges, e) \
        if execution == "streamed" else 0
    return GenPlan(spec=spec, model="pk", execution=execution,
                   sink=spec.sink, executor=executor, topology=topo,
                   num_procs=num_procs, lp=lp, num_vertices=n,
                   requested_edges=e, pair_capacity=0, exchange_rounds=1,
                   round_capacity=0, urn_budget=0,
                   device_bytes=device_bytes, host_bytes=host_bytes,
                   disk_bytes=disk_bytes, config=cfg,
                   seed_graph=seed_graph, block_bytes=block_bytes)


def _plan_cfree(spec: GraphSpec) -> GenPlan:
    cfg = CFreeConfig(model=spec.model, vertices=spec.cfree_vertices,
                      edges=spec.cfree_edges, ba_degree=spec.ba_degree,
                      rmat_a=spec.rmat_a, rmat_b=spec.rmat_b,
                      rmat_c=spec.rmat_c, seed=spec.seed)
    CFreeConfig.validate(cfg)
    n, e = cfree_lib.cfree_sizes(cfg)
    p_req = spec.procs
    execution = _resolve_execution(
        spec, divisible=True if spec.topology is not None or p_req == 0
        else p_req % max(spmd.device_count(), 1) == 0)

    # Working set per logical rank: the index vector, the endpoint pair,
    # and the ba chain-resolution temporaries — a handful of int32 arrays
    # of the rank's chunk, no pools, no round buffers, no exchange.
    block_bytes = 0
    if execution == "sharded":
        d = (spec.topology.num_devices if spec.topology is not None
             else spmd.device_count())
        p = p_req or d
        topo, lp = _device_topology(spec, p)
        executor = "generate_cfree"
        chunk = -(-e // p) if e else 0
        device_bytes = 4 * lp * chunk * 6
    elif execution == "streamed":
        topo = spec.topology
        if topo is None and spmd.device_count() > 1:
            topo = Topology.flat(spmd.device_count())
        if topo is not None and not topo.is_host:
            topo, _ = _device_topology(spec)
            p, lp, executor = topo.num_devices, 1, "cfree_stream_sharded"
        else:
            topo, p, lp = Topology.host(), 1, 1
            executor = "cfree_stream"
        slab = min(spec.slab_edges, e) if e else 0
        block_bytes = 8 * slab
        device_bytes = 4 * -(-slab // max(topo.num_devices, 1)) * 6
    else:
        topo, lp = Topology.host(), max(p_req, 1)
        p = lp
        executor = "generate_cfree_host"
        device_bytes = 4 * e * 6
    host_bytes = (block_bytes if execution == "streamed"
                  and spec.sink == "shards" else 8 * e)
    disk_bytes = 8 * e if spec.sink == "shards" else 0

    return GenPlan(spec=spec, model=spec.model, execution=execution,
                   sink=spec.sink, executor=executor, topology=topo,
                   num_procs=p, lp=lp, num_vertices=n,
                   requested_edges=e, pair_capacity=0, exchange_rounds=0,
                   round_capacity=0, urn_budget=0,
                   device_bytes=device_bytes, host_bytes=host_bytes,
                   disk_bytes=disk_bytes, config=cfg,
                   block_bytes=block_bytes)


def plan(spec: GraphSpec) -> GenPlan:
    """Compile a :class:`GraphSpec` into a validated :class:`GenPlan`.

    Pure resolution — no JAX compilation, no generation. Raises
    ``ValueError`` with an actionable message for every invalid spec:
    unknown model/execution/sink, incomplete scale, faction layouts that
    don't cover P, logical-processor counts that do not factor over the
    device topology, missing shard sinks, and int32 overflows.
    """
    with spans.span("repro.plan", model=str(spec.model)):
        if spec.model not in MODELS:
            raise ValueError(
                f"unknown model {spec.model!r}: one of {MODELS}")
        if spec.sink not in SINKS:
            raise ValueError(f"unknown sink {spec.sink!r}: one of {SINKS}")
        if spec.sink == "shards" and not spec.out_dir:
            raise ValueError("sink='shards' needs out_dir")
        if spec.model == "pba":
            return _plan_pba(spec)
        if spec.model == "pk":
            return _plan_pk(spec)
        return _plan_cfree(spec)


# --- generate -----------------------------------------------------------------

def _edges_from_stream(stream, overlap: bool = True
                       ) -> tuple[EdgeList, GenStats]:
    """Drain a stream's blocks into one in-memory EdgeList + stats.

    Device-sharded streams are drained double-buffered (block i+1's
    device round in flight while block i is gathered), same as the shard
    sink."""
    import jax.numpy as jnp
    srcs, dsts = [], []
    if hasattr(stream, "dispatch_block"):
        def gather(i, handle):
            src, dst = stream.gather_block(handle)
            srcs.append(src)
            dsts.append(dst)

        streaming.drive_rounds(range(stream.num_blocks),
                               stream.dispatch_block, gather,
                               overlap=overlap)
    else:
        for block in stream.iter_blocks():
            srcs.append(block.src)
            dsts.append(block.dst)
    with spans.span("repro.sink.assemble",
                    bytes=sum(a.nbytes for a in srcs + dsts)):
        src = np.concatenate(srcs) if srcs else np.empty(0, np.int32)
        dst = np.concatenate(dsts) if dsts else np.empty(0, np.int32)
        edges = EdgeList(src=jnp.asarray(src), dst=jnp.asarray(dst),
                         num_vertices=stream.num_vertices)
    return edges, stream_lib.stream_stats(stream, int(len(src)))


def _make_stream(pl: GenPlan):
    if pl.model == "pba":
        if pl.executor == "pba_stream_sharded":
            return stream_lib.PBAShardedStream(
                pl.config, pl.table, topology=pl.topology,
                auto_capacity=pl.spec.auto_capacity)
        return stream_lib.PBAStream(pl.config, pl.table,
                                    auto_capacity=pl.spec.auto_capacity)
    if pl.model == "pk":
        return stream_lib.PKStream(pl.seed_graph, pl.config,
                                   slab_edges=pl.spec.slab_edges)
    return cfree_lib.CFreeStream(
        pl.config, slab_edges=pl.spec.slab_edges,
        topology=pl.topology if pl.executor == "cfree_stream_sharded"
        else None)


def generate(plan_or_spec: Union[GenPlan, GraphSpec]) -> GenResult:
    """Execute a plan (or plan a spec and execute it) and return the result.

    Dispatches to the internal executors — bit-identical to calling the
    legacy entry points directly with the plan's resolved arguments (the
    parity suite in tests/test_api.py pins this). Every span the call
    opens carries one new graph id (:mod:`repro.runtime.spans`).
    """
    with spans.graph():
        pl = (plan_or_spec if isinstance(plan_or_spec, GenPlan)
              else plan(plan_or_spec))
        with spans.span("repro.generate", executor=pl.executor):
            return _execute(pl)


def _execute(pl: GenPlan) -> GenResult:
    spec = pl.spec

    if pl.execution == "streamed":
        stream = _make_stream(pl)
        if pl.sink == "shards":
            manifest, stats = stream_lib.stream_to_shards(
                stream, spec.out_dir, overlap=spec.overlap)
            return GenResult(plan=pl, stats=stats, manifest=manifest,
                             out_dir=spec.out_dir)
        edges, stats = _edges_from_stream(stream, overlap=spec.overlap)
        return GenResult(plan=pl, stats=stats, edges=edges)

    if pl.model == "pba":
        edges, stats = pba_lib.generate_pba(pl.config, pl.table,
                                            pl.topology)
    elif pl.model == "pk":
        if pl.execution == "host":
            edges, stats = pk_lib.generate_pk_host(pl.seed_graph, pl.config)
        else:
            edges, stats = pk_lib.generate_pk(pl.seed_graph, pl.config,
                                              topology=pl.topology)
    else:
        if pl.execution == "host":
            edges, stats = cfree_lib.generate_cfree_host(pl.config)
        else:
            edges, stats = cfree_lib.generate_cfree(
                pl.config, topology=pl.topology, num_procs=pl.num_procs)

    result = GenResult(plan=pl, stats=stats, edges=edges)
    if pl.sink == "shards":
        result.manifest = storage_lib.write_shards(
            edges.flat(), spec.out_dir, num_shards=spec.num_shards,
            meta={"spec_digest": spec.digest()})
        result.out_dir = spec.out_dir
    return result


# --- presets ------------------------------------------------------------------

def _preset_paper_1b_5b() -> GraphSpec:
    """The paper's headline run: 1000 ranks, 1B vertices, 5B edges —
    streamed out-of-core (add sink='shards', out_dir=... to land on disk)."""
    return GraphSpec(model="pba", procs=1000, vertices_per_proc=1_000_000,
                     edges_per_vertex=5, exchange_rounds=8, seed=7,
                     execution="streamed")


def _preset_pod_1000rank() -> GraphSpec:
    """The collective-gate pod-scale reference: P=1000 logical ranks over
    whatever devices are present (auto: sharded when P divides)."""
    return GraphSpec(model="pba", procs=1000, vertices_per_proc=40,
                     edges_per_vertex=2, pair_capacity=8, seed=7)


def _preset_paper_smoke() -> GraphSpec:
    """Small end-to-end PBA smoke — the verify.sh front-door leg."""
    return GraphSpec(model="pba", procs=8, vertices_per_proc=2000,
                     edges_per_vertex=4, seed=7)


def _preset_hub_stress() -> GraphSpec:
    """Adversarial hub factions + streamed exchange: zero drops where the
    single-shot exchange clips the tail."""
    return GraphSpec(model="pba", procs=8, vertices_per_proc=300,
                     edges_per_vertex=4, factions="hub", pair_capacity=16,
                     exchange_rounds=4, total_capacity_factor=8, seed=5)


def _preset_pk_smoke() -> GraphSpec:
    """Small PK expansion (star-clique seed, 9^5 edges)."""
    return GraphSpec(model="pk", levels=5, noise=0.05, seed=3)


def _preset_pk_3b() -> GraphSpec:
    """Paper-scale PK: star-clique-5 seed to the 10th power (~3.5B edges),
    streamed slab by slab (add sink='shards', out_dir=...)."""
    return GraphSpec(model="pk", levels=10, seed=3, execution="streamed")


def _preset_rmat_smoke() -> GraphSpec:
    """Small communication-free R-MAT (2^14 vertices, 2^16 edges)."""
    return GraphSpec(model="rmat", cfree_vertices=1 << 14,
                     cfree_edges=1 << 16, seed=7)


def _preset_ba_cfree_1b() -> GraphSpec:
    """Paper-scale communication-free BA: 250M vertices x degree 4 = 1B
    edges, streamed slab by slab (add sink='shards', out_dir=...)."""
    return GraphSpec(model="ba_cfree", cfree_vertices=250_000_000,
                     ba_degree=4, seed=7, execution="streamed")


PRESETS = {
    "paper_1b_5b": _preset_paper_1b_5b,
    "pod_1000rank": _preset_pod_1000rank,
    "paper_smoke": _preset_paper_smoke,
    "hub_stress": _preset_hub_stress,
    "pk_smoke": _preset_pk_smoke,
    "pk_3b": _preset_pk_3b,
    "rmat_smoke": _preset_rmat_smoke,
    "ba_cfree_1b": _preset_ba_cfree_1b,
}


def preset(name: str, **overrides) -> GraphSpec:
    """A named scenario as a one-liner; overrides are applied on top
    (e.g. ``preset('paper_1b_5b', sink='shards', out_dir='/data/g')``)."""
    try:
        spec = PRESETS[name]()
    except KeyError:
        raise ValueError(
            f"unknown preset {name!r}: one of {sorted(PRESETS)}") from None
    return spec.replace(**overrides) if overrides else spec
