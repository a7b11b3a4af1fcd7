"""Communication-free generators: ba_cfree / rmat / er.

Sanders & Schulz (arXiv 1602.07106) show Barabási–Albert edges can be
*recomputed* instead of communicated: with a counter-based hash, edge
``t``'s attachment draw is a pure function of ``(seed, t)``, so the
Batagelj–Brandes dependency chain (an odd draw points at a *previous*
edge's endpoint) is resolved by re-evaluating the predecessor's draw
rather than asking the rank that owns it. Funke et al. (arXiv 1710.07565)
generalize the recipe to fully communication-free distributed generation;
ER and R-MAT need no chain at all — every edge is direct.

The executor family here makes that the contract: per-edge work is a pure
function of ``(seed, edge_index)``, so the host, sharded, and streamed
paths all just slice the global index range ``[0, E)`` — per logical rank
(the blocked ``P = lp·D`` layout) or per slab — with **zero exchange
rounds** and zero collectives. Any partition emits bit-identical edges.

RNG design (FC001, see :data:`repro.core.spec.DETERMINISM_ROOTS`): one
clean-lineage ``jax.random.bits`` draw per (seed, stream) produces the
model's *stream words* — identical on every device, derived from the seed
literal alone — and every per-edge value is then a pure uint32 mixing
hash of ``(words, t, ctr)``. The hash (a murmur-style finalizer, applied
twice with the words folded in) is partition-independent by construction
and cheap enough to re-evaluate ``CHAIN_BOUND`` times per edge inside a
Pallas kernel. Modulo draws carry bias < bound/2^32, irrelevant for graph
statistics (same note as :func:`repro.core.rng.uniform_slots`).

ba_cfree chain resolution: Batagelj–Brandes writes ``M[2t] = t // d`` and
``M[2t+1] = M[r]`` with ``r`` uniform on ``[0, 2t+1)``. Recomputed: an
even ``r`` terminates at source ``(r/2) // d``; an odd ``r`` recurses
into edge ``(r-1)/2``'s draw. Each hop strictly decreases the index and
is odd with probability ~1/2, so a fixed ``CHAIN_BOUND``-deep masked loop
leaves a residual odd ``r`` with probability ~2^-CHAIN_BOUND per edge; in
that (never observed) case the edge attaches to edge ``(r-1)/2``'s source
instead of its destination — a principled degradation, not an error.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import rng as rng_lib
from repro.core.graph import EdgeList, GenStats
from repro.runtime import blocking, spmd
from repro.runtime import topology as topology_lib
from repro.runtime.topology import Topology

CFREE_MODELS = ("ba_cfree", "rmat", "er")

#: Fixed recomputation depth of the ba_cfree dependency chain. Each hop is
#: odd w.p. ~1/2, so the residual probability is ~2^-64 per edge.
CHAIN_BOUND = 64

_GOLDEN = 0x9E3779B9
_MIX1 = 0x7FEB352D
_MIX2 = 0x846CA68B
_M32 = 0xFFFFFFFF


@dataclasses.dataclass(frozen=True)
class CFreeConfig:
    """model: one of :data:`CFREE_MODELS`. vertices: global vertex count n
    (rmat requires a power of two). edges: global edge count E for rmat/er
    (ba_cfree derives E = n * ba_degree). ba_degree: edges issued per
    arriving BA vertex. rmat_a/b/c: R-MAT quadrant probabilities (d is the
    remainder). seed: RNG seed — with the config, the graph's identity."""

    model: str
    vertices: int
    edges: int = 0
    ba_degree: int = 2
    rmat_a: float = 0.57
    rmat_b: float = 0.19
    rmat_c: float = 0.19
    seed: int = 0

    @staticmethod
    def validate(cfg: "CFreeConfig") -> None:
        if cfg.model not in CFREE_MODELS:
            raise ValueError(
                f"model {cfg.model!r} not in {CFREE_MODELS}")
        if not 1 <= cfg.vertices <= 2**31 - 1:
            raise ValueError(
                f"vertices {cfg.vertices} out of int32 vertex-id space")
        if cfg.model == "ba_cfree":
            if cfg.ba_degree < 1:
                raise ValueError(f"ba_degree {cfg.ba_degree} must be >= 1")
            if cfg.vertices * cfg.ba_degree > 2**31 - 1:
                raise ValueError(
                    f"ba_cfree edge count {cfg.vertices * cfg.ba_degree} "
                    "exceeds int32 edge-index space")
        else:
            if not 1 <= cfg.edges <= 2**31 - 1:
                raise ValueError(
                    f"edges {cfg.edges} out of int32 edge-index space")
        if cfg.model == "rmat":
            if cfg.vertices & (cfg.vertices - 1):
                raise ValueError(
                    f"rmat vertices {cfg.vertices} must be a power of two")
            a, b, c = cfg.rmat_a, cfg.rmat_b, cfg.rmat_c
            if min(a, b, c) < 0.0 or a + b + c > 1.0:
                raise ValueError(
                    f"rmat quadrant probabilities a={a} b={b} c={c} must "
                    "be non-negative with a+b+c <= 1")


def cfree_sizes(cfg: CFreeConfig) -> tuple[int, int]:
    """(num_vertices, num_edges) of the generated graph, exact ints."""
    if cfg.model == "ba_cfree":
        return cfg.vertices, cfg.vertices * cfg.ba_degree
    return cfg.vertices, cfg.edges


def edge_slices(e: int, p: int) -> list:
    """Per-rank [start, stop) global edge-index slices.

    Rank r owns ``[r*chunk, min((r+1)*chunk, e))`` with chunk = ceil(e/P)
    — the slices exactly partition ``[0, e)`` (no gaps, no overlaps) for
    any (e, P); trailing ranks may own empty slices.
    """
    chunk = -(-e // p) if e else 0
    return [(min(r * chunk, e), min((r + 1) * chunk, e)) for r in range(p)]


# --- counter-based hash -------------------------------------------------------

def _mix32(x: jax.Array) -> jax.Array:
    x = (x ^ (x >> 16)) * jnp.uint32(_MIX1)
    x = (x ^ (x >> 15)) * jnp.uint32(_MIX2)
    return x ^ (x >> 16)


def cfree_hash(words: jax.Array, t: jax.Array, ctr: int) -> jax.Array:
    """Pure uint32 draw for edge counter ``t`` under draw counter ``ctr``.

    ``words`` is a (>=2,) uint32 array of stream words (:func:`cfree_words`);
    only ``words[0]``/``words[1]`` are folded in, so callers select a word
    pair by slicing. ``ctr`` is a static python int namespacing the draws
    an edge makes (R-MAT level, chain draw, ...).
    """
    x = t.astype(jnp.uint32) ^ words[0]
    x = _mix32(x + jnp.uint32((_GOLDEN * (ctr + 1)) & _M32))
    return _mix32(x ^ words[1])


def hash_int(w0: int, w1: int, t: int, ctr: int) -> int:
    """Exact python-int mirror of :func:`cfree_hash` (serial oracles)."""
    def mix(x: int) -> int:
        x = ((x ^ (x >> 16)) * _MIX1) & _M32
        x = ((x ^ (x >> 15)) * _MIX2) & _M32
        return x ^ (x >> 16)

    x = (t ^ w0) & _M32
    x = mix((x + _GOLDEN * (ctr + 1)) & _M32)
    return mix(x ^ w1)


def cfree_words(cfg: CFreeConfig) -> jax.Array:
    """(4,) uint32 stream words for the model's per-edge hash.

    One clean-lineage draw per (seed, stream) with the pristine rank-0
    key: the lineage is exactly seed literal -> fold_in -> bits (FC001),
    the words are identical on every device, and everything downstream is
    a pure function of (words, t) — so no partitioning of the edge-index
    range can change any edge. er uses two streams (word pairs [0:2] for
    u, [2:4] for v); ba_cfree/rmat draw all four from their one stream.
    """
    if cfg.model == "er":
        ku = rng_lib.device_key(cfg.seed, rng_lib.STREAM_CFREE_ER_U, 0)
        kv = rng_lib.device_key(cfg.seed, rng_lib.STREAM_CFREE_ER_V, 0)
        return jnp.concatenate([jax.random.bits(ku, (2,), jnp.uint32),
                                jax.random.bits(kv, (2,), jnp.uint32)])
    stream = (rng_lib.STREAM_CFREE_BA if cfg.model == "ba_cfree"
              else rng_lib.STREAM_CFREE_RMAT)
    return jax.random.bits(rng_lib.device_key(cfg.seed, stream, 0), (4,),
                           jnp.uint32)


# --- per-model endpoint functions (pure jnp — the ref/oracle path) -----------

def ba_dst(words: jax.Array, t: jax.Array, degree: int) -> jax.Array:
    """Destination of BA edge ``t`` by chain recomputation (module doc)."""
    def draw(j):
        bound = (j.astype(jnp.uint32) << 1) + jnp.uint32(1)  # 2j + 1
        return cfree_hash(words, j, 0) % bound

    r = draw(t)
    for _ in range(CHAIN_BOUND):
        odd = (r & jnp.uint32(1)) == jnp.uint32(1)
        r = jnp.where(odd, draw((r >> 1).astype(jnp.int32)), r)
    return (r >> 1).astype(jnp.int32) // degree


def rmat_thresholds(cfg: CFreeConfig) -> tuple[int, int, int]:
    """Cumulative quadrant probabilities as uint32 comparison thresholds.

    a+b+c == 1 clamps the last threshold to 2^32-1 (bias 2^-32, ignored).
    """
    a, b, c = cfg.rmat_a, cfg.rmat_b, cfg.rmat_c
    return tuple(min(int(s * 2**32), _M32) for s in (a, a + b, a + b + c))


def rmat_endpoints(words: jax.Array, t: jax.Array, levels: int,
                   ta: int, tb: int, tc: int) -> tuple[jax.Array, jax.Array]:
    """R-MAT quadrant descent: one hash per level, integer thresholds."""
    u = jnp.zeros(t.shape, jnp.int32)
    v = jnp.zeros(t.shape, jnp.int32)
    for level in range(levels):
        x = cfree_hash(words, t, level)
        q = ((x >= jnp.uint32(ta)).astype(jnp.int32)
             + (x >= jnp.uint32(tb)).astype(jnp.int32)
             + (x >= jnp.uint32(tc)).astype(jnp.int32))
        u = (u << 1) + (q >> 1)
        v = (v << 1) + (q & 1)
    return u, v


def er_endpoints(words: jax.Array, t: jax.Array, n: int
                 ) -> tuple[jax.Array, jax.Array]:
    """G(n, m) edge ``t``: independent uniform endpoints, one word pair
    each."""
    u = (cfree_hash(words[0:2], t, 0) % jnp.uint32(n)).astype(jnp.int32)
    v = (cfree_hash(words[2:4], t, 0) % jnp.uint32(n)).astype(jnp.int32)
    return u, v


def cfree_endpoints(cfg: CFreeConfig, t: jax.Array, words: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """(u, v) int32 endpoints of global edge indices ``t`` — pure in
    (words, t); every executor path funnels through here, and through
    ``kops.cfree_expand``: the Mosaic kernel on TPU, the jnp functions
    above elsewhere."""
    from repro.kernels import ops as kops
    n, _ = cfree_sizes(cfg)
    return kops.cfree_expand(t, words, model=cfg.model, n=n,
                             ba_degree=cfg.ba_degree,
                             thresholds=rmat_thresholds(cfg))


# --- serial oracle ------------------------------------------------------------

def serial_ba_cfree_reference(cfg: CFreeConfig) -> tuple[np.ndarray,
                                                         np.ndarray]:
    """Batagelj–Brandes serial M-array construction driven by the same
    hash — the gold oracle the vectorized chain must match bit-for-bit
    (small n only: python loop)."""
    n, e = cfree_sizes(cfg)
    w = [int(x) for x in np.asarray(jax.device_get(cfree_words(cfg)))]
    m_arr = np.zeros(2 * e, np.int32)
    u = np.zeros(e, np.int32)
    v = np.zeros(e, np.int32)
    for t in range(e):
        m_arr[2 * t] = t // cfg.ba_degree
        r = hash_int(w[0], w[1], t, 0) % (2 * t + 1)
        m_arr[2 * t + 1] = m_arr[r]
        u[t] = m_arr[2 * t]
        v[t] = m_arr[2 * t + 1]
    return u, v


# --- executors ----------------------------------------------------------------

def _cfree_stats(e: int, n: int) -> GenStats:
    # exchange_rounds=0 is the zero-exchange contract signal (PK reports 1
    # for its single local pass; cfree never exchanges at all).
    return GenStats(requested_edges=e, emitted_edges=e, dropped_edges=0,
                    num_vertices=n, exchange_rounds=0, pair_capacity=0)


def generate_cfree_host(cfg: CFreeConfig) -> tuple[EdgeList, GenStats]:
    """Single-device expansion of the full index range."""
    CFreeConfig.validate(cfg)
    n, e = cfree_sizes(cfg)

    @jax.jit
    def expand(t):
        return cfree_endpoints(cfg, t, cfree_words(cfg))

    u, v = expand(jnp.arange(e, dtype=jnp.int32))
    return EdgeList(src=u, dst=v, num_vertices=n), _cfree_stats(e, n)


def sharded_expand_fn(cfg: CFreeConfig, num_procs: int, topo: Topology):
    """(jitted_fn, example_args) for the sharded zero-collective program.

    The one front-door cfree program: ``P = lp·D`` logical ranks each
    expand their contiguous edge-index slice (:func:`edge_slices`) with no
    transpose and no collective of any kind. Shared by
    :func:`generate_cfree`, the compile-only bench harness
    (``repro.launch.bench.compile_sharded_cfree``), and the flowcheck /
    auditor registrations, so every layer inspects the same program. The
    input is a per-device token that only pins the program to the mesh.
    """
    n, e = cfree_sizes(cfg)
    d = topo.num_devices
    lp = topo.lp(num_procs)
    chunk = -(-e // num_procs)
    if chunk > 2**31 - 1:
        raise ValueError(f"per-rank chunk {chunk} exceeds int32")
    mesh = topo.build_mesh()
    spec = topo.spec_axes

    def body(tok):
        del tok  # mesh token only
        words = cfree_words(cfg)
        ranks = blocking.logical_ranks(lp, topo)

        def one(rank):
            t = rank * chunk + jnp.arange(chunk, dtype=jnp.int32)
            u, v = cfree_endpoints(cfg, t, words)
            if chunk * num_procs > e:
                u, v = blocking.mask_tail((u, v), rank, chunk, e)
            return u, v

        u, v = blocking.map_logical(one, ranks)
        return u[None], v[None]

    fn = jax.jit(spmd.shard_map(
        body, mesh=mesh, in_specs=(P(spec),),
        out_specs=(P(spec, None, None), P(spec, None, None)),
        check_vma=False))
    return fn, (jnp.zeros((d,), jnp.int32),)


def generate_cfree(cfg: CFreeConfig, mesh: Optional[Mesh] = None,
                   axis_name: str = "proc", num_procs: Optional[int] = None,
                   topology: Optional[Topology] = None
                   ) -> tuple[EdgeList, GenStats]:
    """Distributed communication-free generation over any topology.

    ``num_procs`` (default D) sets the logical rank count P = lp·D; the
    topology only names the devices — the blocked layout needs no
    transpose because nothing is ever sent. Output order is global
    edge-index order (rank-major flatten), so any (topology, P) choice is
    bit-identical to the host path after tail-mask compaction.
    """
    CFreeConfig.validate(cfg)
    topology, mesh = topology_lib.resolve(topology, mesh, axis_name)
    p = num_procs or topology.num_devices
    n, e = cfree_sizes(cfg)
    fn, args = sharded_expand_fn(cfg, p, topology)
    u, v = fn(*args)
    return EdgeList(src=u, dst=v, num_vertices=n), _cfree_stats(e, n)


class CFreeStream:
    """Out-of-core communication-free stream: block i covers global edge
    indices [i*slab, (i+1)*slab).

    Because every edge is a pure function of (seed, t), any slab size
    yields the same edge sequence (slab-boundary independence) and a
    restart regenerates exactly the missing blocks. With a multi-device
    ``topology``, each slab is expanded device-sharded (contiguous
    per-device spans, still zero collectives); the host slices the slab
    back to its true length, so out-of-range tail indices are computed
    harmlessly and discarded.
    """

    def __init__(self, cfg: CFreeConfig, slab_edges: int,
                 topology: Optional[Topology] = None):
        CFreeConfig.validate(cfg)
        n, e = cfree_sizes(cfg)
        if not 1 <= slab_edges <= 2**31 - 1:
            raise ValueError(f"slab_edges {slab_edges} out of range")
        self.cfg = cfg
        self.num_vertices = n
        self.requested_edges = e
        self.slab_edges = int(slab_edges)
        self.num_blocks = -(-e // self.slab_edges)
        self.exchange_rounds = 0
        self._sharded = (topology is not None and not topology.is_host
                         and topology.num_devices > 1)
        if self._sharded:
            self._d = topology.num_devices
            per_dev = -(-self.slab_edges // self._d)
            mesh = topology.build_mesh()
            spec = topology.spec_axes

            def body(t0_blk):
                dev = blocking.device_index(topology)
                words = cfree_words(cfg)
                t = (t0_blk[0] + dev * per_dev
                     + jnp.arange(per_dev, dtype=jnp.int32))
                u, v = cfree_endpoints(cfg, t, words)
                return u[None], v[None]

            self._expand = jax.jit(spmd.shard_map(
                body, mesh=mesh, in_specs=(P(spec),),
                out_specs=(P(spec, None), P(spec, None)),
                check_vma=False))
        else:
            t_rel = jnp.arange(self.slab_edges, dtype=jnp.int32)

            @jax.jit
            def expand(t0):
                return cfree_endpoints(cfg, t_rel + t0, cfree_words(cfg))

            self._expand = expand

    def meta(self) -> dict:
        """Generator identity for the shard manifest's resume check."""
        from repro.core.spec import spec_digest
        return {"generator": "cfree", "model": self.cfg.model,
                "seed": self.cfg.seed, "spec_digest": spec_digest(self.cfg)}

    def block(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        if not 0 <= i < self.num_blocks:
            raise ValueError(f"block {i} out of range "
                             f"[0, {self.num_blocks})")
        t0 = i * self.slab_edges
        m = min(self.slab_edges, self.requested_edges - t0)
        if self._sharded:
            u, v = self._expand(jnp.full((self._d,), t0, jnp.int32))
        else:
            u, v = self._expand(jnp.int32(t0))
        return (np.asarray(u).reshape(-1)[:m],
                np.asarray(v).reshape(-1)[:m])

    def iter_blocks(self):
        from repro.core.stream import EdgeBlock
        for i in range(self.num_blocks):
            src, dst = self.block(i)
            yield EdgeBlock(i, src, dst)
