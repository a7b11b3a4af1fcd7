"""Parallel Kronecker (PK) generator — closed-form meta-edge expansion.

The paper generates the L-th Kronecker power of a seed graph by expanding a
meta-edge *stack* and recursively splitting processor groups (O(e0*L) memory,
acknowledged load imbalance). We replace both with a closed form (DESIGN.md
§2): edge t of G^{⊗L} is determined by the base-e0 digits of t —

    t = sum_i d_i * e0^(L-1-i),   d_i ∈ [0, e0)
    U(t) = sum_i u0[d_i] * n0^(L-1-i),   V(t) likewise,

so each device independently materializes a *contiguous index range*
[t0, t1) with zero communication and exact static load balance.

TPU adaptation: no int64. The global range start t0 is digit-decomposed on the
host (exact python ints); devices decompose only their local offset
(< 2^31) and perform a mixed-radix carry-add. Vertex ids fit int32
(n0^L <= 2^31 — checked).

Randomization (the paper's "temporarily modify the seed graph"): with
probability ``noise`` per (edge, level), the digit is redrawn uniformly —
counter-based, reproducible. Optional deletion sampling emits -1 slots.
"""
from __future__ import annotations

import dataclasses
import functools
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


@dataclasses.dataclass(frozen=True)
class SeedGraph:
    """The Kronecker seed: e0 edges over n0 vertices (host-side, tiny)."""

    u: np.ndarray  # (e0,) int32
    v: np.ndarray  # (e0,) int32
    num_vertices: int

    @property
    def num_edges(self) -> int:
        return int(self.u.shape[0])

    @staticmethod
    def validate(seed: "SeedGraph") -> None:
        if seed.u.shape != seed.v.shape or seed.u.ndim != 1:
            raise ValueError("seed edge arrays must be 1-D and equal length")
        if seed.num_edges < 2:
            raise ValueError("seed needs >= 2 edges")
        for arr in (seed.u, seed.v):
            if (arr < 0).any() or (arr >= seed.num_vertices).any():
                raise ValueError("seed endpoints out of range")


def star_clique_seed(num_vertices: int = 5) -> SeedGraph:
    """A seed in the spirit of the paper's Fig. 2: hub 0 + self-loops.

    Row/col 0 dense plus the diagonal — gives communities-within-communities
    blocks under Kronecker powering.
    """
    u, v = [], []
    for i in range(num_vertices):
        u.append(0), v.append(i)
        if i:
            u.append(i), v.append(i)
    return SeedGraph(np.array(u, np.int32), np.array(v, np.int32), num_vertices)


def dense_power_seed(num_vertices: int, avg_degree: int, seed: int = 0) -> SeedGraph:
    """Random seed with e0 = n0*avg_degree edges (paper's large-degree seed)."""
    rng = np.random.default_rng(seed)
    e0 = num_vertices * avg_degree
    return SeedGraph(rng.integers(0, num_vertices, e0).astype(np.int32),
                     rng.integers(0, num_vertices, e0).astype(np.int32),
                     num_vertices)


@dataclasses.dataclass(frozen=True)
class PKConfig:
    """levels: Kronecker power L. noise: per-(edge, level) digit-redraw prob.
    delete_prob: per-edge deletion prob (static-shape -1 slots).
    seed: RNG seed for the randomization streams."""

    levels: int
    noise: float = 0.0
    delete_prob: float = 0.0
    seed: int = 0


def pk_sizes(seed: SeedGraph, cfg: PKConfig) -> tuple[int, int]:
    """(num_vertices, num_edges) of the expanded graph, exact python ints."""
    return seed.num_vertices ** cfg.levels, seed.num_edges ** cfg.levels


def _check_int32(seed: SeedGraph, cfg: PKConfig, chunk: int) -> None:
    n, _ = pk_sizes(seed, cfg)
    if n > 2**31 - 1:
        raise ValueError(f"n0^L = {n} exceeds int32 vertex-id space")
    if chunk > 2**31 - 1:
        raise ValueError(f"per-device chunk {chunk} exceeds int32")


def decompose_base(t0: int, base: int, levels: int) -> np.ndarray:
    """Host-side exact digit decomposition of a python int (MSB first)."""
    digits = np.zeros(levels, np.int32)
    for i in range(levels - 1, -1, -1):
        digits[i] = t0 % base
        t0 //= base
    if t0:
        raise ValueError("t0 out of range for levels")
    return digits


def expand_chunk(t_local: jax.Array, base_digits: jax.Array,
                 seed_u: jax.Array, seed_v: jax.Array,
                 n0: int, e0: int, levels: int,
                 cfg: PKConfig, rank) -> tuple[jax.Array, jax.Array]:
    """Pure-jnp expansion of local edge indices (the ref/oracle path).

    t_local: (m,) int32 local offsets; base_digits: (L,) digits of the range
    start. Returns (u, v) int32 global endpoint ids.
    """
    m = t_local.shape[0]
    # Local digits, LSB-first extraction.
    digs = []
    rem = t_local
    for _ in range(levels):
        digs.append(rem % e0)
        rem = rem // e0
    local_digits = jnp.stack(digs[::-1], axis=0)  # (L, m) MSB first

    # Mixed-radix carry add: base_digits + local_digits, LSB -> MSB.
    total = jnp.flip(local_digits, 0) + jnp.flip(base_digits, 0)[:, None]

    def carry_step(carry, row):
        row = row + carry
        new_carry = (row >= e0).astype(jnp.int32)
        return new_carry, row - new_carry * e0

    _, digits_lsb = jax.lax.scan(carry_step, jnp.zeros((m,), jnp.int32), total)
    digits = jnp.flip(digits_lsb, 0)  # (L, m) MSB first

    if cfg.noise > 0.0:
        ckey = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PK_NOISE_COIN, rank)
        dkey = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PK_NOISE_DIGIT, rank)
        flip = jax.random.uniform(ckey, (levels, m)) < cfg.noise
        redraw = (jax.random.bits(dkey, (levels, m), dtype=jnp.uint32)
                  % jnp.uint32(e0)).astype(jnp.int32)
        digits = jnp.where(flip, redraw, digits)

    # Horner accumulation of vertex coordinates, MSB first.
    def horner(acc, d):
        return acc * n0 + d, None

    u_coord, _ = jax.lax.scan(horner, jnp.zeros((m,), jnp.int32), seed_u[digits])
    v_coord, _ = jax.lax.scan(horner, jnp.zeros((m,), jnp.int32), seed_v[digits])

    if cfg.delete_prob > 0.0:
        delkey = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PK_XOR, rank)
        keep = jax.random.uniform(delkey, (m,)) >= cfg.delete_prob
        u_coord = jnp.where(keep, u_coord, -1)
        v_coord = jnp.where(keep, v_coord, -1)
    return u_coord, v_coord


def generate_pk_host(seed: SeedGraph, cfg: PKConfig
                     ) -> tuple[EdgeList, GenStats]:
    """Single-device PK expansion of the full index range."""
    from repro.kernels import ops as kops
    SeedGraph.validate(seed)
    n, e = pk_sizes(seed, cfg)
    _check_int32(seed, cfg, e)
    su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
    base = jnp.zeros((cfg.levels,), jnp.int32)
    t = jnp.arange(e, dtype=jnp.int32)
    u, v = jax.jit(
        functools.partial(kops.pk_expand, n0=seed.num_vertices,
                          e0=seed.num_edges, levels=cfg.levels,
                          noise=cfg.noise, delete_prob=cfg.delete_prob,
                          seed=cfg.seed, rank=0)
    )(t, base, su, sv)
    edges = EdgeList(src=u, dst=v, num_vertices=n)
    emitted = int(jnp.sum(u >= 0))
    return edges, GenStats(requested_edges=e, emitted_edges=emitted,
                           dropped_edges=e - emitted, num_vertices=n)


def generate_pk(seed: SeedGraph, cfg: PKConfig,
                mesh: Optional[Mesh] = None, axis_name: str = "proc",
                topology: Optional[Topology] = None
                ) -> tuple[EdgeList, GenStats]:
    """Distributed PK: contiguous index range per device, zero communication.

    The per-device range start is digit-decomposed host-side; devices do pure
    int32 arithmetic. Embarrassingly parallel, exactly load balanced. The
    topology only partitions the index space (ranks are pod-major linear
    device indices) — there is nothing to exchange hierarchically.
    """
    SeedGraph.validate(seed)
    topology, mesh = topology_lib.resolve(topology, mesh, axis_name)
    num_procs = topology.num_devices
    spec = topology.spec_axes
    n, e = pk_sizes(seed, cfg)
    chunk = -(-e // num_procs)  # ceil
    _check_int32(seed, cfg, chunk)

    # Host-side exact base decomposition per rank: (P, L).
    bases = np.stack([
        decompose_base(min(p * chunk, e), seed.num_edges, cfg.levels)
        for p in range(num_procs)
    ]).astype(np.int32)
    su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)

    def body(base_blk):
        from repro.kernels import ops as kops
        rank = blocking.device_index(topology)
        t = jnp.arange(chunk, dtype=jnp.int32)
        u, v = kops.pk_expand(t, base_blk[0], su, sv, seed.num_vertices,
                              seed.num_edges, cfg.levels, cfg.noise,
                              cfg.delete_prob, cfg.seed, rank=rank)
        if chunk * num_procs > e:
            # mask indices past the global edge count (last device's tail)
            u, v = blocking.mask_tail((u, v), rank, chunk, e)
        return u[None], v[None]

    u, v = jax.jit(
        spmd.shard_map(body, mesh=mesh, in_specs=(P(spec, None),),
                       out_specs=(P(spec, None), P(spec, None)),
                       check_vma=False)
    )(jnp.asarray(bases))

    edges = EdgeList(src=u, dst=v, num_vertices=n)
    emitted = int(jnp.sum(u >= 0))
    return edges, GenStats(requested_edges=e, emitted_edges=emitted,
                           dropped_edges=e - emitted, num_vertices=n)


def _xor_apply(src: np.ndarray, dst: np.ndarray, er_u: np.ndarray,
               er_v: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Exact multiset XOR of an edge list with sampled flip edges.

    XOR is an involution, so multiplicity matters on both sides:
      * a flip edge sampled an even number of times cancels pairwise —
        net no-op; odd multiplicity acts exactly once;
      * an acting flip that matches an existing edge removes *one* copy of
        it (an original with multiplicity > 1 keeps the rest);
      * an acting flip with no match is appended.
    O(E log E) via sorted matching.
    """
    key = src.astype(np.int64) * n + dst.astype(np.int64)
    er_key = er_u.astype(np.int64) * n + er_v.astype(np.int64)
    flip_key, flip_mult = np.unique(er_key, return_counts=True)
    flip_key = flip_key[flip_mult % 2 == 1]  # even multiplicities cancel

    order = np.argsort(key, kind="stable")
    sorted_key = key[order]
    pos = np.searchsorted(sorted_key, flip_key)
    present = (pos < len(key)) & (sorted_key[np.minimum(pos, max(len(key) - 1, 0))]
                                  == flip_key) if len(key) else np.zeros(len(flip_key), bool)
    # flip_key entries are unique, so each present flip deletes one distinct
    # original occurrence (its first in sort order).
    keep_mask = np.ones(len(key), bool)
    keep_mask[order[pos[present]]] = False
    add_key = flip_key[~present]
    add_u = (add_key // n).astype(np.int32)
    add_v = (add_key % n).astype(np.int32)
    new_src = np.concatenate([src[keep_mask], add_u]).astype(np.int32)
    new_dst = np.concatenate([dst[keep_mask], add_v]).astype(np.int32)
    return new_src, new_dst


def xor_randomize(edges: EdgeList, flip_fraction: float = 0.01,
                  seed: int = 0) -> EdgeList:
    """The paper's second PK randomization: XOR the adjacency with a sparse
    Erdős–Rényi graph — edges present in both vanish, ER-only edges appear.

    |E|·flip_fraction ER edges are sampled and XORed with exact multiset
    semantics (see :func:`_xor_apply`): duplicate samples cancel pairwise,
    and a matching original loses exactly one copy.
    """
    import jax.numpy as jnp
    src, dst = edges.to_numpy()
    n = edges.num_vertices
    rng = np.random.default_rng(seed)
    m = max(int(len(src) * flip_fraction), 1)
    er_u = rng.integers(0, n, m).astype(np.int64)
    er_v = rng.integers(0, n, m).astype(np.int64)
    new_src, new_dst = _xor_apply(src, dst, er_u, er_v, n)
    return EdgeList(src=jnp.asarray(new_src), dst=jnp.asarray(new_dst),
                    num_vertices=n)


def dense_kronecker_power(seed: SeedGraph, levels: int) -> np.ndarray:
    """Oracle: dense adjacency of the L-th Kronecker power (tiny graphs only)."""
    a0 = np.zeros((seed.num_vertices, seed.num_vertices), np.int32)
    a0[seed.u, seed.v] += 1
    a = a0.copy()
    for _ in range(levels - 1):
        a = np.kron(a, a0)
    return a
