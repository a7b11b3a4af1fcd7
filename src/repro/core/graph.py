"""Distributed edge-list graph container and conversions.

The generators produce graphs as sharded COO edge lists: ``src``/``dst``
int32 arrays, optionally carrying a validity mask (PBA capacity overflow and
PK noise deletions leave invalid slots rather than compacting, to keep shapes
static). Analysis utilities densify / CSR-ify on demand.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EdgeList:
    """A (possibly sharded) COO edge list with static capacity.

    Attributes:
      src, dst: int32 arrays, same shape. Invalid slots hold -1.
      num_vertices: static python int — global vertex-id space size.
    """

    src: jax.Array
    dst: jax.Array
    num_vertices: int = dataclasses.field(metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return int(np.prod(self.src.shape))

    def valid_mask(self) -> jax.Array:
        return (self.src >= 0) & (self.dst >= 0)

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid_mask())

    def flat(self) -> "EdgeList":
        return EdgeList(self.src.reshape(-1), self.dst.reshape(-1), self.num_vertices)

    def to_numpy(self) -> tuple[np.ndarray, np.ndarray]:
        """Host-side compacted (src, dst) with invalid slots removed."""
        s = np.asarray(self.src).reshape(-1)
        d = np.asarray(self.dst).reshape(-1)
        m = (s >= 0) & (d >= 0)
        return s[m], d[m]


@dataclasses.dataclass
class GenStats:
    """Bookkeeping returned alongside a generated graph.

    exchange_rounds: how many rounds the endpoint exchange actually ran
    (1 for the legacy single-shot exchange and for PK, which has none).
    pair_capacity: the per-(sender, receiver) exchange budget C the run
    used — explicit from the config or the derived latency/memory-aware
    default (0 for generators without an exchange, e.g. PK).
    """

    requested_edges: int
    emitted_edges: int
    dropped_edges: int
    num_vertices: int
    exchange_rounds: int = 1
    pair_capacity: int = 0

    @property
    def drop_fraction(self) -> float:
        return self.dropped_edges / max(self.requested_edges, 1)


def degree_counts(edges: EdgeList, num_vertices: Optional[int] = None,
                  directed: bool = False) -> jax.Array:
    """Per-vertex degree from an edge list (host of analysis pipeline).

    Undirected by default: each edge contributes to both endpoints.
    Invalid slots (negative ids) are ignored via a guarded scatter into an
    extra trash bin.
    """
    n = num_vertices or edges.num_vertices
    s = edges.src.reshape(-1)
    d = edges.dst.reshape(-1)
    valid = (s >= 0) & (d >= 0)
    # Route invalid entries to bin n (trash), then drop it.
    s = jnp.where(valid, s, n)
    d = jnp.where(valid, d, n)
    counts = jnp.zeros((n + 1,), jnp.int32)
    counts = counts.at[s].add(1)
    if not directed:
        counts = counts.at[d].add(1)
    return counts[:n]


def to_csr(src: np.ndarray, dst: np.ndarray, num_vertices: int,
           symmetrize: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Host-side CSR (indptr, indices) for BFS/analysis."""
    if symmetrize:
        s = np.concatenate([src, dst])
        d = np.concatenate([dst, src])
    else:
        s, d = src, dst
    order = np.argsort(s, kind="stable")
    s, d = s[order], d[order]
    indptr = np.zeros(num_vertices + 1, np.int64)
    np.add.at(indptr, s + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, d.astype(np.int64)


def dense_adjacency(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                    symmetrize: bool = True) -> np.ndarray:
    """Small-graph dense 0/1 adjacency (tests, Fig.5 community plots)."""
    a = np.zeros((num_vertices, num_vertices), np.int32)
    a[src, dst] = 1
    if symmetrize:
        a[dst, src] = 1
    return a
