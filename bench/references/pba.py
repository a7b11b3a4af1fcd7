"""Plain reference of the PBA generator (arXiv:1003.3684, two-phase
preferential attachment), written from the algorithm's definition.

P processors each own V vertices with k out-edges, so E = V*k local
edges. Every draw comes from ``jax.random`` under the key
``fold_in(fold_in(key(seed), stream), rank)``, with streams 0 (phase-1
urn), 1 (inter-faction coin), 2 (inter-faction processor) and 3
(phase-2 urn).

Phase 1, per processor p: slot j < s_p holds the j-th member of p's
factions; a later slot is, with probability ``interfaction_prob`` (a
float32 uniform below it), a uniformly drawn processor, and otherwise a
copy of slot ``r_j``, uniform on [0, j). Following copies back to the
slot they start from gives slot j's processor tag ``a[p, j]``: the
processor that provides the destination of p's local edge j.

Phase 2, per provider q: q's demand is how many tags name it. Its urn
holds E + B slots, B the demand of the busiest provider rounded up to a
power of two: slot i < E is the source slot of q's local edge i, owned by
q's vertex i // k; a later slot copies slot ``r_i``, uniform on [0, i).
Requester p's request of rank m (the m-th of p's tags that name q)
receives the vertex of urn slot ``E + (requests of processors before p)
+ m``.

Edge (p, j) joins p's vertex j // k to that vertex. Nothing is dropped:
B covers every provider's demand. The output order of the program is a
scheduling detail, so the comparison is of the edge multiset; the harness
holds the order to the run's first graph.

``control=True`` draws the inter-faction coin in bfloat16, the next
precision below the float32 the generator states.

The reference runs on the host's CPU device: XLA's CPU gathers are far
faster than the chip's for these random pointer chases, and the CPU is a
second witness beside the chip the program runs on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.edges import block_fingerprint, to_host

#: PBA's output order is a scheduling detail: compare edge multisets.
ORDERED = False

_URN, _COIN, _PROC, _POOL = 0, 1, 2, 3


def faction_table(num_procs: int, num_factions: int, min_size: int,
                  max_size: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The random faction layout: ``num_factions`` factions of uniformly
    drawn sizes and members; a processor in none joins one drawn at
    random. Row p lists the members of every faction p is in, in faction
    order. Returns (rows padded with -1, row lengths)."""
    rng = np.random.default_rng(seed)
    factions = []
    for _ in range(num_factions):
        size = int(rng.integers(min_size, max_size + 1))
        factions.append(np.sort(rng.choice(num_procs, size=size,
                                           replace=False)))
    member_of = [[] for _ in range(num_procs)]
    for fi, members in enumerate(factions):
        for m in members:
            member_of[int(m)].append(fi)
    for p in range(num_procs):
        if not member_of[p]:
            fi = int(rng.integers(0, len(factions)))
            factions[fi] = np.sort(np.append(factions[fi], p))
            member_of[p].append(fi)
    rows = [np.concatenate([factions[fi] for fi in member_of[p]])
            for p in range(num_procs)]
    s = np.array([len(r) for r in rows], np.int32)
    table = np.full((num_procs, int(s.max())), -1, np.int32)
    for p, row in enumerate(rows):
        table[p, :len(row)] = row
    return table, s


def _keys(key, stream: int, num: int):
    k = jax.random.fold_in(key, stream)
    return jax.vmap(lambda r: jax.random.fold_in(k, r))(
        jnp.arange(num, dtype=jnp.int32))


def _origin(parent, terminal):
    """For every slot, the terminal slot its chain of copies starts from.
    Terminal slots are their own parents, so replacing every pointer by
    its target's pointer halves every chain until all stand on terminal
    slots."""
    def cond(cur):
        return ~jnp.all(jnp.take_along_axis(terminal, cur, axis=-1))

    def body(cur):
        return jnp.take_along_axis(cur, cur, axis=-1)

    return jax.lax.while_loop(cond, body, parent)


def _uniform_below(keys, n: int):
    """r_j uniform on [0, max(j, 1)) for j < n, one row per key."""
    bits = jax.vmap(lambda k: jax.random.bits(k, (n,), jnp.uint32))(keys)
    bound = jnp.maximum(jnp.arange(n, dtype=jnp.uint32), jnp.uint32(1))
    return (bits % bound).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("procs", "edges", "prob",
                                             "control"))
def _phase1(key, table, s, *, procs: int, edges: int, prob: float,
            control: bool):
    j = jnp.arange(edges, dtype=jnp.int32)
    copy_of = _uniform_below(_keys(key, _URN, procs), edges)
    dtype = jnp.bfloat16 if control else jnp.float32
    coin = jax.vmap(lambda k: jax.random.uniform(k, (edges,), dtype))(
        _keys(key, _COIN, procs)) < prob
    drawn = jax.vmap(lambda k: jax.random.bits(k, (edges,), jnp.uint32))(
        _keys(key, _PROC, procs)) % jnp.uint32(procs)
    seeded = j[None, :] < s[:, None]
    inter = coin & ~seeded
    terminal = seeded | inter
    member = jnp.take_along_axis(
        table, jnp.minimum(j, table.shape[1] - 1)[None, :]
        .repeat(procs, 0), axis=1)
    value = jnp.where(seeded, member,
                      jnp.where(inter, drawn.astype(jnp.int32), -1))
    parent = jnp.where(terminal, j[None, :], copy_of)
    tags = jnp.take_along_axis(value, _origin(parent, terminal), axis=1)
    counts = jax.vmap(lambda row: jnp.zeros((procs,), jnp.int32)
                      .at[row].add(1))(tags)
    return tags, counts


def _rank_among_equals(tags):
    """m[p, j] = #{j' < j : tags[p, j'] == tags[p, j]}."""
    procs, edges = tags.shape
    j = jnp.arange(edges, dtype=jnp.int32)
    order = jnp.argsort(tags * edges + j[None, :], axis=1)
    ordered = jnp.take_along_axis(tags, order, axis=1)
    first = jax.vmap(lambda row: jnp.searchsorted(row, row, side="left")
                     )(ordered).astype(jnp.int32)
    rank = j[None, :] - first
    return jnp.zeros_like(tags).at[
        jnp.arange(procs)[:, None], order].set(rank)


@functools.partial(jax.jit, static_argnames=("procs", "edges", "verts",
                                             "degree", "budget"))
def _phase2(key, tags, counts, *, procs: int, edges: int, verts: int,
            degree: int, budget: int):
    size = edges + budget
    i = jnp.arange(size, dtype=jnp.int32)
    copy_of = _uniform_below(_keys(key, _POOL, procs), size)
    terminal = jnp.broadcast_to(i < edges, (procs, size))
    parent = jnp.where(terminal, i[None, :], copy_of)
    slot = _origin(parent, terminal)
    urn = (jnp.arange(procs, dtype=jnp.int32)[:, None] * verts
           + slot // degree)
    before = jnp.cumsum(counts, axis=0) - counts   # [requester, provider]
    rank = _rank_among_equals(tags)
    offset = jnp.take_along_axis(before, tags, axis=1)
    dst = urn[tags, edges + offset + rank]
    j = jnp.arange(edges, dtype=jnp.int32)
    src = (jnp.arange(procs, dtype=jnp.int32)[:, None] * verts
           + (j // degree)[None, :])
    return block_fingerprint(src, dst, 0, ordered=False)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _sizes(config: dict):
    spec = config["spec"]
    procs = int(spec["procs"])
    verts = int(spec["vertices_per_proc"])
    degree = int(spec["edges_per_vertex"])
    return procs, verts, degree, verts * degree


def _tags(config: dict, seed: int, control: bool):
    procs, _, _, edges = _sizes(config)
    f = config["reference_params"]["factions"]
    table, s = faction_table(procs, f["num_factions"], f["min_size"],
                             f["max_size"], f["seed"])
    key = jax.random.key(seed)
    tags, counts = _phase1(key, jnp.asarray(table), jnp.asarray(s),
                           procs=procs, edges=edges,
                           prob=float(config["spec"]["interfaction_prob"]),
                           control=control)
    return key, tags, counts


def reference(config: dict, seed: int, *, control: bool = False):
    """Fingerprint of the PBA graph ``config`` describes, for ``seed``."""
    procs, verts, degree, edges = _sizes(config)
    with jax.default_device(jax.devices("cpu")[0]):
        key, tags, counts = _tags(config, seed, control)
        demand = int(np.asarray(counts).sum(axis=0).max())
        return to_host(_phase2(key, tags, counts, procs=procs, edges=edges,
                               verts=verts, degree=degree,
                               budget=_next_pow2(demand)))
