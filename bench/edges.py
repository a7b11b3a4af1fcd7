"""Fingerprints of an edge list, the unit the correctness check compares.

A generated graph is too large to keep every edge of every graph of a
window, so each graph is reduced on the device, as it is produced, to
three numbers: the count of valid edges and two independent 32-bit sums of
a per-edge hash. Two edge lists with equal fingerprints differ, with
probability about 2**-64, only if they are equal.

``ordered=True`` folds each edge's position into its hash, for generators
whose edge ``t`` is defined by its index (R-MAT). ``ordered=False`` makes
the fingerprint a function of the edge multiset alone, for generators whose
output order is a scheduling detail (PBA's streamed rounds). Slots with a
negative endpoint are padding and do not count.

The hash is murmur3's 32-bit finalizer, with constants of its own: nothing
here is shared with the generators under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_C1 = 0x85EBCA6B
_C2 = 0xC2B2AE35
#: Per-lane seeds of the two independent sums.
_LANES = (0x243F6A88, 0x13198A2E)
_POS = 0x3707344A


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_C1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_C2)
    return x ^ (x >> 16)


def _lane_sum(u, v, t, valid, lane: int):
    h = _fmix(u + jnp.uint32(lane))
    h = _fmix(h ^ v)
    if t is not None:
        h = _fmix(h + _fmix(t ^ jnp.uint32(_POS ^ lane)))
    return jnp.sum(jnp.where(valid, h, jnp.uint32(0)), dtype=jnp.uint32)


def block_fingerprint(u, v, t0, ordered: bool):
    """(count, sum_a, sum_b) of one block of edges whose first position is
    ``t0``; ``u`` and ``v`` are int32 of any shape, flattened in order."""
    u = u.reshape(-1)
    v = v.reshape(-1)
    valid = (u >= 0) & (v >= 0)
    uu = u.astype(jnp.uint32)
    vv = v.astype(jnp.uint32)
    t = None
    if ordered:
        t = (jnp.arange(u.shape[0], dtype=jnp.uint32)
             + jnp.asarray(t0, jnp.uint32))
    count = jnp.sum(valid, dtype=jnp.int32)
    return (count,) + tuple(_lane_sum(uu, vv, t, valid, lane)
                            for lane in _LANES)


#: Edges hashed per step of :func:`fingerprint`: bounds its temporaries.
BLOCK = 1 << 24


@functools.partial(jax.jit, static_argnames="ordered")
def fingerprint(u, v, ordered: bool):
    """Device fingerprint of a whole edge list, block by block so that it
    needs little memory beside the edges; returns without waiting."""
    u = u.reshape(-1)
    v = v.reshape(-1)
    n = u.shape[0]
    block = min(n, BLOCK)
    if block == 0:
        return jnp.int32(0), jnp.uint32(0), jnp.uint32(0)

    def step(i, acc):
        start = i * block
        at = jnp.minimum(start, n - block)    # the last block may overlap
        ub = jax.lax.dynamic_slice(u, (at,), (block,))
        vb = jax.lax.dynamic_slice(v, (at,), (block,))
        fresh = at + jnp.arange(block, dtype=jnp.int32) >= start
        part = block_fingerprint(jnp.where(fresh, ub, -1), vb, at, ordered)
        return tuple(a + p for a, p in zip(acc, part))

    zero = (jnp.int32(0), jnp.uint32(0), jnp.uint32(0))
    return jax.lax.fori_loop(0, -(-n // block), step, zero)


def combine(parts) -> tuple[int, int, int]:
    """Fold block fingerprints (host values) into one: counts add, sums add
    modulo 2**32."""
    count, a, b = 0, 0, 0
    for c, x, y in parts:
        count += int(c)
        a = (a + int(x)) & 0xFFFFFFFF
        b = (b + int(y)) & 0xFFFFFFFF
    return count, a, b


def to_host(fp) -> tuple[int, int, int]:
    return tuple(int(np.asarray(x)) for x in fp)
