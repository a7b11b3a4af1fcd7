"""Plain reference of the R-MAT generator: Graph 500's Kronecker descent.

Edge ``t`` of an R-MAT graph on ``2**scale`` vertices descends ``scale``
levels of the adjacency matrix. At each level one 32-bit draw picks a
quadrant: top-left below ``A``, top-right below ``A+B``, bottom-left
below ``A+B+C``, bottom-right otherwise; the row bit extends the source,
the column bit the destination. The probabilities become integer
thresholds ``floor(p * 2**32)``, computed from the configuration's
decimal probabilities in double precision.

The draw is the generator's documented counter-based hash of
``(stream words, t, level)``, and the stream words are four
``jax.random.bits`` of the key ``fold_in(fold_in(key(seed), 10), 0)``
(stream 10 is R-MAT's). This module spells both out from those
definitions and imports nothing of the program.

``control=True`` computes the thresholds from float32 probabilities, the
next precision below the configuration's: a graph that differs from the
reference in a few edges per thousand million.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.edges import block_fingerprint, combine

#: Edge order is part of R-MAT's definition: edge t is a function of t.
ORDERED = True

_STREAM = 10
_GOLDEN = 0x9E3779B9
_MIX1 = 0x7FEB352D
_MIX2 = 0x846CA68B
_M32 = 0xFFFFFFFF
#: Edges per reference block: bounds the reference's device memory.
BLOCK = 1 << 26


def stream_words(seed: int) -> jax.Array:
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed),
                                                _STREAM), 0)
    return jax.random.bits(key, (4,), jnp.uint32)


def _mix(x):
    x = (x ^ (x >> 16)) * jnp.uint32(_MIX1)
    x = (x ^ (x >> 15)) * jnp.uint32(_MIX2)
    return x ^ (x >> 16)


def _draw(words, t, level: int):
    x = t ^ words[0]
    x = _mix(x + jnp.uint32((_GOLDEN * (level + 1)) & _M32))
    return _mix(x ^ words[1])


def thresholds(a: float, b: float, c: float, control: bool = False):
    if control:
        a, b, c = (np.float32(x) for x in (a, b, c))
        cum = (a, a + b, a + b + c)
    else:
        cum = (a, a + b, a + b + c)
    return tuple(min(int(float(s) * 2**32), _M32) for s in cum)


@functools.partial(jax.jit, static_argnames=("block", "levels", "edges",
                                             "cuts"))
def _block(words, t0, *, block: int, levels: int, edges: int, cuts):
    t = t0.astype(jnp.uint32) + jnp.arange(block, dtype=jnp.uint32)
    ta, tb, tc = (jnp.uint32(x) for x in cuts)
    src = jnp.zeros((block,), jnp.uint32)
    dst = jnp.zeros((block,), jnp.uint32)
    for level in range(levels):
        x = _draw(words, t, level)
        row = x >= tb
        col = ((x >= ta) & (x < tb)) | (x >= tc)
        src = (src << 1) | row.astype(jnp.uint32)
        dst = (dst << 1) | col.astype(jnp.uint32)
    live = t < jnp.uint32(edges)
    src = jnp.where(live, src.astype(jnp.int32), -1)
    dst = jnp.where(live, dst.astype(jnp.int32), -1)
    return block_fingerprint(src, dst, t0, ordered=True)


def reference(config: dict, seed: int, *, control: bool = False):
    """Fingerprint of the R-MAT graph ``config`` describes, for ``seed``."""
    spec = config["spec"]
    n, e = int(spec["cfree_vertices"]), int(spec["cfree_edges"])
    levels = n.bit_length() - 1
    if n != 1 << levels:
        raise ValueError(f"R-MAT needs a power-of-two vertex count, got {n}")
    cuts = thresholds(spec["rmat_a"], spec["rmat_b"], spec["rmat_c"],
                      control)
    words = stream_words(seed)
    block = min(BLOCK, e)
    parts = [_block(words, jnp.int32(t0), block=block, levels=levels,
                    edges=e, cuts=cuts)
             for t0 in range(0, e, block)]
    return combine(jax.device_get(parts))
