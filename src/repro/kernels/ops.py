"""Dispatch wrappers for the generators' hot spots.

The histogram, PK expansion and cfree expansion dispatch by mode
(kernels/dispatch.py, per-call overridable):
  * TPU backend        -> compiled Pallas kernels.
  * elsewhere          -> pure-jnp reference (XLA:CPU) — interpret-mode Pallas
                          is for *correctness tests*, not speed, so the
                          library only routes through it when forced via
                          REPRO_PALLAS=interpret (used by the test suite).

PBA's gathers (pointer doubling, grant, band) and its band compaction run
the XLA formulation in every mode: the ``edge_resolve`` and
``band_compact`` kernels do not lower for TPU. The doubling loop
(``core.pba.resolve_pointers``) calls ``gather`` on pointers that encode
their terminals; ``resolve_step`` is the plain pass the kernel mirrors.

The kernel functions themselves default ``interpret=None`` and resolve the
mode through the same probe, so direct kernel calls and these wrappers can
never disagree about execution mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import rng as rng_lib
from repro.kernels import ref
from repro.kernels.dispatch import mode as _mode
from repro.kernels.cfree_expand import cfree_expand_pallas
from repro.kernels.pk_expand import pk_expand_pallas
from repro.kernels.histogram import histogram_pallas


def pk_expand(t_local, base_digits, seed_u, seed_v, n0: int, e0: int,
              levels: int, noise: float, delete_prob: float, seed: int,
              rank) -> tuple[jax.Array, jax.Array]:
    """Kernel-backed Kronecker expansion with the same contract as
    core.pk.expand_chunk (noise/deletion included)."""
    m = t_local.shape[0]
    flip = redraw = None
    if noise > 0.0:
        ckey = rng_lib.device_key(seed, rng_lib.STREAM_PK_NOISE_COIN, rank)
        dkey = rng_lib.device_key(seed, rng_lib.STREAM_PK_NOISE_DIGIT, rank)
        flip = jax.random.uniform(ckey, (levels, m)) < noise
        redraw = (jax.random.bits(dkey, (levels, m), dtype=jnp.uint32)
                  % jnp.uint32(e0)).astype(jnp.int32)
    mode = _mode()
    if mode == "off":
        u, v = ref.pk_expand_ref(t_local, base_digits, seed_u, seed_v,
                                 n0, e0, levels, flip, redraw)
    else:
        u, v = pk_expand_pallas(t_local, base_digits, seed_u, seed_v,
                                n0, e0, levels, flip, redraw)
    if delete_prob > 0.0:
        delkey = rng_lib.device_key(seed, rng_lib.STREAM_PK_XOR, rank)
        keep = jax.random.uniform(delkey, (m,)) >= delete_prob
        u = jnp.where(keep, u, -1)
        v = jnp.where(keep, v, -1)
    return u, v


def cfree_expand(t, words, *, model: str, n: int, ba_degree: int,
                 thresholds) -> tuple[jax.Array, jax.Array]:
    """Kernel-backed communication-free endpoint expansion with the same
    contract as core.cfree.cfree_endpoints (pure in (words, t))."""
    if _mode() == "off":
        return ref.cfree_expand_ref(t, words, model=model, n=n,
                                    ba_degree=ba_degree,
                                    thresholds=thresholds)
    return cfree_expand_pallas(t, words, model=model, n=n,
                               ba_degree=ba_degree, thresholds=thresholds)


def histogram(values: jax.Array, num_bins: int) -> jax.Array:
    mode = _mode()
    if mode == "off":
        return ref.histogram_ref(values, num_bins)
    return histogram_pallas(values, num_bins)


def resolve_step(ptr: jax.Array) -> jax.Array:
    """One ptr[ptr] pointer-doubling pass, as an XLA gather on every
    backend (Mosaic cannot gather from a multi-MiB VMEM source)."""
    return ref.resolve_step_ref(ptr)


def gather(src: jax.Array, idx: jax.Array) -> jax.Array:
    """values = src[..., clip(idx)] along the last axis (ref.gather_ref
    contract), as an XLA gather on every backend.

    Accepts a 1-D shared source with any-rank indices, or batched rows:
    src (r, m) with idx (r, n).
    """
    if src.ndim == 1 and idx.ndim > 1:
        return ref.gather_ref(src, idx.reshape(-1)).reshape(idx.shape)
    return ref.gather_ref(src, idx)


def band_compact(u: jax.Array, v: jax.Array, band: jax.Array,
                 block_cap: int) -> tuple[jax.Array, jax.Array]:
    """Predicated compaction (ref.band_compact_ref contract): per row,
    band-selected (u, v) move to the front in index order, -1 elsewhere,
    truncated to block_cap. The XLA sort runs on every backend: it costs
    O(e log e) where the one-hot Pallas kernel costs O(e * cap)."""
    return ref.band_compact_ref(u, v, band, block_cap)
