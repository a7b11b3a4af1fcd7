"""Pallas TPU kernels: dynamic gather for PBA urn resolution and grants.

The primitive is values = src[clip(idx)] — one pointer-doubling pass
(ptr'[j] = ptr[ptr[j]]) is the special case src == idx, and the round
program's grant/consume lookups are the general case. Two regimes:

* **Resident** (:func:`gather_pallas` / :func:`resolve_step_pallas`): the
  source stays whole in VMEM (un-blocked spec) while destinations are
  gridded; the gather is jnp.take, which Mosaic lowers to a dynamic
  gather. Valid up to ``MAX_VMEM_ENTRIES`` (~2M int32), where the resident
  source plus the double-buffered idx/out blocks exactly saturate
  ``repro.kernels.dispatch.vmem_budget_bytes``.

* **Hierarchically chunked** (:func:`gather_chunked_pallas`): past the
  resident bound, a second grid dimension tiles the source into
  ``slab_entries()``-sized VMEM slabs (slab-major, destinations fastest,
  so each slab is loaded once). Every destination block emits a *partial*
  per slab — the value where the clipped index lands in the slab, else 0 —
  and XLA sums the (num_slabs, n) partials. Each clipped index hits
  exactly one slab, so the sum is the exact gather (no floating point,
  no scatter, every output block written exactly once — race-free under
  pallascheck's revisit rules). Valid up to ``MAX_CHUNKED_ENTRIES``
  (= ``MAX_SLABS`` slabs, ~67M entries). ``ops.resolve_step`` and
  ``ops.gather`` run the XLA formulation at every size, not this kernel.

Slab/destination-block shapes come from the analytic autotuner
(``dispatch.autotune``) per (backend, padded size): the KC004 working set
(double-buffered slab + idx + out blocks) is the hard feasibility bound
and the HLO-traffic model below scores the survivors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import (autotune, default_interpret,
                                    vmem_budget_bytes)

BLOCK = 8 * 128


def max_resident_entries(backend: str = "tpu") -> int:
    """Largest int32 entry count whose working set fits the VMEM budget.

    Working set = 4 bytes x m_pad resident source + two double-buffered
    (1, BLOCK) int32 blocks (destination indices in, gathered values out);
    floored to a whole number of BLOCKs since the call pads to BLOCK.
    """
    budget = vmem_budget_bytes(backend)
    overhead = 2 * 2 * BLOCK * 4  # double-buffered in + out blocks
    return max((budget - overhead) // 4 // BLOCK * BLOCK, BLOCK)


MAX_VMEM_ENTRIES = max_resident_entries()  # ~2M entries: 8 MiB resident int32

#: Policy cap on the chunked-gather source: past MAX_SLABS slabs the
#: slab-sweep traffic (num_slabs x destinations) stops winning over the
#: XLA gather, so ops.py falls back (counted, per size bucket).
MAX_SLABS = 64


def slab_entries(backend: str = "tpu", dst_block: int = BLOCK) -> int:
    """Largest per-slab entry count for the chunked gather.

    All three operands are gridded (the slab itself is double-buffered,
    unlike the resident kernel), so KC004 reads
    2 x (4*slab + 4*dst_block + 4*dst_block) <= budget.
    """
    budget = vmem_budget_bytes(backend)
    slab = (budget // 2 - 2 * 4 * dst_block) // 4
    return max(slab // BLOCK * BLOCK, BLOCK)


MAX_CHUNKED_ENTRIES = slab_entries() * MAX_SLABS


def _gather_kernel(src_ref, idx_ref, out_ref):
    idx = idx_ref[...]                    # (1, BLOCK) destination indices
    src = src_ref[...].reshape(-1)        # full resident source
    out_ref[...] = jnp.take(src, idx, axis=0, mode="clip")


def resolve_step_pallas(ptr: jax.Array,
                        interpret: bool | None = None) -> jax.Array:
    """One ptr[ptr] pass. ptr: (m,) int32 with 0 <= ptr[j] < m."""
    interpret = default_interpret(interpret)
    m = ptr.shape[0]
    if m > MAX_VMEM_ENTRIES:
        raise ValueError(f"resolve_step kernel supports m <= {MAX_VMEM_ENTRIES}")
    m_pad = -(-m // BLOCK) * BLOCK
    p = jnp.pad(ptr, (0, m_pad - m)).reshape(1, m_pad)
    out = pl.pallas_call(
        _gather_kernel,
        grid=(m_pad // BLOCK,),
        in_specs=[
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),   # resident source
            pl.BlockSpec((1, BLOCK), lambda i: (0, i)),   # destination block
        ],
        out_specs=pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m_pad), jnp.int32),
        interpret=interpret,
    )(p, p)
    return out.reshape(-1)[:m]


def gather_pallas(src: jax.Array, idx: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """out[k] = src[clip(idx[k], 0, m-1)] with a VMEM-resident source.

    The clip happens in XLA *before* the kernel: the padded source tail is
    zeros, so clipping against m_pad inside the kernel would leak padding
    for out-of-range indices instead of honoring the ref.gather_ref
    contract.
    """
    interpret = default_interpret(interpret)
    m, n = src.shape[0], idx.shape[0]
    if m > MAX_VMEM_ENTRIES:
        raise ValueError(f"gather kernel supports m <= {MAX_VMEM_ENTRIES}")
    m_pad = -(-m // BLOCK) * BLOCK
    n_pad = -(-n // BLOCK) * BLOCK
    s = jnp.pad(src, (0, m_pad - m)).reshape(1, m_pad)
    ix = jnp.pad(jnp.clip(idx, 0, m - 1), (0, n_pad - n)).reshape(1, n_pad)
    out = pl.pallas_call(
        _gather_kernel,
        grid=(n_pad // BLOCK,),
        in_specs=[
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),   # resident source
            pl.BlockSpec((1, BLOCK), lambda i: (0, i)),   # destination block
        ],
        out_specs=pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(s, ix)
    return out.reshape(-1)[:n]


def _gather_slab_kernel(src_ref, idx_ref, out_ref, *, slab: int):
    s = pl.program_id(0)
    lo = s * slab
    idx = idx_ref[...]                    # (1, dst_block), pre-clipped
    src = src_ref[...].reshape(-1)        # (slab,) source slice
    local = idx - lo
    hit = (local >= 0) & (local < slab)
    vals = jnp.take(src, jnp.where(hit, local, 0), axis=0, mode="clip")
    out_ref[...] = jnp.where(hit, vals, 0)


def chunked_traffic_bytes(m: int, n: int, slab: int, dst_block: int) -> float:
    """Analytic HBM bytes of one chunked gather at the given tiling: source
    once (slab revisits are consecutive), idx + partials per slab sweep,
    plus the XLA partial-sum read and final write. The autotuner's cost
    term and the round-block benchmark's kernel-traffic accounting."""
    m_pad = -(-m // slab) * slab
    n_pad = -(-n // dst_block) * dst_block
    num_slabs = m_pad // slab
    return 4.0 * (m_pad + 3 * num_slabs * n_pad + n_pad)


def gather_traffic_bytes(m: int, n: int) -> float:
    """Analytic HBM bytes of one resident gather (or resolve pass, n=m)."""
    m_pad = -(-m // BLOCK) * BLOCK
    n_pad = -(-n // BLOCK) * BLOCK
    return 4.0 * (m_pad + 2 * n_pad)


@functools.lru_cache(maxsize=None)
def _chunk_plan(backend: str, m_pad: int, n_pad: int) -> tuple[int, int]:
    """Autotuned (slab, dst_block) for a chunked gather of padded size."""
    cands = []
    for dst in (BLOCK, 2 * BLOCK, 4 * BLOCK):
        cap = slab_entries(backend, dst)
        for slab in sorted({cap, max(cap // 2 // BLOCK * BLOCK, BLOCK)}):
            cands.append({"slab": slab, "dst_block": dst})

    def vmem(c: dict) -> int:
        return 2 * (4 * c["slab"] + 2 * 4 * c["dst_block"])

    def cost(c: dict) -> tuple[float, float, float]:
        num_slabs = -(-m_pad // c["slab"])
        steps = num_slabs * (-(-n_pad // c["dst_block"]))
        # compare/select/gather work ~ 3 ops per (slab, destination) pair
        flops = 3.0 * num_slabs * n_pad
        return flops, chunked_traffic_bytes(m_pad, n_pad, c["slab"],
                                            c["dst_block"]), float(steps)

    c = autotune("edge_resolve.chunked", cands, vmem, cost, backend)
    return c["slab"], c["dst_block"]


def gather_chunked_pallas(src: jax.Array, idx: jax.Array,
                          slab: int | None = None,
                          dst_block: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """out[k] = src[clip(idx[k], 0, m-1)] for sources past MAX_VMEM_ENTRIES.

    Slab-major grid: each source slab loads once and sweeps all destination
    blocks, emitting a (num_slabs, n_pad) partial that XLA sums — exact,
    because a clipped index lands in exactly one slab. Tiling defaults to
    the autotuned plan; explicit slab/dst_block are test hooks (the
    boundary differential forces tiny slabs so multi-slab execution is
    exercised in-process).
    """
    interpret = default_interpret(interpret)
    m, n = src.shape[0], idx.shape[0]
    if slab is None or dst_block is None:
        t_slab, t_dst = _chunk_plan("tpu", -(-m // BLOCK) * BLOCK,
                                    -(-n // BLOCK) * BLOCK)
        slab = t_slab if slab is None else slab
        dst_block = t_dst if dst_block is None else dst_block
    m_pad = -(-m // slab) * slab
    n_pad = -(-n // dst_block) * dst_block
    num_slabs = m_pad // slab
    s = jnp.pad(src, (0, m_pad - m)).reshape(1, m_pad)
    ix = jnp.pad(jnp.clip(idx, 0, m - 1),
                 (0, n_pad - n)).reshape(1, n_pad)
    part = pl.pallas_call(
        functools.partial(_gather_slab_kernel, slab=slab),
        grid=(num_slabs, n_pad // dst_block),
        in_specs=[
            pl.BlockSpec((1, slab), lambda s_, i: (0, s_)),      # source slab
            pl.BlockSpec((1, dst_block), lambda s_, i: (0, i)),  # dest block
        ],
        out_specs=pl.BlockSpec((1, dst_block), lambda s_, i: (s_, i)),
        out_shape=jax.ShapeDtypeStruct((num_slabs, n_pad), jnp.int32),
        interpret=interpret,
    )(s, ix)
    return part.sum(axis=0, dtype=jnp.int32)[:n]
