"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel module provides a pl.pallas_call with explicit BlockSpec VMEM
tiling; ops.py holds the jitted dispatch wrappers; ref.py the pure-jnp
oracles that tests sweep against.

This package also hosts the **kernel registry** pallascheck introspects
(``python -m repro.analysis kernels``): every registered entry names a
kernel entry point, a swept size grid, and its ref.py oracle, so the
static grid/BlockSpec race and VMEM checks (repro.analysis.kernelcheck)
cover every pl.pallas_call the library can issue without executing on a
TPU. The module stays import-light — registry builders import JAX (and
the kernel modules) lazily, on first use.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One concrete (kernel entry point, example inputs, oracle) triple.

    ``fn`` takes only array arguments (static shape parameters are closed
    over) plus a pass-through ``interpret=`` keyword; ``ref`` shares the
    array signature. ``execute`` marks sizes small enough for the
    interpret-vs-ref differential sanitizer (static checks always run).
    """

    fn: Callable
    args: tuple
    ref: Optional[Callable]
    label: str
    execute: bool = True


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One registered kernel: ``build(**size)`` -> KernelCase per swept size.

    ``sizes`` is a zero-arg callable (sizes may depend on derived bounds
    like edge_resolve's MAX_VMEM_ENTRIES); ``meta`` contributes static
    facts — derived caps, fallback policy — to pallascheck's inventory.
    """

    name: str
    build: Callable
    sizes: Callable
    meta: Optional[Callable] = None


# --- edge_resolve ------------------------------------------------------------

def _edge_resolve_case(m: int, chunked: bool = False,
                       slab: int | None = None,
                       dst_block: int | None = None) -> KernelCase:
    import numpy as np
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.edge_resolve import (gather_chunked_pallas,
                                            resolve_step_pallas)

    rng = np.random.default_rng(1000 + m)
    ptr = jnp.asarray(rng.integers(0, m, m), jnp.int32)
    if chunked:
        # chunked regime: one doubling pass as the src == idx gather; tiny
        # explicit slabs make the multi-slab path executable in interpret
        # mode, the autotuned case stays structural.
        return KernelCase(
            fn=lambda p, interpret=None: gather_chunked_pallas(
                p, p, slab=slab, dst_block=dst_block, interpret=interpret),
            args=(ptr,), ref=ref.resolve_step_ref,
            label=f"m{m}_chunked" + (f"_s{slab}" if slab else ""),
            execute=m <= 8192)
    return KernelCase(
        fn=lambda p, interpret=None: resolve_step_pallas(p,
                                                         interpret=interpret),
        args=(ptr,), ref=ref.resolve_step_ref, label=f"m{m}",
        execute=m <= 8192)


def _edge_resolve_sizes() -> tuple:
    from repro.kernels.edge_resolve import BLOCK, MAX_VMEM_ENTRIES
    return ({"m": 1}, {"m": 127}, {"m": 4097}, {"m": MAX_VMEM_ENTRIES},
            # past the resident bound: autotuned slabs (structural) plus an
            # executable multi-slab case with forced tiny tiles
            {"m": MAX_VMEM_ENTRIES + 1, "chunked": True},
            {"m": 4097, "chunked": True, "slab": BLOCK, "dst_block": BLOCK})


def _edge_resolve_meta() -> dict:
    from repro.kernels.edge_resolve import (BLOCK, MAX_CHUNKED_ENTRIES,
                                            MAX_SLABS, MAX_VMEM_ENTRIES,
                                            slab_entries)
    return {
        "block": BLOCK,
        "max_vmem_entries": MAX_VMEM_ENTRIES,
        "slab_entries": slab_entries(),
        "max_slabs": MAX_SLABS,
        "max_chunked_entries": MAX_CHUNKED_ENTRIES,
        "routing": (
            "not on the hot path: ops.resolve_step/ops.gather run the XLA "
            "gather on every backend, since this kernel does not lower for "
            "TPU v5e"),
    }


# --- band_compact ------------------------------------------------------------

def _band_compact_case(rows: int, e: int, cap: int) -> KernelCase:
    import numpy as np
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.band_compact import band_compact_pallas

    rng = np.random.default_rng(rows * 131 + e * 17 + cap)
    u = jnp.asarray(rng.integers(0, 2**30, (rows, e)), jnp.int32)
    v = jnp.asarray(rng.integers(0, 2**30, (rows, e)), jnp.int32)
    band = jnp.asarray(rng.random((rows, e)) < 0.35)
    return KernelCase(
        fn=lambda u_, v_, b_, interpret=None: band_compact_pallas(
            u_, v_, b_, cap, interpret=interpret),
        args=(u, v, band),
        ref=lambda u_, v_, b_: ref.band_compact_ref(u_, v_, b_, cap),
        label=f"r{rows}_e{e}_c{cap}", execute=rows * e <= 65536)


def _band_compact_sizes() -> tuple:
    return ({"rows": 1, "e": 1, "cap": 1},
            {"rows": 2, "e": 1500, "cap": 600},
            {"rows": 4, "e": 8192, "cap": 2048},
            {"rows": 1, "e": 262144, "cap": 65536})


def _band_compact_meta() -> dict:
    from repro.kernels.band_compact import IN_BLOCK, OUT_BLOCK
    return {
        "in_block": IN_BLOCK,
        "out_block": OUT_BLOCK,
        "note": ("fused predicated prefix-sum compaction; not on the hot "
                 "path: ops.band_compact runs the XLA sort on every "
                 "backend, since this kernel does not lower for TPU v5e; "
                 "tile shapes autotuned per size (dispatch.autotune)"),
    }


# --- histogram ---------------------------------------------------------------

def _histogram_case(m: int, nbins: int) -> KernelCase:
    import numpy as np
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.histogram import histogram_pallas

    rng = np.random.default_rng(m * 31 + nbins)
    v = jnp.asarray(rng.integers(0, nbins, m), jnp.int32)
    return KernelCase(
        fn=lambda v_, interpret=None: histogram_pallas(v_, nbins,
                                                       interpret=interpret),
        args=(v,), ref=lambda v_: ref.histogram_ref(v_, nbins),
        label=f"m{m}_b{nbins}", execute=m <= 8192)


def _histogram_sizes() -> tuple:
    return ({"m": 1, "nbins": 1}, {"m": 2048, "nbins": 512},
            {"m": 5003, "nbins": 700}, {"m": 65536, "nbins": 1537})


# --- pk_expand ---------------------------------------------------------------

def _pk_expand_case(m: int, n0: int, levels: int, noise: bool) -> KernelCase:
    import numpy as np
    import jax.numpy as jnp

    from repro.core.pk import decompose_base, star_clique_seed
    from repro.kernels import ref
    from repro.kernels.pk_expand import pk_expand_pallas

    seed = star_clique_seed(n0)
    e0 = seed.num_edges
    rng = np.random.default_rng(m * 13 + n0 * 7 + levels)
    hi = min(e0 ** levels, 2**31 - 1)
    t = jnp.asarray(rng.integers(0, max(hi - m, 1), m), jnp.int32)
    base = jnp.asarray(decompose_base(int(rng.integers(0, max(hi // 2, 1))),
                                      e0, levels))
    su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
    label = f"m{m}_n{n0}_L{levels}"
    if noise:
        flip = jnp.asarray(rng.random((levels, m)) < 0.3)
        redraw = jnp.asarray(rng.integers(0, e0, (levels, m)), jnp.int32)
        return KernelCase(
            fn=lambda t_, b_, u_, v_, f_, r_, interpret=None:
                pk_expand_pallas(t_, b_, u_, v_, n0, e0, levels, f_, r_,
                                 interpret=interpret),
            args=(t, base, su, sv, flip, redraw),
            ref=lambda t_, b_, u_, v_, f_, r_:
                ref.pk_expand_ref(t_, b_, u_, v_, n0, e0, levels, f_, r_),
            label=label + "_noise")
    return KernelCase(
        fn=lambda t_, b_, u_, v_, interpret=None:
            pk_expand_pallas(t_, b_, u_, v_, n0, e0, levels,
                             interpret=interpret),
        args=(t, base, su, sv),
        ref=lambda t_, b_, u_, v_:
            ref.pk_expand_ref(t_, b_, u_, v_, n0, e0, levels),
        label=label)


def _pk_expand_sizes() -> tuple:
    return ({"m": 100, "n0": 3, "levels": 2, "noise": False},
            {"m": 3000, "n0": 5, "levels": 4, "noise": False},
            {"m": 2048, "n0": 6, "levels": 3, "noise": True})


# --- cfree_expand ------------------------------------------------------------

def _cfree_expand_case(m: int, model: str, n: int,
                       degree: int = 2) -> KernelCase:
    import numpy as np
    import jax.numpy as jnp

    from repro.core.cfree import CFreeConfig, cfree_words, rmat_thresholds
    from repro.kernels import ref
    from repro.kernels.cfree_expand import cfree_expand_pallas

    e = n * degree if model == "ba_cfree" else max(m, 1)
    cfg = CFreeConfig(model=model, vertices=n, edges=e, ba_degree=degree,
                      seed=m * 7 + n)
    words = cfree_words(cfg)
    th = rmat_thresholds(cfg)
    rng = np.random.default_rng(m * 29 + n)
    t = jnp.asarray(rng.integers(0, e, m), jnp.int32)
    return KernelCase(
        fn=lambda t_, w_, interpret=None: cfree_expand_pallas(
            t_, w_, model=model, n=n, ba_degree=degree, thresholds=th,
            interpret=interpret),
        args=(t, words),
        ref=lambda t_, w_: ref.cfree_expand_ref(
            t_, w_, model=model, n=n, ba_degree=degree, thresholds=th),
        label=f"{model}_m{m}_n{n}", execute=m <= 8192)


def _cfree_expand_sizes() -> tuple:
    return ({"m": 100, "model": "ba_cfree", "n": 64, "degree": 3},
            {"m": 3000, "model": "ba_cfree", "n": 4096},
            {"m": 2048, "model": "rmat", "n": 1024},
            {"m": 1500, "model": "er", "n": 777})


def _cfree_expand_meta() -> dict:
    from repro.core.cfree import CHAIN_BOUND
    return {
        "chain_bound": CHAIN_BOUND,
        "note": ("pure elementwise uint32 mixing — no gathers, no tables, "
                 "no exchange; the ba_cfree dependency chain is a "
                 "chain_bound-unrolled masked loop (residual odd draw "
                 "probability ~2^-chain_bound per edge, see core/cfree.py)"),
    }


def registry() -> tuple[KernelEntry, ...]:
    """Every Pallas kernel entry point the library can issue, with the
    size sweep pallascheck certifies it over."""
    return (
        KernelEntry("edge_resolve", _edge_resolve_case, _edge_resolve_sizes,
                    _edge_resolve_meta),
        KernelEntry("band_compact", _band_compact_case, _band_compact_sizes,
                    _band_compact_meta),
        KernelEntry("histogram", _histogram_case, _histogram_sizes),
        KernelEntry("pk_expand", _pk_expand_case, _pk_expand_sizes),
        KernelEntry("cfree_expand", _cfree_expand_case, _cfree_expand_sizes,
                    _cfree_expand_meta),
    )
