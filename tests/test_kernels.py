"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.histogram import histogram_pallas
from repro.kernels.edge_resolve import resolve_step_pallas
from repro.kernels.pk_expand import pk_expand_pallas
from repro.core.pk import star_clique_seed, dense_power_seed, decompose_base


@pytest.mark.parametrize("m", [1, 127, 128, 1000, 2048, 5003])
@pytest.mark.parametrize("nbins", [1, 7, 256, 512, 700, 1537])
def test_histogram_sweep(m, nbins):
    rng = np.random.default_rng(m * 31 + nbins)
    v = jnp.asarray(rng.integers(0, nbins, m), jnp.int32)
    got = histogram_pallas(v, nbins, interpret=True)
    want = ref.histogram_ref(v, nbins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got.sum()) == m


def test_histogram_out_of_range_ignored():
    v = jnp.asarray([0, 5, 99, 100, 200, -1], jnp.int32)
    got = histogram_pallas(v, 100, interpret=True)
    assert int(got.sum()) == 3  # 0, 5, 99


@pytest.mark.parametrize("m", [2, 64, 1024, 4097])
def test_resolve_sweep(m):
    rng = np.random.default_rng(m)
    # valid pointer arrays point downward (or anywhere — kernel is a pure gather)
    ptr = jnp.asarray(rng.integers(0, m, m), jnp.int32)
    got = resolve_step_pallas(ptr, interpret=True)
    want = ref.resolve_step_ref(ptr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_resolve_rejects_oversize():
    from repro.kernels.edge_resolve import MAX_VMEM_ENTRIES
    with pytest.raises(ValueError):
        resolve_step_pallas(jnp.zeros(MAX_VMEM_ENTRIES + 1, jnp.int32))


@pytest.mark.parametrize("n0,levels", [(3, 2), (5, 4), (4, 6)])
@pytest.mark.parametrize("m", [1, 100, 1024, 3000])
def test_pk_expand_sweep(n0, levels, m):
    seed = star_clique_seed(n0)
    e0 = seed.num_edges
    rng = np.random.default_rng(m + n0)
    hi = min(e0**levels, 2**31 - 1)
    t = jnp.asarray(rng.integers(0, max(hi - m, 1), m), jnp.int32)
    base = jnp.asarray(decompose_base(int(rng.integers(0, hi // 2)), e0, levels))
    su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
    got_u, got_v = pk_expand_pallas(t, base, su, sv, n0, e0, levels,
                                    interpret=True)
    want_u, want_v = ref.pk_expand_ref(t, base, su, sv, n0, e0, levels)
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(want_u))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_pk_expand_noise_parity():
    seed = dense_power_seed(6, 4, seed=0)
    e0, n0, L, m = seed.num_edges, 6, 3, 2000
    t = jnp.arange(m, dtype=jnp.int32)
    base = jnp.zeros((L,), jnp.int32)
    su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
    rng = np.random.default_rng(0)
    flip = jnp.asarray(rng.random((L, m)) < 0.3)
    redraw = jnp.asarray(rng.integers(0, e0, (L, m)), jnp.int32)
    got = pk_expand_pallas(t, base, su, sv, n0, e0, L, flip, redraw,
                           interpret=True)
    want = ref.pk_expand_ref(t, base, su, sv, n0, e0, L, flip, redraw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("m,n", [(1, 1), (64, 200), (1000, 1000),
                                 (4097, 130), (2048, 4097)])
def test_gather_sweep(m, n):
    from repro.kernels.edge_resolve import gather_pallas

    rng = np.random.default_rng(m * 7 + n)
    src = jnp.asarray(rng.integers(0, 2**30, m), jnp.int32)
    # include out-of-range indices: the contract clips (matches jnp reads)
    idx = jnp.asarray(rng.integers(-3, m + 3, n), jnp.int32)
    got = gather_pallas(src, idx, interpret=True)
    want = ref.gather_ref(src, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,n", [(1, 1), (1023, 777), (1024, 1024),
                                 (1025, 100), (4097, 2050), (5000, 5000)])
def test_chunked_gather_sweep(m, n):
    """Multi-slab path with forced tiny tiles: below / at / above one slab
    and at non-multiples of BLOCK. src == idx is one resolve pass."""
    from repro.kernels.edge_resolve import BLOCK, gather_chunked_pallas

    rng = np.random.default_rng(m * 13 + n)
    src = jnp.asarray(rng.integers(0, 2**30, m), jnp.int32)
    idx = jnp.asarray(rng.integers(-2, m + 2, n), jnp.int32)
    got = gather_chunked_pallas(src, idx, slab=BLOCK, dst_block=BLOCK,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.gather_ref(src, idx)))


def test_chunked_resolve_hypothesis_differential():
    """Property-based boundary sweep vs the pointer-doubling oracle, sizes
    straddling the (forced, tiny) slab bound and non-multiples of BLOCK."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.kernels.edge_resolve import BLOCK, gather_chunked_pallas

    @hyp.settings(max_examples=12, deadline=None)
    @hyp.given(st.integers(min_value=1, max_value=3 * BLOCK + 5),
               st.integers(min_value=0, max_value=2**31 - 1))
    def check(m, seed):
        rng = np.random.default_rng(seed)
        ptr = jnp.asarray(rng.integers(0, m, m), jnp.int32)
        got = gather_chunked_pallas(ptr, ptr, slab=BLOCK, dst_block=BLOCK,
                                    interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.resolve_step_ref(ptr)))

    check()


@pytest.mark.parametrize("rows,e,cap", [(1, 1, 1), (2, 1500, 600),
                                        (3, 100, 100), (1, 2049, 1025)])
def test_band_compact_sweep(rows, e, cap):
    from repro.kernels.band_compact import band_compact_pallas

    rng = np.random.default_rng(rows * 101 + e + cap)
    u = jnp.asarray(rng.integers(-1, 2**30, (rows, e)), jnp.int32)
    v = jnp.asarray(rng.integers(-1, 2**30, (rows, e)), jnp.int32)
    band = jnp.asarray(rng.random((rows, e)) < 0.4)
    got_u, got_v = band_compact_pallas(u, v, band, cap, interpret=True)
    want_u, want_v = ref.band_compact_ref(u, v, band, cap)
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(want_u))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_band_compact_overflow_truncates():
    """More band entries than block_cap: the tail drops, exactly like the
    argsort oracle's [:block_cap]."""
    from repro.kernels.band_compact import band_compact_pallas

    e, cap = 64, 7
    u = jnp.arange(e, dtype=jnp.int32)[None]
    v = (1000 + jnp.arange(e, dtype=jnp.int32))[None]
    band = jnp.ones((1, e), bool)
    got_u, got_v = band_compact_pallas(u, v, band, cap, interpret=True)
    want_u, want_v = ref.band_compact_ref(u, v, band, cap)
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(want_u))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    assert got_u.shape == (1, cap)


def test_resolve_boundary_regimes_subprocess():
    """ops.resolve_step is the XLA gather below, at and above the
    resident and chunked kernel bounds, in kernel (interpret) mode: it
    matches the oracle and traces no pallas_call.
    REPRO_VMEM_BUDGET shrinks the kernel bounds (read at import in the
    subprocess) so the sizes straddle them cheaply."""
    from helpers import run_with_devices
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.kernels import ops, ref
        from repro.kernels.edge_resolve import (BLOCK, MAX_CHUNKED_ENTRIES,
                                                MAX_VMEM_ENTRIES)
        assert MAX_VMEM_ENTRIES == 12 * BLOCK, MAX_VMEM_ENTRIES
        for m in (MAX_VMEM_ENTRIES - 1, MAX_VMEM_ENTRIES,
                  MAX_VMEM_ENTRIES + 1, MAX_VMEM_ENTRIES + 7777,
                  MAX_CHUNKED_ENTRIES + 1):
            ptr = jnp.asarray(
                np.random.default_rng(m).integers(0, m, m), jnp.int32)
            got = ops.resolve_step(ptr)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref.resolve_step_ref(ptr)))
            jaxpr = str(jax.make_jaxpr(ops.resolve_step)(ptr))
            assert "pallas_call" not in jaxpr, m
        print("regimes-ok")
    """
    out = run_with_devices(code, 1, {"REPRO_PALLAS": "interpret",
                                     "REPRO_VMEM_BUDGET": "65536"})
    assert out.strip() == "regimes-ok"


def test_ops_dispatch_interpret_equals_off():
    """ops.* must agree between forced-interpret and jnp fallback modes."""
    from helpers import run_with_devices
    code = """
        import os, numpy as np, jax.numpy as jnp
        from repro.kernels import ops
        v = jnp.asarray(np.random.default_rng(0).integers(0, 99, 4096), jnp.int32)
        print(int(ops.histogram(v, 99).sum()))
    """
    out_interp = run_with_devices(code, 1, {"REPRO_PALLAS": "interpret"})
    out_off = run_with_devices(code, 1, {"REPRO_PALLAS": "off"})
    assert out_interp == out_off == "4096\n"


def test_ref_oracle_against_core_expand_chunk():
    """ref.pk_expand_ref must match core.pk.expand_chunk (two impls, one math)."""
    from repro.core.pk import expand_chunk, PKConfig
    seed = star_clique_seed(5)
    cfg = PKConfig(levels=4, noise=0.0)
    t = jnp.arange(500, dtype=jnp.int32)
    base = jnp.asarray(decompose_base(777, seed.num_edges, 4))
    su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
    u1, v1 = expand_chunk(t, base, su, sv, seed.num_vertices, seed.num_edges,
                          4, cfg, 0)
    u2, v2 = ref.pk_expand_ref(t, base, su, sv, seed.num_vertices,
                               seed.num_edges, 4)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
