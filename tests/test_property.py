"""Hypothesis property tests on system invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (FactionSpec, PBAConfig, PKConfig, degree_counts,
                        generate_pba, generate_pk_host, make_factions,
                        star_clique_seed, dense_power_seed, pk_sizes)
from repro.core import storage
from repro.core.pba import occurrence_rank
from repro.core.pk import decompose_base
from repro.core.stream import PBAStream
from repro.kernels import ref
from repro.runtime import Topology, streaming

SETTINGS = settings(max_examples=25, deadline=None)


@given(st.integers(2, 6), st.integers(2, 4))
@SETTINGS
def test_pk_edge_count_exact_power(n0, levels):
    seed = star_clique_seed(n0)
    cfg = PKConfig(levels=levels)
    n, e = pk_sizes(seed, cfg)
    _, stats = generate_pk_host(seed, cfg)
    assert stats.emitted_edges == e == seed.num_edges ** levels
    assert stats.num_vertices == n == n0 ** levels


@given(st.integers(2, 6), st.integers(2, 4), st.integers(0, 100))
@SETTINGS
def test_pk_endpoints_in_range(n0, levels, rseed):
    seed = dense_power_seed(n0, 2, seed=rseed)
    edges, _ = generate_pk_host(seed, PKConfig(levels=levels))
    s, d = edges.to_numpy()
    n = n0 ** levels
    assert s.min() >= 0 and s.max() < n
    assert d.min() >= 0 and d.max() < n


@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 50))
@SETTINGS
def test_pba_degree_sum_invariant(num_procs, k, seed):
    table = make_factions(num_procs,
                          FactionSpec(2, 1, max(num_procs // 2, 1), seed=seed))
    cfg = PBAConfig(vertices_per_proc=64, edges_per_vertex=k, seed=seed)
    edges, stats = generate_pba(cfg, table, Topology.host())
    deg = np.asarray(degree_counts(edges))
    # sum of degrees == 2 * emitted edges (undirected view)
    assert deg.sum() == 2 * stats.emitted_edges
    assert stats.emitted_edges + stats.dropped_edges == stats.requested_edges


# --- streaming round/residual contract (runtime/streaming.py) ---------------

@given(st.lists(st.integers(0, 500), min_size=1, max_size=64),
       st.integers(1, 64))
@SETTINGS
def test_round_windows_partition_any_counts(counts, cap):
    """For any pair-counts vector: the round windows partition every
    pair's count exactly, the residual is monotone non-increasing, and it
    hits zero within the static ``rounds_needed`` bound."""
    c = jnp.asarray(counts, jnp.int32)
    bound = streaming.rounds_needed(max(max(counts), 1), cap)
    windows = np.stack([np.asarray(streaming.round_window(c, r, cap))
                        for r in range(bound)])
    residuals = np.stack([np.asarray(streaming.residual_counts(c, r, cap))
                          for r in range(bound)])
    np.testing.assert_array_equal(windows.sum(axis=0), np.asarray(counts))
    assert windows.min() >= 0 and windows.max() <= cap
    assert (np.diff(residuals, axis=0) <= 0).all()
    assert (residuals >= 0).all()
    np.testing.assert_array_equal(residuals[-1], 0)
    # conservation per round: what a pair ships is exactly what its
    # residual drops by
    prev = np.asarray(counts)
    for r in range(bound):
        np.testing.assert_array_equal(windows[r], prev - residuals[r])
        prev = residuals[r]


@given(st.integers(0, 10_000), st.integers(1, 3), st.integers(1, 6))
@SETTINGS
def test_stream_blocks_partition_edges_any_layout(seed, num_factions,
                                                  rounds):
    """Arbitrary faction layouts: the stream's per-round blocks partition
    the full edge set (windows partition every pair's count), and auto
    capacity means zero drops — emitted totals equal requested exactly."""
    table = make_factions(4, FactionSpec(num_factions, 1, 4, seed=seed))
    cfg = PBAConfig(vertices_per_proc=32, edges_per_vertex=2, seed=seed,
                    pair_capacity=8, exchange_rounds=rounds)
    stream = PBAStream(cfg, table)
    blocks = [stream.block(i) for i in range(stream.num_blocks)]
    assert sum(len(s) for s, _ in blocks) == stream.requested_edges
    # every source vertex appears exactly edges_per_vertex times overall
    src = np.concatenate([s for s, _ in blocks])
    np.testing.assert_array_equal(
        np.bincount(src, minlength=stream.num_vertices),
        np.full(stream.num_vertices, cfg.edges_per_vertex))


@given(st.lists(st.integers(0, 50), min_size=1, max_size=8),
       st.integers(0, 10_000))
@SETTINGS
def test_shard_writer_manifest_totals(block_sizes, seed):
    """ShardWriter manifest totals equal emitted edges exactly — invalid
    (-1) slots are dropped from both the shard files and the counts."""
    import tempfile
    rng = np.random.default_rng(seed)
    with tempfile.TemporaryDirectory() as d:
        writer = storage.ShardWriter(d, 100, len(block_sizes))
        total = 0
        for i, m in enumerate(block_sizes):
            src = rng.integers(-1, 100, m)
            dst = rng.integers(-1, 100, m)
            writer.write_block(i, src, dst)
            total += int(((src >= 0) & (dst >= 0)).sum())
        assert writer.edges_written == total
        assert sorted(writer.manifest["complete"]) == \
            list(range(len(block_sizes)))
        src_all, dst_all, man = storage.read_shards(d)
        assert len(src_all) == len(dst_all) == total
        assert sum(man["counts"].values()) == total


# --- communication-free executor (core/cfree.py) ----------------------------

@given(st.integers(0, 5000), st.integers(1, 64))
@SETTINGS
def test_cfree_edge_slices_partition_exactly(e, p):
    """Per-rank edge-index slices partition [0, E) exactly for arbitrary
    (E, P): no gaps, no overlaps, every slice bounded by the static
    ceil(E/P) chunk — the whole zero-exchange contract rests on this
    split being a partition."""
    from repro.core.cfree import edge_slices
    slices = edge_slices(e, p)
    assert len(slices) == p
    chunk = -(-e // p) if e else 0
    cursor = 0
    for lo, hi in slices:
        assert lo == cursor and lo <= hi and hi - lo <= chunk
        cursor = hi
    assert cursor == e


@given(st.integers(2, 40), st.integers(1, 4), st.integers(1, 64),
       st.integers(0, 1000))
@SETTINGS
def test_cfree_stream_shard_totals(n, degree, slab, seed):
    """CFreeStream blocks through ShardWriter: manifest totals equal the
    model's exact emitted edge count for arbitrary (n, degree, slab)."""
    import tempfile
    from repro.core import cfree as cfree_lib
    cfg = cfree_lib.CFreeConfig(model="ba_cfree", vertices=n,
                                ba_degree=degree, seed=seed)
    stream = cfree_lib.CFreeStream(cfg, slab_edges=slab)
    _, e = cfree_lib.cfree_sizes(cfg)
    with tempfile.TemporaryDirectory() as d:
        writer = storage.ShardWriter(d, stream.num_vertices,
                                     stream.num_blocks, meta=stream.meta())
        for i in writer.missing():
            writer.write_block(i, *stream.block(i))
        assert writer.edges_written == e
        src, dst, man = storage.read_shards(d)
        assert len(src) == e and sum(man["counts"].values()) == e


@given(st.integers(0, 2**31 - 1), st.integers(0, 127),
       st.integers(0, 100))
@SETTINGS
def test_cfree_hash_python_mirror(t, ctr, seed):
    """hash_int (the serial-oracle python mirror) agrees with the jitted
    cfree_hash word-for-word on arbitrary (t, ctr)."""
    from repro.core import cfree as cfree_lib
    cfg = cfree_lib.CFreeConfig(model="ba_cfree", vertices=4, ba_degree=1,
                                seed=seed)
    w0, w1, _, _ = (int(w) for w in np.asarray(cfree_lib.cfree_words(cfg)))
    jax_val = int(np.asarray(cfree_lib.cfree_hash(
        cfree_lib.cfree_words(cfg), jnp.uint32(t), ctr)))
    assert jax_val == cfree_lib.hash_int(w0, w1, t, ctr)


@given(st.lists(st.integers(0, 9), min_size=1, max_size=200))
@SETTINGS
def test_occurrence_rank_property(vals):
    a = jnp.asarray(vals, jnp.int32)
    occ = np.asarray(occurrence_rank(a))
    want = np.zeros(len(vals), np.int64)
    seen: dict[int, int] = {}
    for i, v in enumerate(vals):
        want[i] = seen.get(v, 0)
        seen[v] = want[i] + 1
    np.testing.assert_array_equal(occ, want)


@given(st.integers(2, 50), st.integers(1, 8),
       st.integers(0, 2**31 - 1))
@SETTINGS
def test_decompose_base_is_inverse(base, levels, t):
    t = t % (base ** levels)
    digits = decompose_base(t, base, levels)
    assert (digits >= 0).all() and (digits < base).all()
    back = 0
    for d in digits:
        back = back * base + int(d)
    assert back == t


@given(st.integers(1, 2000), st.integers(1, 400), st.integers(0, 99))
@SETTINGS
def test_histogram_ref_total_mass(m, nbins, seed):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.integers(0, nbins, m), jnp.int32)
    h = np.asarray(ref.histogram_ref(v, nbins))
    assert h.sum() == m
    np.testing.assert_array_equal(h, np.bincount(np.asarray(v), minlength=nbins))


@given(st.integers(2, 1000), st.integers(0, 99))
@SETTINGS
def test_resolve_converges_for_downward_chains(m, seed):
    rng = np.random.default_rng(seed)
    ptr = np.minimum(rng.integers(0, m, m), np.maximum(np.arange(m) - 1, 0))
    ptr[0] = 0
    from repro.core.pba import resolve_pointers
    terminal = jnp.asarray(np.arange(m) < max(1, m // 10))
    p = jnp.asarray(np.where(np.asarray(terminal), np.arange(m), ptr), jnp.int32)
    out = np.asarray(resolve_pointers(p, terminal))
    assert np.asarray(terminal)[out].all()  # everyone landed on a terminal
