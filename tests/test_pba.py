"""PBA generator: two-phase attachment invariants, BA-limit statistics."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (FactionSpec, PBAConfig, block_factions,
                        degree_counts, fit_power_law, generate_pba,
                        make_factions, sampled_path_stats,
                        community_contrast, serial_ba_reference)
from repro.core.pba import occurrence_rank, resolve_pointers
from repro.runtime import Topology

from helpers import run_with_devices

HOST = Topology.host()


def test_occurrence_rank():
    a = jnp.asarray([3, 1, 3, 3, 1, 0], jnp.int32)
    occ = np.asarray(occurrence_rank(a))
    np.testing.assert_array_equal(occ, [0, 0, 1, 2, 1, 0])


def test_resolve_pointers_chain():
    # 0,1 terminal; chain 5->4->3->2->0
    terminal = jnp.asarray([True, True, False, False, False, False])
    ptr = jnp.asarray([0, 1, 0, 2, 3, 4], jnp.int32)
    out = np.asarray(resolve_pointers(ptr, terminal))
    np.testing.assert_array_equal(out, [0, 1, 0, 0, 0, 0])


def _serial_resolve(ptr, terminal):
    """res[j] = j if terminal[j] else res[ptr[j]], in increasing j."""
    res = np.arange(len(ptr))
    for j in range(len(ptr)):
        if not terminal[j]:
            res[j] = res[ptr[j]]
    return res


def _doubled(ptr, passes):
    """ptr <- ptr[ptr], ``passes`` times: a capped resolve's result."""
    for _ in range(passes):
        ptr = ptr[ptr]
    return ptr


def _urn_row(kind, m=4096, seed=7):
    """(ptr, terminal) of one urn: terminals are fixed points and every
    other slot points strictly downward."""
    j = np.arange(m)
    rng = np.random.default_rng(seed)
    if kind == "chain":
        return np.maximum(j - 1, 0), j == 0
    terminal = {"prefix": j < m // 8,                        # phase-2 pool
                "scattered": (j < 3) | (rng.random(m) < 0.05),  # phase 1
                "terminal": np.ones(m, bool)}[kind]
    return np.where(terminal, j, rng.integers(0, np.maximum(j, 1))), terminal


@pytest.mark.parametrize("kinds,max_rounds", [
    (("prefix",), 64), (("scattered",), 64), (("chain",), 64),
    (("chain",), 1), (("chain",), 2),
    (("chain", "prefix", "terminal", "scattered"), 64),
], ids=["prefix", "scattered", "chain", "chain-cap1", "chain-cap2",
        "vmap-unequal-depths"])
def test_resolve_pointers_matches_serial_oracle(kinds, max_rounds):
    """Resolved slots equal the serial urn walk; under a ``max_rounds``
    cap, the pointers doubled that many times. A batch of rows whose
    depths differ runs under vmap, as the stream programs run it."""
    rows = [_urn_row(k) for k in kinds]
    ptr = np.stack([p for p, _ in rows])
    terminal = np.stack([t for _, t in rows])
    got = np.asarray(jax.vmap(lambda p, t: resolve_pointers(p, t, max_rounds))(
        jnp.asarray(ptr, jnp.int32), jnp.asarray(terminal)))
    for g, p, t in zip(got, ptr, terminal):
        want = (_serial_resolve(p, t) if max_rounds == 64
                else _doubled(p, max_rounds))
        np.testing.assert_array_equal(g, want)


def test_phase2_pool_matches_serial_oracle():
    """Three ranks' urn pools equal the serial urn walk over the same
    draws, mapped to global vertex ids."""
    from repro.core import rng as rng_lib
    from repro.core.pba import _phase2_pool
    cfg = PBAConfig(vertices_per_proc=300, edges_per_vertex=3,
                    seed=2718281828)
    e_local = cfg.edges_per_proc
    pool_n = e_local + cfg.total_capacity_factor * e_local
    pools = np.asarray(jax.vmap(lambda r: _phase2_pool(r, cfg))(
        jnp.arange(3, dtype=jnp.int32)))
    jj = np.arange(pool_n)
    terminal = jj < e_local
    for rank in range(3):
        key = rng_lib.device_key(cfg.seed, rng_lib.STREAM_PBA_PHASE2_URN,
                                 jnp.int32(rank))
        r = np.asarray(rng_lib.uniform_slots(
            key, pool_n, jnp.asarray(np.maximum(jj, 1), jnp.int32)))
        slot = _serial_resolve(np.where(terminal, jj, r), terminal)
        np.testing.assert_array_equal(
            pools[rank], rank * cfg.vertices_per_proc
            + slot // cfg.edges_per_vertex)


def test_stream_setup_while_loops_gather_once_per_pass():
    """Each pointer-doubling loop of the stream's setup and pool programs
    gathers once per pass: its cond and body hold one gather between them.
    Under vmap the lowering evaluates the cond a second time inside the
    body, so a gather in the cond would cost two a pass."""
    from repro.analysis.audit import iter_eqns
    from repro.core import stream as stream_lib

    def while_gathers(jaxpr):
        return [sum(e.primitive.name == "gather"
                    for part in ("cond_jaxpr", "body_jaxpr")
                    for e in iter_eqns(eqn.params[part].jaxpr))
                for eqn in iter_eqns(jaxpr) if eqn.primitive.name == "while"]

    table = make_factions(4, FactionSpec(2, 2, 3, seed=0))
    cfg = PBAConfig(vertices_per_proc=100, edges_per_vertex=3,
                    exchange_rounds=2, seed=5)
    topo = Topology.flat(1)
    setup = stream_lib._sharded_setup_fn(cfg, 4, topo)
    procs = jnp.asarray(table.procs)[None]
    s = jnp.asarray(table.s)[None]
    pool_fn, _ = stream_lib._sharded_grant_fns(cfg, 4, topo, 512, 16, 64)
    for name, jaxpr in (("setup", jax.make_jaxpr(setup)(procs, s)),
                        ("pool", jax.make_jaxpr(pool_fn)())):
        assert while_gathers(jaxpr.jaxpr) == [1], name


def test_counts_conservation_and_no_drops():
    table = make_factions(8, FactionSpec(4, 2, 4, seed=2))
    cfg = PBAConfig(vertices_per_proc=500, edges_per_vertex=4,
                    interfaction_prob=0.05, seed=11)
    edges, stats = generate_pba(cfg, table, HOST)
    assert stats.requested_edges == 8 * 500 * 4
    assert stats.dropped_edges == 0
    s, d = edges.to_numpy()
    assert len(s) == stats.emitted_edges
    # every source vertex appears exactly k times
    src_counts = np.bincount(s, minlength=stats.num_vertices)
    np.testing.assert_array_equal(src_counts,
                                  np.full(stats.num_vertices, 4))
    # endpoints are valid global vertex ids
    assert d.min() >= 0 and d.max() < stats.num_vertices


def test_determinism():
    table = make_factions(4, FactionSpec(2, 2, 3, seed=0))
    cfg = PBAConfig(vertices_per_proc=100, edges_per_vertex=3, seed=5)
    e1, _ = generate_pba(cfg, table, HOST)
    e2, _ = generate_pba(cfg, table, HOST)
    np.testing.assert_array_equal(np.asarray(e1.src), np.asarray(e2.src))
    np.testing.assert_array_equal(np.asarray(e1.dst), np.asarray(e2.dst))


def test_seed_changes_graph():
    table = make_factions(4, FactionSpec(2, 2, 3, seed=0))
    e1, _ = generate_pba(PBAConfig(100, 3, seed=5), table, HOST)
    e2, _ = generate_pba(PBAConfig(100, 3, seed=6), table, HOST)
    assert (np.asarray(e1.dst) != np.asarray(e2.dst)).any()


def test_power_law_gamma_range():
    # Paper Fig. 4: fitted gamma > 2 for PBA graphs.
    table = make_factions(8, FactionSpec(4, 2, 4, seed=1))
    cfg = PBAConfig(vertices_per_proc=4000, edges_per_vertex=4,
                    interfaction_prob=0.05, seed=7)
    edges, _ = generate_pba(cfg, table, HOST)
    deg = np.asarray(degree_counts(edges))
    fit = fit_power_law(deg, kmin=5)
    assert 2.0 < fit.gamma_mle < 3.6, fit
    assert 1.5 < fit.gamma_ls < 4.5, fit


def test_small_world():
    # Paper Table 2: short avg path length, small diameter.
    table = make_factions(8, FactionSpec(4, 2, 4, seed=1))
    cfg = PBAConfig(vertices_per_proc=2000, edges_per_vertex=4, seed=7)
    edges, _ = generate_pba(cfg, table, HOST)
    ps = sampled_path_stats(edges, num_sources=8)
    assert ps.avg_path_length < 8.0
    assert ps.diameter_estimate <= 16
    assert ps.reachable_fraction > 0.95


def test_faction_structure_creates_communities():
    # Paper Fig. 5: block factions => block community structure.
    table = block_factions(8, 2)
    cfg = PBAConfig(vertices_per_proc=1000, edges_per_vertex=4,
                    interfaction_prob=0.02, seed=3)
    edges, _ = generate_pba(cfg, table, HOST)
    contrast = community_contrast(edges, num_blocks=4)
    assert contrast > 2.0, contrast


def test_interfaction_prob_spreads_edges():
    table = block_factions(8, 2)
    lo, _ = generate_pba(
        PBAConfig(500, 4, interfaction_prob=0.0, seed=3), table, HOST)
    hi, _ = generate_pba(
        PBAConfig(500, 4, interfaction_prob=0.5, seed=3), table, HOST)
    assert community_contrast(hi, 4) < community_contrast(lo, 4)


def test_capacity_overflow_is_counted_not_crashed():
    table = make_factions(4, FactionSpec(2, 2, 2, seed=0))
    cfg = PBAConfig(vertices_per_proc=500, edges_per_vertex=4,
                    pair_capacity=16, seed=1)  # absurdly small on purpose
    edges, stats = generate_pba(cfg, table, HOST)
    assert stats.dropped_edges > 0
    assert stats.emitted_edges + stats.dropped_edges == stats.requested_edges
    s, d = edges.to_numpy()
    assert len(s) == stats.emitted_edges


def test_serial_ba_reference_gamma():
    edges = serial_ba_reference(4000, 4, seed=0)
    deg = np.asarray(degree_counts(edges))
    fit = fit_power_law(deg, kmin=5)
    assert 2.3 < fit.gamma_mle < 3.4  # BA theory: gamma = 3


def test_pba_vs_serial_ba_statistics():
    """P=1 PBA should match serial BA's degree statistics (not exact edges)."""
    table = make_factions(1, FactionSpec(1, 1, 1, seed=0))
    cfg = PBAConfig(vertices_per_proc=4000, edges_per_vertex=4, seed=2,
                    interfaction_prob=0.0)
    e_pba, _ = generate_pba(cfg, table, HOST)
    e_ser = serial_ba_reference(4000, 4, seed=2)
    d_pba = np.sort(np.asarray(degree_counts(e_pba)))[::-1]
    d_ser = np.sort(np.asarray(degree_counts(e_ser)))[::-1]
    g_pba = fit_power_law(d_pba, kmin=5).gamma_mle
    g_ser = fit_power_law(d_ser, kmin=5).gamma_mle
    assert abs(g_pba - g_ser) < 0.4, (g_pba, g_ser)


def test_distributed_matches_host_8dev():
    run_with_devices("""
        import numpy as np
        from repro.core import *
        from repro.runtime import Topology
        table = make_factions(8, FactionSpec(4, 2, 4, seed=1))
        cfg = PBAConfig(vertices_per_proc=300, edges_per_vertex=3,
                        interfaction_prob=0.05, seed=7)
        e_d, st_d = generate_pba(cfg, table, Topology.flat(8))
        e_h, st_h = generate_pba(cfg, table, Topology.host())
        np.testing.assert_array_equal(np.asarray(e_d.src).reshape(-1),
                                      np.asarray(e_h.src).reshape(-1))
        np.testing.assert_array_equal(np.asarray(e_d.dst).reshape(-1),
                                      np.asarray(e_h.dst).reshape(-1))
        assert st_d.dropped_edges == st_h.dropped_edges
        print("OK")
    """, 8)


def test_logical_procs_sharded_matches_host_4dev():
    """Paper-scale config: more logical processors than devices (1000-proc
    MPI runs on a 256-chip pod). Must be bit-identical to host mode."""
    run_with_devices("""
        import numpy as np
        from repro.core import (make_factions, FactionSpec, PBAConfig,
                                generate_pba)
        from repro.runtime import Topology
        table = make_factions(16, FactionSpec(8, 2, 6, seed=2))
        cfg = PBAConfig(vertices_per_proc=200, edges_per_vertex=3,
                        interfaction_prob=0.05, seed=9)
        e_s, st_s = generate_pba(cfg, table, Topology.flat(4))
        e_h, st_h = generate_pba(cfg, table, Topology.host())
        np.testing.assert_array_equal(np.asarray(e_s.src).reshape(-1),
                                      np.asarray(e_h.src).reshape(-1))
        np.testing.assert_array_equal(np.asarray(e_s.dst).reshape(-1),
                                      np.asarray(e_h.dst).reshape(-1))
        assert st_s.dropped_edges == st_h.dropped_edges
        print("OK")
    """, 4)
