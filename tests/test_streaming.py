"""Multi-round streaming exchange: zero drops, parity, out-of-core streams.

The hub faction layout (every urn half-seeded with processor 0) overflows
any fixed per-pair capacity — the configuration whose tail the single-shot
exchange silently clips. These tests pin the streaming contract:

  * the legacy path drops >0 edges on the hub table (the seed behavior);
  * the multi-round path drops exactly 0 with per-round buffer
    C_r <= ceil(C / R);
  * host == sharded bit-parity holds at 1 / 2 / 8 forced host devices;
  * the recovered degree tail is unbiased: gamma_mle matches the host
    oracle generated with overflow-free capacity;
  * PBAStream / PKStream blocks land in resumable shards that reproduce the
    on-device graph.
"""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (PBAConfig, PKConfig, PBAStream, PKStream, SeedGraph,
                        degree_counts, fit_power_law, generate_pba,
                        hub_factions, star_clique_seed, stream_to_shards)
from repro.core.storage import read_shards
from repro.runtime import Topology, streaming

from helpers import run_with_devices

HUB_CFG = PBAConfig(vertices_per_proc=300, edges_per_vertex=4, seed=5,
                    pair_capacity=16, total_capacity_factor=8)
HOST = Topology.host()


# --- round/residual invariants (the streaming contract) ---------------------

def test_round_capacity_ceil():
    assert streaming.round_capacity(16, 4) == 4
    assert streaming.round_capacity(17, 4) == 5
    assert streaming.round_capacity(3, 8) == 1
    with pytest.raises(ValueError):
        streaming.round_capacity(16, 0)


def test_windows_partition_counts():
    counts = jnp.asarray([0, 1, 4, 5, 17, 64], jnp.int32)
    cap = 4
    rounds = streaming.rounds_needed(64, cap)
    windows = np.stack([np.asarray(streaming.round_window(counts, r, cap))
                        for r in range(rounds)])
    # every request served exactly once across rounds
    np.testing.assert_array_equal(windows.sum(axis=0), np.asarray(counts))
    assert windows.max() <= cap
    # residual after the last round is zero everywhere
    np.testing.assert_array_equal(
        np.asarray(streaming.residual_counts(counts, rounds - 1, cap)),
        np.zeros(len(counts), np.int32))


# --- hub stress: seed drops, streaming doesn't ------------------------------

def test_seed_path_drops_on_hub_table():
    edges, stats = generate_pba(HUB_CFG, hub_factions(8), HOST)
    assert stats.dropped_edges > 0
    assert stats.emitted_edges + stats.dropped_edges == stats.requested_edges


def test_multiround_zero_drops_on_hub_table():
    cfg = dataclasses.replace(HUB_CFG, exchange_rounds=4)
    edges, stats = generate_pba(cfg, hub_factions(8), HOST)
    assert stats.dropped_edges == 0, stats
    assert stats.emitted_edges == stats.requested_edges
    assert stats.exchange_rounds > 1
    s, d = edges.to_numpy()
    # the full attachment survives: every source exactly k times
    np.testing.assert_array_equal(
        np.bincount(s, minlength=stats.num_vertices),
        np.full(stats.num_vertices, HUB_CFG.edges_per_vertex))
    assert d.min() >= 0 and d.max() < stats.num_vertices


def test_round_buffer_capacity_bound():
    # acceptance: C_r <= ceil(C_total / R) for the swept configs
    for total, r in ((16, 4), (17, 4), (256, 8), (5, 8)):
        assert streaming.round_capacity(total, r) <= -(-total // r)


def test_streaming_rounds1_bit_matches_legacy_when_no_overflow():
    # ample capacity: the stream serves everything in round 0 from the same
    # pool slots the single-shot grant uses -> bit-identical graphs
    table = hub_factions(4)
    cfg_legacy = PBAConfig(vertices_per_proc=200, edges_per_vertex=3, seed=3,
                           pair_capacity=2048, total_capacity_factor=8)
    cfg_stream = dataclasses.replace(cfg_legacy, exchange_rounds=1)
    e_l, st_l = generate_pba(cfg_legacy, table, HOST)
    e_s, st_s = generate_pba(cfg_stream, table, HOST)
    assert st_l.dropped_edges == st_s.dropped_edges == 0
    np.testing.assert_array_equal(np.asarray(e_l.src), np.asarray(e_s.src))
    np.testing.assert_array_equal(np.asarray(e_l.dst), np.asarray(e_s.dst))


def test_drive_rounds_overlap_dispatch_before_writeback():
    """The double-buffered driver dispatches round i+1 before writing back
    round i, writes back in order, and drives arbitrary resume subsets."""
    events = []

    def dispatch(i):
        events.append(("dispatch", i))
        return i * 10

    def writeback(i, handle):
        assert handle == i * 10
        events.append(("write", i))

    n = streaming.drive_rounds([0, 1, 2], dispatch, writeback, overlap=True)
    assert n == 3
    assert events == [("dispatch", 0), ("dispatch", 1), ("write", 0),
                      ("dispatch", 2), ("write", 1), ("write", 2)]
    events.clear()
    streaming.drive_rounds([4, 2], dispatch, writeback, overlap=False)
    assert events == [("dispatch", 4), ("write", 4),
                      ("dispatch", 2), ("write", 2)]
    assert streaming.drive_rounds([], dispatch, writeback) == 0


def test_pba_sharded_stream_single_device_matches_host_stream():
    """flat(1) runs the full sharded-stream machinery in-process (lp = P):
    blocks and meta must match the host stream exactly, and the two
    drivers must be resume-compatible."""
    from repro.core.stream import PBAShardedStream
    from repro.runtime import Topology

    cfg = dataclasses.replace(HUB_CFG, exchange_rounds=4)
    table = hub_factions(8)
    host = PBAStream(cfg, table)
    sh = PBAShardedStream(cfg, table, topology=Topology.flat(1))
    assert sh.num_blocks == host.num_blocks
    assert sh.meta() == host.meta()  # interchangeable mid-manifest
    for i in (0, 1, host.num_blocks - 1):
        hu, hv = host.block(i)
        su, sv = sh.block(i)
        np.testing.assert_array_equal(su, hu)
        np.testing.assert_array_equal(sv, hv)
    with pytest.raises(ValueError, match="out of range"):
        sh.block(sh.num_blocks)
    # the sharded stream needs devices; the host topology is the host
    # stream's job
    with pytest.raises(ValueError, match="host topology"):
        PBAShardedStream(cfg, table, topology=Topology.host())


# --- host == sharded bit-parity under streaming -----------------------------

@pytest.mark.parametrize("num_devices", [1, 2, 8])
def test_streaming_sharded_matches_host(num_devices):
    """Host == sharded bit-parity for the streamed exchange; on 8 devices
    the full topology matrix (flat 1x8, hierarchical 2x4 and 4x2) must be
    bit-identical too."""
    run_with_devices(f"""
        import numpy as np
        from repro.core import PBAConfig, generate_pba, hub_factions
        from repro.runtime import Topology
        table = hub_factions(8)
        cfg = PBAConfig(vertices_per_proc=150, edges_per_vertex=3, seed=5,
                        pair_capacity=16, total_capacity_factor=8,
                        exchange_rounds=4)
        e_h, st_h = generate_pba(cfg, table, Topology.host())
        topos = [Topology.flat({num_devices})]
        if {num_devices} == 8:
            topos += [Topology.pods(2, 4), Topology.pods(4, 2)]
        for topo in topos:
            e_s, st_s = generate_pba(cfg, table, topology=topo)
            np.testing.assert_array_equal(np.asarray(e_s.src).reshape(-1),
                                          np.asarray(e_h.src).reshape(-1),
                                          err_msg=topo.label)
            np.testing.assert_array_equal(np.asarray(e_s.dst).reshape(-1),
                                          np.asarray(e_h.dst).reshape(-1),
                                          err_msg=topo.label)
            assert st_s.dropped_edges == st_h.dropped_edges == 0, \\
                (topo.label, st_s, st_h)
            assert st_s.exchange_rounds == st_h.exchange_rounds, \\
                (topo.label, st_s, st_h)
        print("OK")
    """, num_devices)


# --- degree-tail fidelity ---------------------------------------------------

def test_gamma_mle_unbiased_vs_host_oracle():
    """The recovered hub tail must match the overflow-free host oracle."""
    table = hub_factions(8)
    oracle_cfg = PBAConfig(vertices_per_proc=2000, edges_per_vertex=4,
                           seed=7, pair_capacity=64_000,
                           total_capacity_factor=8)
    stream_cfg = dataclasses.replace(oracle_cfg, pair_capacity=64,
                                     exchange_rounds=4)
    e_o, st_o = generate_pba(oracle_cfg, table, HOST)
    e_s, st_s = generate_pba(stream_cfg, table, HOST)
    assert st_o.dropped_edges == 0 and st_s.dropped_edges == 0
    g_o = fit_power_law(np.asarray(degree_counts(e_o)), kmin=5).gamma_mle
    g_s = fit_power_law(np.asarray(degree_counts(e_s)), kmin=5).gamma_mle
    assert abs(g_o - g_s) < 0.15, (g_o, g_s)
    # and the clipped seed path IS biased on this table — the bug being fixed
    clip_cfg = dataclasses.replace(oracle_cfg, pair_capacity=64)
    e_c, st_c = generate_pba(clip_cfg, table, HOST)
    assert st_c.dropped_edges > 0


# --- out-of-core streams ----------------------------------------------------

def test_pba_stream_zero_drops_and_shard_roundtrip(tmp_path):
    cfg = dataclasses.replace(HUB_CFG, exchange_rounds=4)
    stream = PBAStream(cfg, hub_factions(8))
    assert stream.round_cap <= -(-16 // 4)
    man, stats = stream_to_shards(stream, str(tmp_path))
    assert stats.dropped_edges == 0, stats
    src, dst, _ = read_shards(str(tmp_path))
    assert len(src) == stats.requested_edges
    np.testing.assert_array_equal(
        np.bincount(src, minlength=stats.num_vertices),
        np.full(stats.num_vertices, cfg.edges_per_vertex))


def test_pba_stream_matches_on_device_multiround():
    table = hub_factions(4)
    cfg = PBAConfig(vertices_per_proc=200, edges_per_vertex=3, seed=11,
                    pair_capacity=32, exchange_rounds=4,
                    total_capacity_factor=8)
    e_dev, st_dev = generate_pba(cfg, table, HOST)
    stream = PBAStream(cfg, table, auto_capacity=False)
    assert stream.num_blocks == st_dev.exchange_rounds
    su = np.concatenate([b.src for b in stream.iter_blocks()])
    dv = np.concatenate([b.dst for b in stream.iter_blocks()])
    s0, d0 = e_dev.to_numpy()
    n = stream.num_vertices

    def key(a, b):
        return np.sort(a.astype(np.int64) * n + b)

    np.testing.assert_array_equal(key(su, dv), key(s0, d0))


def test_pk_stream_slabs_match_host(tmp_path):
    seed = star_clique_seed(4)
    cfg = PKConfig(levels=5, noise=0.0)
    stream = PKStream(seed, cfg, slab_edges=1000)
    man, stats = stream_to_shards(stream, str(tmp_path))
    assert stats.dropped_edges == 0
    src, dst, _ = read_shards(str(tmp_path))
    from repro.core import generate_pk_host
    e_h, _ = generate_pk_host(seed, cfg)
    s0, d0 = e_h.to_numpy()
    # slabs are contiguous index ranges -> concatenation preserves order
    np.testing.assert_array_equal(src, s0)
    np.testing.assert_array_equal(dst, d0)


def test_stream_resume_rejects_different_generator(tmp_path):
    """Same shapes, different seed => different graph: resume must raise
    instead of silently interleaving shards of two graphs."""
    seed = star_clique_seed(4)
    stream_to_shards(PKStream(seed, PKConfig(levels=5, seed=3),
                              slab_edges=1000), str(tmp_path))
    with pytest.raises(ValueError, match="meta mismatch"):
        stream_to_shards(PKStream(seed, PKConfig(levels=5, seed=4),
                                  slab_edges=1000), str(tmp_path))


def test_stream_resume_rejects_same_shape_different_seed_graph(tmp_path):
    """Two seed graphs with identical (n0, e0) — so identical legacy meta,
    num_vertices and num_shards — still define different graphs: only the
    full spec digest in the manifest fingerprint catches the swap."""
    s1 = star_clique_seed(4)
    s2 = SeedGraph(s1.v.copy(), s1.u.copy(), s1.num_vertices)  # reversed
    cfg = PKConfig(levels=5, seed=3)
    m1 = PKStream(s1, cfg, slab_edges=1000).meta()
    m2 = PKStream(s2, cfg, slab_edges=1000).meta()
    legacy = {k: v for k, v in m1.items() if k != "spec_digest"}
    assert legacy == {k: v for k, v in m2.items() if k != "spec_digest"}
    assert m1["spec_digest"] != m2["spec_digest"]
    stream_to_shards(PKStream(s1, cfg, slab_edges=1000), str(tmp_path))
    with pytest.raises(ValueError, match="meta mismatch"):
        stream_to_shards(PKStream(s2, cfg, slab_edges=1000), str(tmp_path))


def test_stream_resume_rejects_colliding_exchange_config(tmp_path):
    """(pair_capacity=16, rounds=4) and (8, 2) collide on every legacy meta
    field (same C_r, same auto urn budget) — resuming across them must
    still fail loudly on the folded-in spec digest."""
    table = hub_factions(4)
    cfg_a = PBAConfig(vertices_per_proc=100, edges_per_vertex=3, seed=3,
                      pair_capacity=16, exchange_rounds=4)
    cfg_b = dataclasses.replace(cfg_a, pair_capacity=8, exchange_rounds=2)
    m_a = PBAStream(cfg_a, table).meta()
    m_b = PBAStream(cfg_b, table).meta()
    legacy = {k: v for k, v in m_a.items() if k != "spec_digest"}
    assert legacy == {k: v for k, v in m_b.items() if k != "spec_digest"}
    assert m_a["spec_digest"] != m_b["spec_digest"]
    stream_to_shards(PBAStream(cfg_a, table), str(tmp_path))
    with pytest.raises(ValueError, match="meta mismatch"):
        stream_to_shards(PBAStream(cfg_b, table), str(tmp_path))


def test_stream_resume_regenerates_only_missing(tmp_path):
    import json
    import os
    seed = star_clique_seed(4)
    cfg = PKConfig(levels=5, noise=0.0)
    stream_to_shards(PKStream(seed, cfg, slab_edges=1000), str(tmp_path))
    with open(tmp_path / "manifest.json") as f:
        man = json.load(f)
    man["complete"] = [i for i in man["complete"] if i != 3]
    del man["counts"]["3"]
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump(man, f)
    os.remove(tmp_path / "shard_00003.npz")
    mtime0 = os.path.getmtime(tmp_path / "shard_00000.npz")
    man2, stats2 = stream_to_shards(PKStream(seed, cfg, slab_edges=1000),
                                    str(tmp_path))
    assert os.path.getmtime(tmp_path / "shard_00000.npz") == mtime0
    assert sorted(man2["complete"]) == sorted(range(man2["num_shards"]))
    assert stats2.dropped_edges == 0
