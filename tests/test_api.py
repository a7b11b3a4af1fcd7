"""The one front door: GraphSpec -> plan() -> generate().

Golden parity suite — ``api.generate(spec)`` must be *bit-identical* to
every legacy entry point it wraps (host and 8 forced host devices, flat
and pods topologies, single-shot and streamed exchanges, memory and shard
sinks) — plus planner validation-error units, presets, and describe().
"""
import dataclasses

import numpy as np
import pytest

from repro import api
from repro.api import GraphSpec
from repro.core import FactionSpec, hub_factions, make_factions
from repro.core.pba import PBAConfig, generate_pba
from repro.core.pk import PKConfig, generate_pk_host, star_clique_seed
from repro.core.storage import read_shards
from repro.core.stream import PBAStream, PKStream, stream_to_shards
from repro.runtime import Topology

from helpers import run_with_devices

PBA_SPEC = GraphSpec(model="pba", procs=8, vertices_per_proc=100,
                     edges_per_vertex=3, seed=5,
                     factions=FactionSpec(4, 2, 4, seed=2))
PK_SPEC = GraphSpec(model="pk", levels=5, noise=0.05, seed=3)


def _legacy_pba_cfg(spec: GraphSpec) -> PBAConfig:
    return PBAConfig(vertices_per_proc=spec.vertices_per_proc,
                     edges_per_vertex=spec.edges_per_vertex,
                     interfaction_prob=spec.interfaction_prob,
                     pair_capacity=spec.pair_capacity,
                     exchange_rounds=spec.exchange_rounds,
                     total_capacity_factor=spec.total_capacity_factor,
                     seed=spec.seed)


def _assert_bit_equal(edges, ref_edges, msg=""):
    np.testing.assert_array_equal(np.asarray(edges.src).reshape(-1),
                                  np.asarray(ref_edges.src).reshape(-1),
                                  err_msg=msg)
    np.testing.assert_array_equal(np.asarray(edges.dst).reshape(-1),
                                  np.asarray(ref_edges.dst).reshape(-1),
                                  err_msg=msg)


# --- parity: host executors --------------------------------------------------

def test_pba_host_parity():
    spec = PBA_SPEC.replace(execution="host")
    res = api.generate(spec)
    table = make_factions(8, FactionSpec(4, 2, 4, seed=2))
    e_h, st_h = generate_pba(_legacy_pba_cfg(spec), table, Topology.host())
    _assert_bit_equal(res.edges, e_h)
    assert res.stats == st_h
    assert res.plan.executor == "generate_pba"
    assert res.plan.execution == "host"


def test_pba_host_parity_streamed_exchange():
    spec = PBA_SPEC.replace(execution="host", factions="hub",
                            pair_capacity=16, exchange_rounds=4,
                            total_capacity_factor=8)
    res = api.generate(spec)
    e_h, st_h = generate_pba(_legacy_pba_cfg(spec), hub_factions(8),
                             Topology.host())
    _assert_bit_equal(res.edges, e_h)
    assert res.stats == st_h
    assert res.stats.dropped_edges == 0 and res.stats.exchange_rounds > 1


def test_pk_host_parity():
    res = api.generate(PK_SPEC.replace(execution="host"))
    e_h, st_h = generate_pk_host(star_clique_seed(5),
                                 PKConfig(levels=5, noise=0.05, seed=3))
    _assert_bit_equal(res.edges, e_h)
    assert res.stats == st_h


# --- parity: stream drivers, memory and shard sinks --------------------------

def test_pba_streamed_memory_matches_stream_driver():
    spec = PBA_SPEC.replace(execution="streamed", auto_capacity=False,
                            exchange_rounds=2)
    res = api.generate(spec)
    stream = PBAStream(_legacy_pba_cfg(spec),
                       make_factions(8, FactionSpec(4, 2, 4, seed=2)),
                       auto_capacity=False)
    src = np.concatenate([b.src for b in stream.iter_blocks()])
    dst = np.concatenate([b.dst for b in stream.iter_blocks()])
    np.testing.assert_array_equal(np.asarray(res.edges.src), src)
    np.testing.assert_array_equal(np.asarray(res.edges.dst), dst)
    assert res.stats.exchange_rounds == stream.num_blocks


def test_pba_shard_sink_matches_legacy(tmp_path):
    spec = PBA_SPEC.replace(execution="streamed", sink="shards",
                            out_dir=str(tmp_path / "api"),
                            exchange_rounds=2)
    res = api.generate(spec)
    assert res.manifest is not None and res.out_dir == spec.out_dir
    stream = PBAStream(_legacy_pba_cfg(spec),
                       make_factions(8, FactionSpec(4, 2, 4, seed=2)))
    man, st = stream_to_shards(stream, str(tmp_path / "legacy"))
    s_a, d_a, man_a = read_shards(spec.out_dir)
    s_l, d_l, _ = read_shards(str(tmp_path / "legacy"))
    np.testing.assert_array_equal(s_a, s_l)
    np.testing.assert_array_equal(d_a, d_l)
    assert man_a["counts"] == man["counts"]
    assert res.stats == st


def test_pk_shard_sink_matches_legacy(tmp_path):
    spec = PK_SPEC.replace(execution="streamed", sink="shards",
                           out_dir=str(tmp_path / "api"), slab_edges=1000)
    res = api.generate(spec)
    man, st = stream_to_shards(
        PKStream(star_clique_seed(5), PKConfig(levels=5, noise=0.05, seed=3),
                 slab_edges=1000),
        str(tmp_path / "legacy"))
    s_a, d_a, _ = read_shards(spec.out_dir)
    s_l, d_l, _ = read_shards(str(tmp_path / "legacy"))
    np.testing.assert_array_equal(s_a, s_l)
    np.testing.assert_array_equal(d_a, d_l)
    assert res.stats == st


def test_non_streamed_shard_sink(tmp_path):
    """host execution + shards sink: generate in memory, land shards."""
    spec = PBA_SPEC.replace(execution="host", sink="shards",
                            out_dir=str(tmp_path), num_shards=4)
    res = api.generate(spec)
    assert res.edges is not None and res.manifest is not None
    src, dst, man = read_shards(str(tmp_path))
    s0, d0 = res.edges.flat().to_numpy()
    np.testing.assert_array_equal(src, s0)
    np.testing.assert_array_equal(dst, d0)
    assert man["num_shards"] == 4
    assert man["meta"]["spec_digest"] == spec.digest()


# --- parity: sharded executors on 8 forced host devices ----------------------

def test_sharded_parity_matrix_8dev():
    """api.generate == generate_pba / generate_pk on flat and pods
    topologies, single-shot and streamed exchange."""
    run_with_devices("""
        import dataclasses
        import numpy as np
        from repro import api
        from repro.api import GraphSpec
        from repro.core import FactionSpec, make_factions
        from repro.core.pba import PBAConfig, generate_pba
        from repro.core.pk import PKConfig, generate_pk, star_clique_seed
        from repro.runtime import Topology

        table = make_factions(8, FactionSpec(4, 2, 4, seed=2))
        base = GraphSpec(model="pba", procs=8, vertices_per_proc=100,
                         edges_per_vertex=3, seed=5,
                         factions=FactionSpec(4, 2, 4, seed=2))
        for streamed in (False, True):
            spec = (base.replace(pair_capacity=16, exchange_rounds=4,
                                 total_capacity_factor=8)
                    if streamed else base)
            cfg = PBAConfig(vertices_per_proc=100, edges_per_vertex=3,
                            seed=5,
                            pair_capacity=spec.pair_capacity,
                            exchange_rounds=spec.exchange_rounds,
                            total_capacity_factor=spec.total_capacity_factor)
            for topo in (None, Topology.flat(8), Topology.pods(2, 4),
                         Topology.pods(4, 2)):
                res = api.generate(spec.replace(execution="sharded",
                                                topology=topo))
                t = topo or Topology.flat(8)
                ref, st = generate_pba(cfg, table, topology=t)
                np.testing.assert_array_equal(
                    np.asarray(res.edges.src).reshape(-1),
                    np.asarray(ref.src).reshape(-1), err_msg=t.label)
                np.testing.assert_array_equal(
                    np.asarray(res.edges.dst).reshape(-1),
                    np.asarray(ref.dst).reshape(-1), err_msg=t.label)
                assert res.stats.dropped_edges == st.dropped_edges
                assert res.plan.lp == 1 and res.plan.num_procs == 8

        # lp > 1: 16 logical procs over 8 devices
        table16 = make_factions(16, FactionSpec(8, 2, 8, seed=2))
        spec16 = GraphSpec(model="pba", procs=16, vertices_per_proc=50,
                           edges_per_vertex=3, seed=5,
                           factions=FactionSpec(8, 2, 8, seed=2),
                           execution="sharded")
        res16 = api.generate(spec16)
        cfg16 = PBAConfig(vertices_per_proc=50, edges_per_vertex=3, seed=5)
        e_16, _ = generate_pba(cfg16, table16, topology=Topology.flat(8))
        np.testing.assert_array_equal(
            np.asarray(res16.edges.src).reshape(-1),
            np.asarray(e_16.src).reshape(-1))
        assert res16.plan.lp == 2

        # PK sharded
        pk = GraphSpec(model="pk", levels=5, noise=0.05, seed=3,
                       execution="sharded")
        res_pk = api.generate(pk)
        e_pk, st_pk = generate_pk(star_clique_seed(5),
                                  PKConfig(levels=5, noise=0.05, seed=3))
        np.testing.assert_array_equal(np.asarray(res_pk.edges.src),
                                      np.asarray(e_pk.src))
        np.testing.assert_array_equal(np.asarray(res_pk.edges.dst),
                                      np.asarray(e_pk.dst))
        assert res_pk.stats.emitted_edges == st_pk.emitted_edges
        print("OK")
    """, 8)


def test_auto_resolution_8dev():
    """auto picks sharded when P divides the devices, host otherwise; a
    shards sink on D > 1 devices resolves to sharded-streamed execution."""
    run_with_devices("""
        from repro import api
        from repro.api import GraphSpec
        base = GraphSpec(model="pba", procs=8, vertices_per_proc=50,
                         edges_per_vertex=3, seed=5)
        assert api.plan(base).execution == "sharded"
        assert api.plan(base.replace(procs=6)).execution == "host"
        pl = api.plan(base.replace(sink="shards", out_dir="/tmp/x"))
        assert pl.execution == "streamed"
        assert pl.executor == "pba_stream_sharded"
        assert pl.topology.label == "flat_1x8" and pl.lp == 1
        # P that does not divide the devices falls back to the host driver
        pl6 = api.plan(base.replace(procs=6, sink="shards", out_dir="/tmp/x"))
        assert pl6.executor == "pba_stream"
        print("OK")
    """, 8)


def test_sharded_streamed_parity_matrix_8dev():
    """Sharded-streamed output is bit-identical to host-streamed and (as a
    multiset) to single-shot across host / flat(8) / pods(2,4) / pods(4,2)
    x memory / shards sinks, and a partial manifest written by one driver
    resumes mid-round under another topology's driver."""
    run_with_devices("""
        import json
        import os
        import tempfile
        import numpy as np
        from repro import api
        from repro.api import GraphSpec
        from repro.core.storage import read_shards
        from repro.runtime import Topology

        base = GraphSpec(model="pba", procs=8, vertices_per_proc=100,
                         edges_per_vertex=3, seed=5, factions="hub",
                         pair_capacity=16, exchange_rounds=4,
                         total_capacity_factor=8)
        topos = (Topology.host(), Topology.flat(8), Topology.pods(2, 4),
                 Topology.pods(4, 2))
        with tempfile.TemporaryDirectory() as d:
            ref_dir = os.path.join(d, "ref")
            ref = api.generate(base.replace(execution="streamed",
                                            topology=Topology.host(),
                                            sink="shards", out_dir=ref_dir))
            assert ref.plan.executor == "pba_stream"
            assert ref.stats.dropped_edges == 0, ref.stats
            s_ref, d_ref, man_ref = read_shards(ref_dir)

            for topo in topos:
                for sink in ("memory", "shards"):
                    out = os.path.join(d, f"{topo.label}_{sink}")
                    res = api.generate(base.replace(
                        execution="streamed", topology=topo, sink=sink,
                        out_dir=out if sink == "shards" else None))
                    want = ("pba_stream" if topo.is_host
                            else "pba_stream_sharded")
                    assert res.plan.executor == want, (topo.label, sink)
                    assert res.stats.dropped_edges == 0, (topo.label, sink)
                    if sink == "memory":
                        s, dd = (np.asarray(res.edges.src),
                                 np.asarray(res.edges.dst))
                        man = None
                    else:
                        s, dd, man = read_shards(out)
                    np.testing.assert_array_equal(
                        s, s_ref, err_msg=f"{topo.label}/{sink}")
                    np.testing.assert_array_equal(
                        dd, d_ref, err_msg=f"{topo.label}/{sink}")
                    if man is not None:
                        assert man["counts"] == man_ref["counts"], topo.label

            # vs single-shot: parity-mode stream (pools at the static
            # device budget) over an overflow-free capacity must emit the
            # single-shot edge multiset exactly, on every topology
            shot_spec = base.replace(pair_capacity=512, exchange_rounds=None,
                                     execution="sharded")
            shot = api.generate(shot_spec)
            assert shot.stats.dropped_edges == 0, shot.stats
            n = shot.stats.num_vertices
            def key(a, b):
                a = np.asarray(a).reshape(-1).astype(np.int64)
                return np.sort(a * n + np.asarray(b).reshape(-1))
            k_shot = key(shot.edges.src, shot.edges.dst)
            for topo in topos:
                res = api.generate(shot_spec.replace(
                    execution="streamed", exchange_rounds=8,
                    auto_capacity=False, topology=topo))
                assert res.stats.exchange_rounds > 1  # actually multi-round
                assert res.stats.dropped_edges == 0, (topo.label, res.stats)
                np.testing.assert_array_equal(
                    key(res.edges.src, res.edges.dst), k_shot,
                    err_msg=topo.label)

            # resume from a partial manifest mid-round: drop a middle
            # shard from the host-streamed run, finish it with the
            # pods-sharded driver — same shards, bit for bit
            man = json.load(open(os.path.join(ref_dir, "manifest.json")))
            drop = sorted(man["complete"])[len(man["complete"]) // 2]
            man["complete"] = [i for i in man["complete"] if i != drop]
            del man["counts"][str(drop)]
            json.dump(man, open(os.path.join(ref_dir, "manifest.json"), "w"))
            os.remove(os.path.join(ref_dir, f"shard_{drop:05d}.npz"))
            res = api.generate(base.replace(execution="streamed",
                                            topology=Topology.pods(2, 4),
                                            sink="shards", out_dir=ref_dir))
            assert res.plan.executor == "pba_stream_sharded"
            assert sorted(res.manifest["complete"]) == \
                list(range(res.manifest["num_shards"]))
            s2, d2, _ = read_shards(ref_dir)
            np.testing.assert_array_equal(s2, s_ref)
            np.testing.assert_array_equal(d2, d_ref)
        print("OK")
    """, 8)


# --- planner validation ------------------------------------------------------

def test_plan_rejects_unknown_model_execution_sink():
    with pytest.raises(ValueError, match="unknown model"):
        api.plan(GraphSpec(model="erdos"))
    with pytest.raises(ValueError, match="unknown execution"):
        api.plan(PBA_SPEC.replace(execution="warp"))
    with pytest.raises(ValueError, match="unknown sink"):
        api.plan(PBA_SPEC.replace(sink="tape"))


def test_plan_rejects_incomplete_scale():
    with pytest.raises(ValueError, match="scale incomplete"):
        api.plan(GraphSpec(model="pba", procs=8))
    with pytest.raises(ValueError, match="levels"):
        api.plan(GraphSpec(model="pk"))


def test_plan_rejects_non_factoring_procs():
    """The headline validation: P must factor over the topology, checked
    before any compilation (and before any device allocation)."""
    spec = PBA_SPEC.replace(procs=10, topology=Topology.pods(2, 4),
                            execution="sharded",
                            factions=FactionSpec(5, 2, 5, seed=2))
    with pytest.raises(ValueError, match="divide"):
        api.plan(spec)


def test_plan_rejects_missing_devices():
    spec = PBA_SPEC.replace(topology=Topology.pods(2, 4),
                            execution="sharded")
    with pytest.raises(ValueError, match="devices"):
        api.plan(spec)  # single-device test process has no 8-device mesh


def test_plan_rejects_sink_and_topology_conflicts():
    with pytest.raises(ValueError, match="out_dir"):
        api.plan(PBA_SPEC.replace(sink="shards"))
    with pytest.raises(ValueError, match="host execution"):
        api.plan(PBA_SPEC.replace(execution="host",
                                  topology=Topology.flat(1)))
    with pytest.raises(ValueError, match="device topology"):
        api.plan(PBA_SPEC.replace(execution="sharded",
                                  topology=Topology.host()))
    # pk streaming stays host-driven: a device topology is a config error
    with pytest.raises(ValueError, match="host-driven"):
        api.plan(PK_SPEC.replace(execution="streamed",
                                 topology=Topology.flat(1)))


def test_plan_streamed_resolves_sharded_stream():
    """Streamed execution over a device topology resolves to the
    device-sharded stream driver — the out-of-core path uses the devices
    (the pre-PR planner rejected exactly this combination)."""
    pl = api.plan(PBA_SPEC.replace(execution="streamed",
                                   topology=Topology.flat(1)))
    assert pl.execution == "streamed"
    assert pl.executor == "pba_stream_sharded" and pl.lp == 8
    # auto + shards sink routes through the same resolution
    pl = api.plan(PBA_SPEC.replace(sink="shards", out_dir="/d",
                                   topology=Topology.flat(1)))
    assert pl.execution == "streamed"
    assert pl.executor == "pba_stream_sharded"
    # Topology.host() (or a single device with no topology request) still
    # selects the host-driven stream
    assert api.plan(PBA_SPEC.replace(execution="streamed",
                                     topology=Topology.host())
                    ).executor == "pba_stream"
    assert api.plan(PBA_SPEC.replace(execution="streamed")
                    ).executor == "pba_stream"
    # P must still factor over the requested topology, pre-compilation
    with pytest.raises(ValueError, match="divide"):
        api.plan(PBA_SPEC.replace(procs=10, execution="streamed",
                                  topology=Topology.flat(8),
                                  factions=FactionSpec(5, 2, 5, seed=2)))


def test_plan_rejects_bad_factions():
    with pytest.raises(ValueError, match="unknown faction layout"):
        api.plan(PBA_SPEC.replace(factions="rings"))
    with pytest.raises(ValueError, match="covers"):
        api.plan(PBA_SPEC.replace(factions=hub_factions(4)))


def test_plan_rejects_int32_overflow():
    with pytest.raises(ValueError, match="int32"):
        api.plan(GraphSpec(model="pk", levels=20))


# --- plan inspection ---------------------------------------------------------

def test_plan_describe_contents():
    pl = api.plan(PBA_SPEC.replace(pair_capacity=16, exchange_rounds=4))
    text = pl.describe()
    assert pl.topology.label in text
    assert "P = lp*D" in text and "8 * 1 = 8" in text
    assert "pair_capacity=16" in text and "rounds=4" in text
    assert "C_r=4" in text
    assert "bytes:" in text
    assert pl.requested_edges == 8 * 100 * 3
    assert pl.num_vertices == 800


def test_plan_describe_streamed_bytes():
    """Streamed plans report the streaming working set — per-round block
    bytes and the overlap double-buffer — not the host-path numbers (the
    describe() fix: a sharded-streamed plan used to print the host
    stream's byte estimates)."""
    spec = PBA_SPEC.replace(execution="streamed", pair_capacity=16,
                            exchange_rounds=4, topology=Topology.flat(1))
    pl = api.plan(spec)
    assert pl.executor == "pba_stream_sharded"
    block_cap = min(300, 8 * pl.round_capacity)  # min(E, P * C_r)
    assert pl.block_bytes == 8 * 8 * block_cap
    assert pl.overlap_bytes == 2 * pl.block_bytes
    assert pl.host_bytes == 2 * pl.block_bytes  # gather + write-back copy
    # per-device resident set scales with lp, not with the host edge list
    assert pl.device_bytes == 4 * 8 * (3 * 300 + 2 * 300 + 8
                                       + 2 * 8 * pl.round_capacity
                                       + 2 * block_cap)
    text = pl.describe()
    assert "block ~" in text and "overlap buffer ~" in text
    off = api.plan(spec.replace(overlap=False))
    assert off.overlap_bytes == 0
    assert off.host_bytes == off.block_bytes
    assert "overlap off" in off.describe()
    # host-driven streamed plans still report their block size, no overlap
    host_pl = api.plan(spec.replace(topology=Topology.host()))
    assert host_pl.executor == "pba_stream"
    assert host_pl.block_bytes > 0 and host_pl.overlap_bytes == 0
    # non-streamed plans carry no streaming estimates
    shot = api.plan(PBA_SPEC.replace(execution="host"))
    assert shot.block_bytes == 0 and shot.overlap_bytes == 0
    assert "block ~" not in shot.describe()


def test_plan_is_pure_resolution():
    """Planning the paper-scale preset must not allocate or compile
    anything — it is a capacity-planning tool."""
    pl = api.plan(api.preset("paper_1b_5b"))
    assert pl.requested_edges == 5_000_000_000
    assert pl.num_vertices == 1_000_000_000
    assert pl.execution == "streamed"
    assert pl.device_bytes > 0 and pl.host_bytes > 0


def test_presets_all_plan():
    for name in api.PRESETS:
        pl = api.plan(api.preset(name))
        assert pl.describe(), name
    with pytest.raises(ValueError, match="unknown preset"):
        api.preset("nope")
    # overrides apply on top
    spec = api.preset("paper_smoke", seed=11, sink="shards", out_dir="/d")
    assert spec.seed == 11 and spec.out_dir == "/d"


def test_generate_accepts_spec_or_plan():
    res1 = api.generate(PK_SPEC.replace(execution="host"))
    res2 = api.generate(api.plan(PK_SPEC.replace(execution="host")))
    _assert_bit_equal(res1.edges, res2.edges)


# --- spec digest -------------------------------------------------------------

def test_spec_digest_sensitivity():
    base = PBA_SPEC
    assert base.digest() == PBA_SPEC.digest()
    assert base.digest() != base.replace(seed=6).digest()
    assert base.digest() != base.replace(pair_capacity=16).digest()
    # execution details are excluded: host/sharded/auto route the same
    # bits (the parity suite pins it), and out_dir/sink only say where
    # they land — a resume across execution modes must not be rejected
    assert base.digest() == base.replace(out_dir="/elsewhere").digest()
    assert base.digest() == base.replace(execution="host").digest()
    assert base.digest() == base.replace(sink="shards", out_dir="/d",
                                         num_shards=4).digest()
    # overlap is pure scheduling — never part of the graph's identity
    assert base.digest() == base.replace(overlap=False).digest()


def test_spec_digest_hashes_large_jax_arrays_by_content():
    """Array-likes are fingerprinted by content, never by repr — a str()
    fallback truncates large arrays and collides different graphs."""
    import jax.numpy as jnp
    from repro.core import SeedGraph
    from repro.core.spec import spec_digest
    u = np.zeros(5000, np.int32)
    v = np.arange(5000, dtype=np.int32) % 5000
    s_np = SeedGraph(u, v, 5000)
    s_jnp = SeedGraph(jnp.asarray(u), jnp.asarray(v), 5000)
    assert spec_digest(s_np) == spec_digest(s_jnp)
    v2 = v.copy()
    v2[2500] += 1  # middle element: invisible to a truncated repr
    assert spec_digest(s_np) != spec_digest(SeedGraph(u, v2, 5000))
    with pytest.raises(TypeError, match="canonicalize"):
        spec_digest(object())


def test_non_streamed_shard_sink_resumes_across_execution_modes(tmp_path):
    """An interrupted host-execution shard write must be resumable by a
    sharded-execution rerun of the same spec (bit-identical graph, same
    spec digest — execution mode is not graph identity)."""
    import json
    import os
    spec = PBA_SPEC.replace(execution="host", sink="shards",
                            out_dir=str(tmp_path), num_shards=4)
    api.generate(spec)
    man_path = tmp_path / "manifest.json"
    man = json.loads(man_path.read_text())
    man["complete"] = [i for i in man["complete"] if i != 1]
    del man["counts"]["1"]
    man_path.write_text(json.dumps(man))
    os.remove(tmp_path / "shard_00001.npz")
    res = api.generate(spec.replace(execution="sharded",
                                    topology=Topology.flat(1)))
    assert sorted(res.manifest["complete"]) == [0, 1, 2, 3]
