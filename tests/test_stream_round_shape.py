"""Streamed PBA rounds sized from the demand the set-up measures.

At P=64 the faction-size heuristic C = 8E/min_s falls short of the busiest
(requester, provider) pair on some seeds, so C_r = ceil(C/R) would need
more than the configured R rounds. Both stream drivers raise C to that
pair's demand instead, and size each round's compacted block from the
largest band a round carries, rounded up to a granule of columns. The
edges do not change: an endpoint's pool slot is fixed by the pair's offset
and the request rank, whatever C_r is. At these small sizes the granule
exceeds every band, so the tests that run the streams set it to one column
and the blocks are exactly as wide as the widest band.
"""
import dataclasses

import numpy as np
import pytest

from repro import api
from repro.api import GraphSpec
from repro.core import (FactionSpec, PBAConfig, generate_pba,
                        make_factions, stream_to_shards)
from repro.core import stream as stream_lib
from repro.core.pba import (_derived_pair_capacity, exchange_memory_cap,
                            stream_block_capacity)
from repro.core.storage import ShardWriter
from repro.core.stream import (PBAShardedStream, PBAStream,
                               stream_round_shape)
from repro.runtime import Topology, streaming

R = 8
#: P=64 ranks in the front door's default layout, 200 vertices x 5 edges.
TABLE = make_factions(64, FactionSpec(32, 2, 32, seed=1))
#: Seed 0's busiest pair asks 104 endpoints where the heuristic gives
#: C=86, C_r=11: ten rounds at the heuristic's capacity.
CFG = PBAConfig(vertices_per_proc=200, edges_per_vertex=5, exchange_rounds=R,
                seed=0)


def _heuristic_rounds(cfg, demand):
    c_r = streaming.round_capacity(_derived_pair_capacity(cfg, TABLE), R)
    return c_r, streaming.rounds_needed(int(demand.max()), c_r)


def _demand_of(sh):
    """The (requester, provider) demand a sharded stream's set-up left on
    the device."""
    return np.asarray(sh._recv).reshape(sh.num_procs, sh.num_procs).T


def _blocks(stream):
    return [stream.block(i) for i in range(stream.num_blocks)]


def _multiset(blocks, n):
    src = np.concatenate([b[0] for b in blocks]).astype(np.int64)
    dst = np.concatenate([b[1] for b in blocks])
    return np.sort(src * n + dst)


@pytest.fixture(scope="module")
def streams():
    """The host and the device-sharded stream of CFG, demand-sized urns,
    blocks as wide as the widest band."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(stream_lib, "BLOCK_GRANULE", 1)
        return (PBAStream(CFG, TABLE),
                PBAShardedStream(CFG, TABLE, topology=Topology.flat(1)))


def test_drivers_derive_one_demand_sized_shape(streams):
    host, sh = streams
    demand = _demand_of(sh)
    old_c_r, old_rounds = _heuristic_rounds(CFG, demand)
    assert old_rounds > R                      # the heuristic is short here
    assert host.shape == sh.shape
    assert (sh.pair_capacity, sh.round_cap, sh.num_blocks) == (
        sh.shape.pair_capacity, sh.shape.round_cap, sh.shape.num_blocks)
    assert sh.shape.demand_sized
    assert sh.pair_capacity == int(demand.max())
    assert sh.round_cap > old_c_r
    assert sh.num_blocks <= R
    band = np.minimum(demand, sh.round_cap).sum(1).max()
    assert sh.shape.block_cap == band < stream_block_capacity(
        CFG.edges_per_proc, 64, sh.round_cap)
    assert sh.meta() == host.meta()
    assert sh.meta()["round_capacity"] == sh.round_cap


def test_drivers_emit_bit_identical_blocks(streams):
    host, sh = streams
    for (hu, hv), (su, sv) in zip(_blocks(host), _blocks(sh)):
        np.testing.assert_array_equal(su, hu)
        np.testing.assert_array_equal(sv, hv)


def test_parity_mode_edges_equal_the_heuristic_capacity_graphs():
    """auto_capacity=False: the demand-sized stream, the stream pinned at
    the heuristic's capacity and ``generate_pba`` on the host emit one edge
    multiset, in fewer rounds for the first."""
    n = 64 * CFG.vertices_per_proc
    sized = PBAStream(CFG, TABLE, auto_capacity=False)
    pinned_cfg = dataclasses.replace(
        CFG, pair_capacity=_derived_pair_capacity(CFG, TABLE))
    pinned = PBAStream(pinned_cfg, TABLE, auto_capacity=False)
    assert sized.num_blocks <= R < pinned.num_blocks
    e_h, st_h = generate_pba(CFG, TABLE, Topology.host())
    assert st_h.dropped_edges == 0 and st_h.exchange_rounds > R
    s0, d0 = e_h.to_numpy()
    want = np.sort(s0.reshape(-1).astype(np.int64) * n + d0.reshape(-1))
    np.testing.assert_array_equal(_multiset(_blocks(sized), n), want)
    np.testing.assert_array_equal(_multiset(_blocks(pinned), n), want)


def _demand(seed, edges):
    """A (requester, provider) demand of 64 rows of ``edges`` endpoints,
    heavy-tailed over providers as the phase-1 urn makes it."""
    rng = np.random.default_rng(seed)
    w = rng.pareto(1.0 + seed % 3, size=(64, 64)) + 1e-3
    return rng.multinomial(edges, w / w.sum(1, keepdims=True))


#: The one-chip benchmark cell's shape: E=100,000, heuristic C=8,602.
CELL = dataclasses.replace(CFG, vertices_per_proc=20_000)


@pytest.mark.parametrize("seed", range(24))
def test_never_more_rounds_than_the_heuristic(seed):
    demand = _demand(seed, CELL.edges_per_proc)
    shape = stream_round_shape(CELL, TABLE, demand)
    old_c_r, old_rounds = _heuristic_rounds(CELL, demand)
    assert old_c_r == 1_076
    assert shape.round_cap >= old_c_r
    assert shape.num_blocks <= old_rounds
    assert shape.num_blocks <= R
    assert shape.demand_sized == (
        demand.max() > _derived_pair_capacity(CELL, TABLE))
    # block_cap holds round 0's largest band, no round's band is wider,
    # and it is that band rounded up to the granule, within min(E, P*C_r)
    bands = [streaming.round_window(demand, r, shape.round_cap).sum(1).max()
             for r in range(shape.num_blocks)]
    assert bands[0] == max(bands) <= shape.block_cap
    bound = stream_block_capacity(CELL.edges_per_proc, 64, shape.round_cap)
    granule = stream_lib.BLOCK_GRANULE
    assert shape.block_cap == min(-(-bands[0] // granule) * granule, bound)


def test_the_memory_cap_bounds_the_raise(monkeypatch):
    demand = _demand(0, CFG.edges_per_proc)
    cap = 96
    assert _derived_pair_capacity(CFG, TABLE) < cap < demand.max()
    monkeypatch.setattr(stream_lib, "exchange_memory_cap",
                        lambda num_procs, rounds: cap)
    shape = stream_round_shape(CFG, TABLE, demand)
    assert shape.pair_capacity == cap and shape.demand_sized
    assert shape.round_cap == streaming.round_capacity(cap, R)
    assert exchange_memory_cap(64, R) > demand.max()


def test_a_pinned_pair_capacity_is_honoured(monkeypatch):
    """An explicit pair_capacity keeps C, C_r, the rounds, the meta and
    every block as the heuristic-free derivation gives them; the blocks,
    as wide as the widest band, equal the round program's at the old
    block bound min(E, P*C_r)."""
    monkeypatch.setattr(stream_lib, "BLOCK_GRANULE", 1)
    cfg = dataclasses.replace(CFG, pair_capacity=40)
    sh = PBAShardedStream(cfg, TABLE, topology=Topology.flat(1))
    demand = _demand_of(sh)
    c_r = streaming.round_capacity(40, R)
    assert (sh.pair_capacity, sh.round_cap) == (40, c_r)
    assert sh.num_blocks == streaming.rounds_needed(int(demand.max()), c_r)
    assert sh.num_blocks > R
    assert sh.meta()["round_capacity"] == c_r
    assert sh.shape.demand_sized is False
    old_cap = stream_block_capacity(cfg.edges_per_proc, 64, c_r)
    assert sh.shape.block_cap < old_cap
    _, old_round = stream_lib._sharded_grant_fns(
        cfg, 64, sh.topology, sh.urn_budget, c_r, old_cap)
    for i in range(sh.num_blocks):
        u, v = sh.block(i)
        old_u, old_v = (np.asarray(a).reshape(-1) for a in old_round(
            np.int32(i), sh._a, sh._occ, sh._recv, sh._pool)[:2])
        keep = (old_u >= 0) & (old_v >= 0)
        np.testing.assert_array_equal(u, old_u[keep])
        np.testing.assert_array_equal(v, old_v[keep])
    host = PBAStream(cfg, TABLE)
    for (hu, hv), (su, sv) in zip(_blocks(host), _blocks(sh)):
        np.testing.assert_array_equal(su, hu)
        np.testing.assert_array_equal(sv, hv)


def test_a_manifest_at_the_heuristic_round_capacity_is_refused(tmp_path,
                                                              streams):
    """Shards written at the heuristic's C_r hold the same edges in other
    blocks: resuming them under the demand-sized stream fails loudly."""
    _, sh = streams
    old_c_r, _ = _heuristic_rounds(CFG, _demand_of(sh))
    old = ShardWriter(str(tmp_path), sh.num_vertices, sh.num_blocks,
                      meta={**sh.meta(), "round_capacity": old_c_r})
    old.write_block(0, *sh.block(0))
    with pytest.raises(ValueError, match="meta mismatch"):
        stream_to_shards(sh, str(tmp_path))


def test_build_span_and_stats_carry_the_shape(monkeypatch):
    """The grant programs' build span records round_cap, block_cap and
    demand_sized; ``GenStats.pair_capacity`` is the C the stream used, of
    which ``api.plan``'s heuristic is a floor."""
    seen = []
    span = stream_lib.spans.span

    def record(name, **args):
        seen.append((name, args))
        return span(name, **args)

    monkeypatch.setattr(stream_lib.spans, "span", record)
    stream_lib._sharded_grant_fns.cache_clear()
    spec = GraphSpec(model="pba", procs=64, vertices_per_proc=200,
                     edges_per_vertex=5, exchange_rounds=R, seed=0,
                     execution="streamed", topology=Topology.flat(1))
    pl = api.plan(spec)
    res = api.generate(pl)
    builds = [a for n, a in seen if n == "repro.build"
              and a["program"] == "pool_body,round_body"]
    assert len(builds) == 1
    b = builds[0]
    assert b["demand_sized"] == 1
    assert b["round_cap"] == streaming.round_capacity(
        res.stats.pair_capacity, R) > pl.round_capacity
    assert 0 < b["block_cap"] <= CFG.edges_per_proc
    assert res.stats.pair_capacity > pl.pair_capacity
    assert res.stats.exchange_rounds <= R
    assert res.stats.dropped_edges == 0
