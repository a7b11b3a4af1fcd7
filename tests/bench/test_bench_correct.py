"""The comparison that decides ``correct``, on the CPU at small sizes.

The plain references agree with the generators, their controls (one
precision lower) do not, and a run whose timed path is broken underneath
comes out not correct: an edge altered where it is made, half of a
graph's edges left out, the exchange left out, a round that returns the
same block every time, the edges of a graph put out in another order.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

import bench_tiny
from bench import harness
from bench.edges import combine, fingerprint, to_host
from repro.core import stream as stream_lib
from repro.kernels import ops as kops
from repro.runtime import blocking

SEEDS = (0, 7, 2**31 + 12345)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _cell(root, workload):
    return harness.resolve(root, workload)


@pytest.mark.parametrize("workload", ["pba_table1.memory",
                                      "rmat_graph500.memory"])
@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_the_generator(root, workload, seed):
    from repro import api
    cell = _cell(root, workload)
    ref = cell.reference()
    g, _ = harness.one_graph(api, harness.graph_spec(cell, seed),
                             ref.ORDERED)
    assert g.whole
    assert to_host(g.fingerprint) == tuple(ref.reference(cell.config, seed))


@pytest.mark.parametrize("seed", SEEDS)
def test_pba_control_differs(root, seed):
    cell = _cell(root, "pba_table1.memory")
    ref = cell.reference()
    assert ref.reference(cell.config, seed, control=True) \
        != ref.reference(cell.config, seed)


def test_rmat_control_differs(tmp_path):
    """float32 thresholds move about 121 of 2**32 draws per level: at
    scale 18 (2**22 edges, 18 levels) about 2 edges a graph."""
    root = bench_tiny.make_root(tmp_path, rmat_scale=18)
    cell = _cell(root, "rmat_graph500.memory")
    ref = cell.reference()
    assert ref.thresholds(0.57, 0.19, 0.19) == (2448131358, 3264175144,
                                                4080218931)
    assert ref.thresholds(0.57, 0.19, 0.19, control=True) == (
        2448131328, 3264175104, 4080218880)
    assert ref.reference(cell.config, 7, control=True) \
        != ref.reference(cell.config, 7)


def test_fingerprint_is_blockwise_and_order_sensitive_when_ordered(
        monkeypatch):
    from bench import edges
    u = jnp.arange(10, dtype=jnp.int32)
    v = (u * 7) % 10
    whole = to_host(fingerprint(u, v, ordered=True))
    parts = [to_host(edges.block_fingerprint(u[i:i + 4], v[i:i + 4], i,
                                             True))
             for i in range(0, 10, 4)]
    assert combine(parts) == whole
    # Blocks of 4 over 11 edges: the last block overlaps the one before.
    monkeypatch.setattr(edges, "BLOCK", 4)
    u11 = jnp.arange(11, dtype=jnp.int32)
    v11 = (u11 * 5) % 11
    for ordered in (True, False):
        assert to_host(fingerprint(u11, v11, ordered=ordered)) == to_host(
            edges.block_fingerprint(u11, v11, 0, ordered))
    swapped = to_host(fingerprint(u[::-1], v[::-1], ordered=True))
    assert swapped != whole
    assert to_host(fingerprint(u[::-1], v[::-1], ordered=False)) \
        == to_host(fingerprint(u, v, ordered=False))
    padded = to_host(fingerprint(jnp.concatenate([u, -jnp.ones(3, jnp.int32)]),
                                 jnp.concatenate([v, jnp.zeros(3, jnp.int32)]),
                                 ordered=False))
    assert padded == to_host(fingerprint(u, v, ordered=False))
    assert padded[0] == 10


# --- faults underneath the timed path -----------------------------------------

def _bump_one(u, v):
    return u, v.at[(0,) * v.ndim].add(1)


def _pba_altered(mp):
    real = kops.band_compact
    mp.setattr(kops, "band_compact",
               lambda u, v, band, cap: _bump_one(*real(u, v, band, cap)))


def _pba_half_left_out(mp):
    real = stream_lib.PBAShardedStream.gather_block

    def half(self, handle):
        src, dst = real(self, handle)
        return src[: len(src) // 2], dst[: len(dst) // 2]
    mp.setattr(stream_lib.PBAShardedStream, "gather_block", half)


def _pba_no_exchange(mp):
    mp.setattr(blocking, "transpose_payload", lambda buf, topo: buf)


def _pba_unchanged_state(mp):
    real = stream_lib.PBAShardedStream.dispatch_block
    mp.setattr(stream_lib.PBAShardedStream, "dispatch_block",
               lambda self, i: real(self, 0))


def _rmat_altered(mp):
    real = kops.cfree_expand
    mp.setattr(kops, "cfree_expand",
               lambda t, w, **kw: _bump_one(*real(t, w, **kw)))


def _rmat_half_left_out(mp):
    real = kops.cfree_expand

    def half(t, w, **kw):
        u, v = real(t, w, **kw)
        keep = jnp.arange(t.shape[0]) < t.shape[0] // 2
        return jnp.where(keep, u, -1), jnp.where(keep, v, -1)
    mp.setattr(kops, "cfree_expand", half)


def _rmat_unchanged_state(mp):
    mp.setattr(kops, "cfree_expand",
               lambda t, w, **kw: (jnp.zeros_like(t), jnp.zeros_like(t)))


FAULTS = {
    ("pba_table1.memory", "altered"): _pba_altered,
    ("pba_table1.memory", "half_left_out"): _pba_half_left_out,
    ("pba_table1.memory", "no_exchange"): _pba_no_exchange,
    ("pba_table1.memory", "unchanged_state"): _pba_unchanged_state,
    ("rmat_graph500.memory", "altered"): _rmat_altered,
    ("rmat_graph500.memory", "half_left_out"): _rmat_half_left_out,
    ("rmat_graph500.memory", "unchanged_state"): _rmat_unchanged_state,
}


@pytest.mark.parametrize("workload,fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, monkeypatch, workload,
                                          fault):
    # Programs traced before the fault would hide it: start clean.
    stream_lib._sharded_setup_fn.cache_clear()
    stream_lib._sharded_grant_fns.cache_clear()
    FAULTS[(workload, fault)](monkeypatch)
    try:
        rc, lines, err = bench_tiny.run(root, [
            "--workload", workload, "--seed", "11", "--seconds", "0.2",
            "--trace", "0"])
    finally:
        stream_lib._sharded_setup_fn.cache_clear()
        stream_lib._sharded_grant_fns.cache_clear()
    assert rc == 0, err[-3000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert line["checks"]["graphs_wrong"]["value"] >= 1


def test_sound_run_is_correct(root):
    line = bench_tiny.result(root, "pba_table1.memory", seed=11)
    assert line["correct"] is True
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert os.path.isdir(os.path.join(root, "bench"))


@pytest.mark.parametrize("seed", SEEDS)
def test_every_graph_takes_the_run_seed(root, seed):
    for workload in ("pba_table1.memory", "rmat_graph500.memory"):
        assert harness.graph_spec(_cell(root, workload), seed).seed == seed


def test_reordered_graph_is_not_correct(root, monkeypatch):
    """The same edges in another order than the run's first graph: the
    multiset agrees with the reference, the order check does not."""
    from repro import api
    real = api._edges_from_stream
    calls = []

    def reordered(stream, overlap=True):
        edges, stats = real(stream, overlap)
        calls.append(1)
        if len(calls) > 1:
            edges = dataclasses.replace(edges, src=edges.src[::-1],
                                        dst=edges.dst[::-1])
        return edges, stats
    monkeypatch.setattr(api, "_edges_from_stream", reordered)
    rc, lines, err = bench_tiny.run(root, [
        "--workload", "pba_table1.memory", "--seed", "13", "--seconds",
        "0.2", "--trace", "0"])
    assert rc == 0, err[-3000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert line["checks"]["graphs_wrong"]["value"] == 0
    assert line["checks"]["graphs_reordered"]["value"] == line["attempted"]
