"""The Pallas hot path of the stream round program: dispatch, parity,
autotuning, fallback observability, and the perf-baseline plumbing."""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.pba import pba_stream_round_block, occurrence_rank, PBAConfig
from repro.kernels import dispatch, ref
from repro.runtime import Topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round_inputs(seed=0, lp=4, e_local=12, k=2, round_cap=3, t_cap=24):
    """Synthetic but in-contract round-program state on the host topology
    (lp == P): processor tags, occurrence ranks, transposed demand, pools."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, lp, (lp, e_local)), jnp.int32)
    occ = jax.vmap(occurrence_rank)(a)
    counts = jnp.stack([ref.histogram_ref(row, lp) for row in a])
    recv_counts = counts.T  # host-topology transpose
    pool = jnp.asarray(rng.integers(0, lp * (e_local // k),
                                    (lp, e_local + t_cap)), jnp.int32)
    ranks = jnp.arange(lp, dtype=jnp.int32)
    cfg = PBAConfig(vertices_per_proc=e_local // k, edges_per_vertex=k,
                    exchange_rounds=2, seed=3)
    return a, occ, recv_counts, pool, ranks, cfg


def _run_round(r, mode):
    a, occ, recv_counts, pool, ranks, cfg = _round_inputs()
    lp, e_local = a.shape
    round_cap, t_cap, block_cap = 3, 24, min(e_local, lp * 3)
    with dispatch.forced_mode(mode):
        u, v, counts = pba_stream_round_block(
            jnp.int32(r), a, occ, recv_counts, pool, ranks, cfg, lp,
            round_cap, t_cap, block_cap, Topology.host())
    return np.asarray(u), np.asarray(v), np.asarray(counts)


@pytest.mark.parametrize("r", [0, 1, 3])
def test_round_program_interpret_matches_off(r):
    """The kernels compute the same permutation of the same values: the
    full round program is bit-identical between the Pallas hot path
    (interpret mode) and the historical jnp formulation."""
    got = _run_round(r, "interpret")
    want = _run_round(r, "off")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_round_program_counts_match_band():
    """The histogram output is the per-provider band census: its total is
    the number of compacted band slots (the gather_block consistency
    check)."""
    u, v, counts = _run_round(0, "interpret")
    assert counts.sum() == (u >= 0).sum()


def _subjaxprs(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(v, ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, Jaxpr):
        return [v]
    if isinstance(v, (tuple, list)):
        return [j for x in v for j in _subjaxprs(x)]
    return []


def _count_pallas_eqns(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for param in eqn.params.values():
            n += sum(_count_pallas_eqns(j) for j in _subjaxprs(param))
    return n


def test_round_program_jaxpr_contains_pallas_calls():
    """Acceptance proxy for the TPU custom-calls: in kernel mode the round
    program's one pallas_call is the histogram census; the grant and band
    gathers and the band compaction are XLA on every backend."""
    a, occ, recv_counts, pool, ranks, cfg = _round_inputs()
    lp, e_local = a.shape
    with dispatch.forced_mode("interpret"):
        jaxpr = jax.make_jaxpr(
            lambda *args: pba_stream_round_block(
                *args, cfg, lp, 3, 24, min(e_local, lp * 3),
                Topology.host())
        )(jnp.int32(0), a, occ, recv_counts, pool, ranks)
    n = _count_pallas_eqns(jaxpr.jaxpr)
    assert n == 1, f"{n} pallas_call equations in the round program"


def test_round_program_off_mode_has_no_pallas_calls():
    a, occ, recv_counts, pool, ranks, cfg = _round_inputs()
    lp, e_local = a.shape
    with dispatch.forced_mode("off"):
        jaxpr = jax.make_jaxpr(
            lambda *args: pba_stream_round_block(
                *args, cfg, lp, 3, 24, min(e_local, lp * 3),
                Topology.host())
        )(jnp.int32(0), a, occ, recv_counts, pool, ranks)
    assert _count_pallas_eqns(jaxpr.jaxpr) == 0


def test_paper_smoke_stream_traces_without_fallback():
    """Tracing the paper_smoke spec's device-sharded round program in
    kernel mode records no kernel-fallback event: gather and compaction
    are XLA by design, not by fallback."""
    from helpers import run_with_devices
    code = """
        from repro import api
        from repro.api import GraphSpec
        from repro.kernels import ops
        from repro.launch.bench import compile_sharded_stream_round
        pl = api.plan(GraphSpec(model="pba", procs=8,
                                vertices_per_proc=2000, edges_per_vertex=4,
                                seed=7, execution="streamed"))
        assert pl.executor == "pba_stream_sharded", pl.executor
        fn, args = compile_sharded_stream_round(pl)
        fn.lower(*args)
        assert ops.fallback_counts() == {}, ops.fallback_counts()
        print("no-fallback")
    """
    out = run_with_devices(code, 8, {"REPRO_PALLAS": "interpret"})
    assert out.strip() == "no-fallback"


# --- dispatch autotuner ------------------------------------------------------

def test_autotune_feasibility_and_scoring():
    budget = dispatch.vmem_budget_bytes("tpu")
    cands = [{"b": 1}, {"b": 2}, {"b": 3}]
    # b=3 is infeasible; b=2 moves fewer bytes than b=1 -> picked
    vmem = lambda c: budget + 1 if c["b"] == 3 else c["b"]
    cost = lambda c: (0.0, 1e9 / c["b"], 1.0)
    assert dispatch.autotune("t", cands, vmem, cost) == {"b": 2}


def test_autotune_step_overhead_breaks_byte_ties():
    # equal traffic: the finer grid pays more per-step overhead
    cands = [{"steps": 10}, {"steps": 10000}]
    cost = lambda c: (0.0, 1e6, float(c["steps"]))
    got = dispatch.autotune("t", cands, lambda c: 64, cost)
    assert got == {"steps": 10}


def test_autotune_raises_when_nothing_fits():
    budget = dispatch.vmem_budget_bytes("tpu")
    with pytest.raises(ValueError, match="no candidate fits"):
        dispatch.autotune("t", [{"b": 1}], lambda c: budget + 1,
                          lambda c: (0.0, 0.0, 1.0))


def test_autotuned_plans_are_deterministic_and_feasible():
    from repro.kernels.band_compact import _tile_plan
    from repro.kernels.edge_resolve import _chunk_plan, slab_entries

    slab, dst = _chunk_plan("tpu", 4 * 2**20, 2**20)
    assert slab % 1024 == 0 and dst % 1024 == 0
    assert slab <= slab_entries("tpu", dst)
    assert _chunk_plan("tpu", 4 * 2**20, 2**20) == (slab, dst)
    t_in, t_out = _tile_plan("tpu", 16384, 4096)
    assert (t_in, t_out) == _tile_plan("tpu", 16384, 4096)
    assert 2 * 4 * (3 * t_in + 2 * t_out) + 4 * t_in * t_out \
        <= dispatch.vmem_budget_bytes("tpu")


# --- hlo_stats: hardware model + per-opcode aggregation ----------------------

def test_hardware_model_optimal_seconds_is_max_ratio():
    from repro.launch.hlo_stats import HardwareModel

    m = HardwareModel("toy", peak_flops=100.0, hbm_bw=10.0, ici_bw=1.0)
    assert m.optimal_seconds(1000.0, 10.0) == pytest.approx(10.0)
    assert m.optimal_seconds(10.0, 1000.0) == pytest.approx(100.0)
    assert m.optimal_seconds(10.0, 10.0, 50.0) == pytest.approx(50.0)


def test_opcode_stats_sum_to_program_totals():
    from repro.launch.hlo_stats import collect_hlo_costs, collect_opcode_stats

    fn = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    hlo = fn.lower(jnp.ones((64, 64), jnp.float32)).compile().as_text()
    totals = collect_hlo_costs(hlo)
    per_op = collect_opcode_stats(hlo)
    assert per_op, "no opcodes collected"
    assert sum(s.flops for s in per_op.values()) == pytest.approx(totals.flops)
    assert sum(s.bytes_accessed for s in per_op.values()) \
        == pytest.approx(totals.hbm_bytes)
    assert all(s.optimal_seconds >= 0 for s in per_op.values())


# --- GenStats fallback surfacing + committed bench baseline ------------------

def test_genstats_surfaces_fallback_counts(monkeypatch):
    from repro.core.graph import GenStats
    from repro.core.stream import stream_stats
    from repro.kernels import ops

    assert GenStats(1, 1, 0, 1).fallback_counts == {}
    monkeypatch.setattr(ops, "FALLBACK_EVENTS",
                        {"gather_oversize:le128": 2})

    class _S:
        requested_edges, num_vertices = 10, 5
        exchange_rounds, pair_capacity = 2, 4

    st = stream_stats(_S(), 9)
    assert st.fallback_counts == {"gather_oversize:le128": 2}
    st.fallback_counts["x"] = 1  # snapshot, not the live dict
    assert ops.fallback_counts() == {"gather_oversize:le128": 2}


def test_bench_baseline_fused_beats_jnp():
    """The committed round-program counts witness the routing: at every
    swept point the fused leg traced one kernel, the census (gathers and
    compaction are XLA glue), and its bytes are that glue plus the
    kernel's analytic traffic."""
    path = os.path.join(REPO, "BENCH_round_block.json")
    with open(path) as f:
        base = json.load(f)
    assert base["schema"] == 1 and base["sweep"]
    for entry in base["sweep"]:
        fused = entry["fused"]
        assert fused["kernel_calls"] == 1, entry["name"]
        assert fused["bytes_accessed"] == pytest.approx(
            fused["glue_bytes"] + fused["kernel_bytes"]), entry["name"]
        assert entry["fused_over_jnp_bytes"] == pytest.approx(
            fused["bytes_accessed"] / entry["jnp"]["bytes_accessed"])
