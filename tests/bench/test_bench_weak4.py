"""The four-chip PBA cell, ``pba_table1.weak4``, on the CPU: the harness
runs it on four host devices at a tiny size and finds it correct, the
shipped configuration's faction layout is the generator's own and its
static urn budget covers the demand, the control differs, and the cell's
three per-layer readers agree with hand-made traces."""
from __future__ import annotations

import gzip
import json
import os
import re

import numpy as np
import pytest

import bench_tiny
from bench import harness, tracereduce
from bench.tracereduce import Event, Trace
from helpers import run_with_devices

REPO = bench_tiny.REPO
CELL = "pba_table1.weak4"
CONFIG = "pba_table1_weak4"
TINY_PROCS, TINY_VERTICES = 16, 500
SEEDS = (0, 7, 2**31 + 12345)
PLANES = tuple(f"/device:TPU:{i}" for i in range(4))


def _metric(name):
    return harness.load_module(os.path.join(REPO, "bench", "metrics",
                                            f"{name}.py"), f"t_{name}")


def _default_layout(p: int) -> dict:
    """The generator's default faction layout for ``p`` ranks, as
    ``api._resolve_factions`` draws it."""
    return {"num_factions": max(p // 2, 1), "min_size": min(2, p),
            "max_size": min(max(p // 2, 2), p), "seed": 1}


def make_tiny_root(dst) -> str:
    """bench_tiny's root with the four-chip cell cut to 16 ranks x 500
    vertices and the reference told the default layout for 16 ranks."""
    root = bench_tiny.make_root(dst)
    bench_tiny.edit_config(root, CONFIG, procs=TINY_PROCS,
                           vertices_per_proc=TINY_VERTICES)
    path = os.path.join(root, "bench", "configs", f"{CONFIG}.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["reference_params"]["factions"] = _default_layout(TINY_PROCS)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


def test_tiny_cell_runs_correct_on_four_host_devices(tmp_path):
    """The whole harness path over flat(4): plan, sharded stream, the
    all_to_all of every round, the four-device gather, the reference."""
    out = run_with_devices(f"""
        import json, sys
        sys.path[:0] = [{os.path.dirname(__file__)!r},
                        {os.path.dirname(os.path.dirname(__file__))!r}]
        import test_bench_weak4 as t
        import bench_tiny
        root = t.make_tiny_root({str(tmp_path / "root")!r})
        rc, lines, err = bench_tiny.run(root, [
            "--workload", {CELL!r}, "--seed", "3000000019",
            "--seconds", "0.2", "--trace", "1"])
        assert rc == 0, err[-3000:]
        print(lines[-1])
        """, 4, extra_env={"JAX_PLATFORMS": "cpu"})
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["metrics"]["pba_exchange_rounds"]["value"] > 0


def test_shipped_layout_is_the_generators_default():
    """The reference's faction table for the shipped configuration equals
    the one the front door draws for its spec (256 ranks: 128 factions of
    2 to 128 ranks, layout seed 1)."""
    from repro import api
    from repro.api import GraphSpec
    from repro.core import factions
    cell = harness.resolve(REPO, CELL)
    spec = cell.config["spec"]
    f = cell.config["reference_params"]["factions"]
    assert f == _default_layout(spec["procs"]) == {
        "num_factions": 128, "min_size": 2, "max_size": 128, "seed": 1}
    ref = cell.reference()
    table, s = ref.pba.faction_table(spec["procs"], f["num_factions"],
                                     f["min_size"], f["max_size"],
                                     f["seed"])
    want = factions.make_factions(spec["procs"], factions.FactionSpec(
        128, 2, 128, seed=1))
    drawn = api._resolve_factions(GraphSpec(**spec, seed=1))
    for t in (want, drawn):
        assert np.array_equal(table, t.procs) and np.array_equal(s, t.s)
    assert int(s.min()) == 1705


@pytest.mark.parametrize("seed", SEEDS)
def test_static_budget_covers_the_demand(seed):
    """At the shipped size every provider's demand fits the static urn
    budget B = 2E with room: nothing is dropped, so the graph is whole."""
    cell = harness.resolve(REPO, CELL)
    ref = cell.reference()
    _, _, counts = ref.pba._tags(cell.config, seed, False)
    demand = int(np.asarray(counts).sum(axis=0).max())
    assert ref.budget(cell.config) == 200_000
    assert 100_000 < demand < 0.8 * ref.budget(cell.config)


def test_static_reference_refuses_a_demand_sized_spec():
    cell = harness.resolve(REPO, CELL)
    auto = dict(cell.config, spec=dict(cell.config["spec"],
                                       auto_capacity=True))
    with pytest.raises(ValueError, match="auto_capacity"):
        cell.reference().budget(auto)


@pytest.mark.parametrize("seed", SEEDS)
def test_tiny_control_differs(tmp_path, seed):
    cell = harness.resolve(make_tiny_root(tmp_path), CELL)
    ref = cell.reference()
    assert ref.reference(cell.config, seed, control=True) \
        != ref.reference(cell.config, seed)


# --- the cell's readers on made-up traces -----------------------------------

A2A = ("%all_to_all.11 = s32[4,1,241664]{2,1,0:T(1,128)S(1)} all-to-all("
       "s32[4,1,241664]{2,1,0:T(1,128)S(1)} %reshape.142), channel_id=1, "
       "replica_groups={{0,1,2,3}}, dimensions={0}")
AR_START = ("%all-reduce-start = s32[]{:T(128)} all-reduce-start("
            "s32[]{:T(128)} %reduce.3), channel_id=2")
AR_DONE = ("%all-reduce-done = s32[]{:T(128)} all-reduce-done("
           "s32[]{:T(128)} %all-reduce-start)")
FUSION = "%fusion.4 = s32[64,100000]{1,0} fusion(s32[] %all-reduce-done)"


def _graph(rounds):
    return harness.Graph(requested=10, emitted=10, dropped=0, rounds=rounds,
                         seconds=0.0)


def _run(trace, graphs=(), span=(0, 10_000)):
    return harness.Run(cell=None, plan=None, setup_s=0, window_s=1,
                       graphs=list(graphs), compile_s=0, peak_bytes=None,
                       peaks=None, trace=trace, span=span)


def _trace(collectives=True):
    """A window [0, 10000) ns with two graphs on four chips. Per chip: a
    round program [1000, 3000) and [6000, 7000), plus one after the
    window; an all-to-all [1000, 1400); an async all-reduce whose start
    [2000, 2300) and done [2200, 2600) overlap (union 600); a fusion that
    reads the all-reduce's result, not a collective; chip i's ops are
    shifted by 10 * i ns, and one all-to-all straddles the window's end
    (9900, 10100), so 100 ns of it count."""
    ops, modules = {}, {}
    for i, plane in enumerate(PLANES):
        d = 10 * i
        evs = [Event(FUSION, 3000 + d, 3500 + d)]
        if collectives:
            evs += [Event(A2A, 1000 + d, 1400 + d),
                    Event(AR_START, 2000 + d, 2300 + d),
                    Event(AR_DONE, 2200 + d, 2600 + d),
                    Event(A2A, 9900, 10_100)]
        ops[plane] = sorted(evs, key=lambda e: e.start_ns)
        modules[plane] = [Event("jit_round_body(7)", 1000 + d, 3000 + d),
                          Event("jit_setup_body(8)", 4000 + d, 5000 + d),
                          Event("jit_round_body(7)", 6000 + d, 7000 + d),
                          Event("jit_round_body(7)", 12_000, 13_000)]
    host = [Event("window", 0, 10_000),
            Event("graph", 100, 5000), Event("graph", 5000, 9990),
            Event("graph", 10_500, 14_000)]
    return Trace(ops=ops, modules=modules, host=host)


def test_exchange_rounds_is_the_mean_over_the_window_graphs():
    read = _metric("pba_exchange_rounds").read
    assert read(_run(None, [_graph(27), _graph(30)])) == 28.5
    assert read(_run(None, [_graph(8)])) == 8.0
    assert read(_run(None, [])) is None


def test_round_chip_ms_is_round_ms_over_the_chips():
    tr = _trace()
    run = _run(tr)
    # 3000 ns of rounds per chip in the window, 4 chips, 2 graphs.
    assert _metric("pba_round_ms").read(run) == pytest.approx(
        4 * 3000 / 2 / 1e6)
    assert _metric("pba_round_chip_ms").read(run) == pytest.approx(
        3000 / 2 / 1e6)
    one = Trace(ops={PLANES[0]: tr.ops[PLANES[0]]},
                modules={PLANES[0]: tr.modules[PLANES[0]]}, host=tr.host)
    assert _metric("pba_round_chip_ms").read(_run(one)) == \
        _metric("pba_round_ms").read(_run(one))
    assert _metric("pba_round_chip_ms").read(_run(None)) is None


def test_exchange_ms_counts_an_async_pair_once():
    """Per chip: all-to-all 400 ns, the start/done pair's union 600 ns,
    the straddling all-to-all's 100 ns in the window: 1100 ns a chip
    over 2 graphs; the fusion that reads the all-reduce is not counted."""
    ms = _metric("pba_exchange_ms").read(_run(_trace()))
    assert ms == pytest.approx(1100 / 2 / 1e6)


@pytest.mark.parametrize("case", ["no_collective", "no_device_plane",
                                  "no_trace", "no_graph"])
def test_exchange_ms_without_a_collective_is_zero_or_none(case):
    read = _metric("pba_exchange_ms").read
    if case == "no_collective":
        assert read(_run(_trace(collectives=False))) == 0.0
    elif case == "no_device_plane":
        tr = _trace()
        assert read(_run(Trace(ops={}, modules={}, host=tr.host))) is None
    elif case == "no_trace":
        assert read(_run(None)) is None
    else:
        tr = _trace()
        assert read(_run(tr, span=(10_000, 10_400))) is None


# --- the cell's readers on a trace recorded on four v5e chips ---------------

#: What the traced run printed: `bench/run.py --workload pba_table1.weak4
#: --seed 2654435769 --seconds 51 --trace 1` on four v5e chips, 5 graphs
#: of 27 exchange rounds in the window, with the demand-sized urn budget
#: the configuration had then (2^17 for this seed); the readers do not
#: look at the urn.
RECORDED = {"graphs": 5, "rounds": 27, "pba_exchange_rounds": 27.0,
            "pba_round_chip_ms": 7785.99629905,
            "pba_exchange_ms": 166.334295}


def _recorded(tmp_path):
    """The run's trace cut to what the three readers read: every device
    plane's ``XLA Modules`` line whole, the collective operations of its
    ``XLA Ops`` line, and the host's ``window``, ``graph`` and
    ``repro.generate`` spans."""
    src = os.path.join(os.path.dirname(__file__), "traces",
                       f"{CELL}.xplane.pb.gz")
    dst = tmp_path / f"{CELL}.xplane.pb"
    with gzip.open(src) as f:
        dst.write_bytes(f.read())
    tr = tracereduce.load(str(dst))
    graphs = [_graph(RECORDED["rounds"])] * RECORDED["graphs"]
    return _run(tr, graphs, span=tracereduce.window(tr))


@pytest.mark.parametrize("name", ["pba_exchange_rounds", "pba_round_chip_ms",
                                  "pba_exchange_ms"])
def test_recorded_trace_reads_what_the_chip_printed(tmp_path, name):
    run = _recorded(tmp_path)
    assert _metric(name).read(run) == pytest.approx(RECORDED[name],
                                                    rel=1e-12)


def test_recorded_trace_has_collectives_on_every_chip(tmp_path):
    """Each of the four device planes ran one set-up all_to_all per graph
    and one round all_to_all per exchange round, and every round program
    holds exactly one of them."""
    run = _recorded(tmp_path)
    tr, (lo, hi) = run.trace, run.span
    assert list(tr.ops) == list(PLANES)
    rx = re.compile(_metric("pba_exchange_ms").COLLECTIVE)
    n, r = RECORDED["graphs"], RECORDED["rounds"]
    for plane in PLANES:
        hits = [e for e in tr.ops[plane]
                if lo <= e.start_ns < hi and rx.search(e.name)]
        assert len(hits) == n * (1 + r)
        assert all(" all-to-all(" in e.name for e in hits)
        rounds = [m for m in tr.modules[plane] if lo <= m.start_ns < hi
                  and m.name.startswith("jit_round_body(")]
        assert len(rounds) == n * r
        for m in rounds:
            assert sum(m.start_ns <= e.start_ns < m.end_ns
                       for e in hits) == 1
