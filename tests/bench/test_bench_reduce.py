"""The trace reduction and the byte counts behind the roofline shares,
checked by hand on made-up traces and shapes, and on traces recorded on
the chip."""
from __future__ import annotations

import gzip
import os
import re

import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import harness, tracereduce
from bench.tracereduce import Event, Trace

REPO = bench_tiny.REPO
DEV = "/device:TPU:0"


def _metric(name):
    return harness.load_module(os.path.join(REPO, "bench", "metrics",
                                            f"{name}.py"), f"t_{name}")


def _trace():
    """A window [100, 1100) ns: two graphs; device ops at [150, 300),
    [250, 400) (overlapping), [700, 900) and one op outside the window."""
    ops = [Event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
                 150, 300),
           Event("%while.2 = (s32[4]{0}, pred[]) while((s32[4]{0}) %t)",
                 250, 400),
           Event('%vmap.1 = (s32[8,128]{1,0}, s32[8,128]{1,0}) custom-call('
                 's32[8,128]{1,0} %a, u32[4]{0} %w), '
                 'custom_call_target="tpu_custom_call"', 700, 900),
           Event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 1200, 1300)]
    modules = [Event("jit_setup_body(11)", 140, 410),
               Event("jit_round_body(22)", 690, 905)]
    host = [Event("window", 100, 1100),
            Event("graph", 110, 600), Event("plan", 110, 130),
            Event("generate", 130, 600),
            Event("np.asarray(jax.Array)", 420, 590),
            Event("graph", 600, 1090), Event("plan", 600, 640),
            Event("generate", 640, 1090),
            Event("backend_compile_and_load", 650, 690)]
    return Trace(ops={DEV: ops}, modules={DEV: modules}, host=host)


def test_window_busy_and_gaps():
    tr = _trace()
    lo, hi = tracereduce.window(tr)
    assert (lo, hi) == (100, 1100)
    assert tracereduce.merged(tr.ops[DEV], lo, hi) == [(150, 400),
                                                       (700, 900)]
    assert tracereduce.busy_ns(tr, DEV, lo, hi) == 450
    assert tracereduce.mean_busy_ns(tr, lo, hi) == 450
    assert tracereduce.idle_gaps(tr, DEV, lo, hi) == [(100, 150),
                                                      (400, 700),
                                                      (900, 1100)]


def test_module_op_and_host_counts():
    tr = _trace()
    assert tracereduce.module_ns(tr, "jit_setup_body(", 100, 1100) == 270
    assert tracereduce.module_ns(tr, "jit_round_body(", 100, 800) == 110
    assert tracereduce.op_ns(tr, r"fusion\(", 100, 1100) == 150
    assert tracereduce.host_count(tr, "graph", 100, 1100) == 2
    assert tracereduce.host_count(tr, "graph", 100, 700) == 1


def test_what_the_host_was_doing():
    tr = _trace()
    assert tracereduce.host_doing(tr, 500) == \
        "generate > np.asarray(jax.Array)"
    assert tracereduce.host_doing(tr, 660) == \
        "generate > backend_compile_and_load"
    assert tracereduce.host_doing(tr, 120) == "plan"
    assert tracereduce.host_doing(tr, 1095) == "outside any span"


def test_breakdown_names_ops_by_program_and_gaps_by_host():
    b = tracereduce.breakdown(_trace(), 100, 1100)
    assert [name for name, _ in b["device_ops"]] == [
        "jit_round_body/vmap.1 (custom-call)",
        "jit_setup_body/fusion.1 (fusion)",
        "jit_setup_body/while.2 (while)"]
    assert [s for _, s in b["device_ops"]] == pytest.approx(
        [200e-9, 150e-9, 150e-9])
    assert [name for name, _ in b["idle_gaps"]] == [
        "generate > np.asarray(jax.Array)", "generate", "plan"]
    assert [s for _, s in b["idle_gaps"]] == pytest.approx(
        [300e-9, 200e-9, 50e-9])


def test_device_idle_share_reader():
    run = harness.Run(cell=None, plan=None, setup_s=0,
                      window_s=1, graphs=[], compile_s=0, peak_bytes=None,
                      peaks=None, trace=_trace(), span=(100, 1100))
    assert _metric("device_idle_pct").read(run) == pytest.approx(55.0)
    assert _metric("pba_setup_ms").read(run) == pytest.approx(270 / 2 / 1e6)
    assert _metric("pba_round_ms").read(run) == pytest.approx(215 / 2 / 1e6)
    run.trace = None
    assert _metric("device_idle_pct").read(run) is None


def test_round_bytes_by_hand():
    """lp=2 ranks of P=4 with E=10 edges, C_r=3, block_cap=10:
    tags + ranks 2*2*10 = 40 ints, grant reads + buffer write + read
    3*2*4*3 = 72 ints, u + v 2*2*10 = 40 ints: 152 ints, 608 bytes."""
    rb = _metric("pba_round_roofline").round_bytes
    assert rb(lp=2, procs=4, edges=10, round_cap=3, block_cap=10) == 608
    assert rb(lp=1, procs=1, edges=1, round_cap=1, block_cap=1) == 4 * 7


def test_cfree_edge_bytes_by_hand():
    assert _metric("cfree_expand_roofline").edge_bytes(1000) == 8000


def test_cfree_kernel_pattern_matches_only_the_expansion_kernel():
    pattern = _metric("cfree_expand_roofline").KERNEL
    tr = _trace()
    assert tracereduce.op_ns(tr, pattern, 100, 1100) == 200
    histogram = Event('%h = s32[64]{0} custom-call(s32[8,128]{1,0} %a), '
                      'custom_call_target="tpu_custom_call"', 0, 10)
    tr2 = Trace(ops={DEV: [histogram]}, modules={}, host=[])
    assert tracereduce.op_ns(tr2, pattern, 0, 10) == 0


def _recorded(tmp_path, workload):
    """A trace the harness recorded on a v5e chip, at bench_tiny's sizes
    (`--seed 5 --seconds 0.05 --trace 1`)."""
    src = os.path.join(os.path.dirname(__file__), "traces",
                       f"{workload}.xplane.pb.gz")
    dst = tmp_path / f"{workload}.xplane.pb"
    with gzip.open(src) as f:
        dst.write_bytes(f.read())
    return tracereduce.load(str(dst))


def _recorded_run(tmp_path, workload, graphs):
    tr = _recorded(tmp_path, workload)
    return harness.Run(cell=None, plan=None, setup_s=0, window_s=1,
                       graphs=graphs, compile_s=0, peak_bytes=None,
                       peaks=harness.load_peaks(REPO, "TPU v5 lite"),
                       trace=tr, span=tracereduce.window(tr))


def _graph(edges, rounds):
    return harness.Graph(requested=edges, emitted=edges, dropped=0,
                         rounds=rounds, seconds=0.0)


def test_recorded_pba_trace(tmp_path):
    """The planes, lines and program names the reduction relies on are
    where the chip put them, and every PBA reader finds its programs."""
    from repro import api
    edges = bench_tiny.PBA_PROCS * bench_tiny.PBA_VERTICES * 5
    run = _recorded_run(tmp_path, "pba_table1.memory",
                        [_graph(edges, 7), _graph(edges, 7)])
    cell = harness.resolve(bench_tiny.make_root(tmp_path / "root"),
                           "pba_table1.memory")
    run.plan = api.plan(harness.graph_spec(cell, 5))
    tr, (lo, hi) = run.trace, run.span
    assert list(tr.ops) == [DEV]
    assert tracereduce.host_count(tr, "graph", lo, hi) == 2
    busy = tracereduce.mean_busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    rounds = sum(1 for e in tr.modules[DEV] if lo <= e.start_ns < hi
                 and e.name.startswith("jit_round_body("))
    assert rounds == 14      # 7 rounds per graph at this size
    for name in ("pba_setup_ms", "pba_round_ms"):
        assert _metric(name).read(run) > 0
    assert 0 < _metric("pba_round_roofline").read(run) < 100
    assert 0 < _metric("device_idle_pct").read(run) < 100
    b = tracereduce.breakdown(tr, lo, hi)
    programs = {name.split("/")[0] for name, _ in b["device_ops"]}
    assert programs <= {"jit_setup_body", "jit_pool_body", "jit_round_body"}
    assert len(b["idle_gaps"]) == 10
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])


def test_recorded_rmat_trace_finds_the_kernel(tmp_path):
    """On the chip the kernel's operands carry tiled layouts; the pattern
    still finds exactly the one expansion kernel of the graph."""
    edges = 16 << bench_tiny.RMAT_SCALE
    run = _recorded_run(tmp_path, "rmat_graph500.memory",
                        [_graph(edges, 0)])
    tr, (lo, hi) = run.trace, run.span
    pattern = _metric("cfree_expand_roofline").KERNEL
    hits = [e for e in tr.ops[DEV] if re.search(pattern, e.name)]
    assert len(hits) == 1 and "T(8,128)" in hits[0].name
    share = _metric("cfree_expand_roofline").read(run)
    ns = hits[0].duration_ns
    assert share == pytest.approx(100 * 8 * edges / (ns / 1e9) / 819e9)
    assert 0 < share < 100


def test_shares_are_none_without_a_trace_or_peaks():
    run = harness.Run(cell=None, plan=None, setup_s=0,
                      window_s=1, graphs=[], compile_s=0, peak_bytes=None,
                      peaks=None, trace=_trace(), span=(100, 1100))
    for name in ("pba_round_roofline", "cfree_expand_roofline"):
        assert _metric(name).read(run) is None
