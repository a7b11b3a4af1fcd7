"""The readers of the program's own spans (front door, host sink), checked
by hand on made-up traces, and silent on traces of a program without
spans."""
from __future__ import annotations

import gzip
import os

import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import harness, programspans, tracereduce
from bench.tracereduce import Event, Trace

REPO = bench_tiny.REPO
DEV = "/device:TPU:0"
READERS = ("front_door_host_ms", "programs_built", "host_sink_ms",
           "sink_exposed_ms")


def _metric(name):
    return harness.load_module(os.path.join(REPO, "bench", "metrics",
                                            f"{name}.py"), f"t_{name}")


def _run(trace, span=(0, 10_000)):
    return harness.Run(cell=None, plan=None, setup_s=0, window_s=1,
                       graphs=[], compile_s=0, peak_bytes=None, peaks=None,
                       trace=trace, span=span)


def _trace():
    """A window [0, 10000) ns holding two graphs, and a third graph's
    spans after it. Device ops [1200, 2200), [2300, 3200), [5500, 6200),
    [6300, 9500): idle (0, 1200), (2200, 2300), (3200, 5500),
    (6200, 6300), (9500, 10000)."""
    host = [Event("window", 0, 10_000),
            # graph 1: build and dispatch overlap, d2h and compact too
            Event("graph", 100, 5000), Event("plan", 100, 200),
            Event("repro.plan", 110, 190),
            Event("generate", 200, 5000),
            Event("repro.generate", 210, 4900),
            Event("repro.build", 220, 300),
            Event("repro.dispatch", 250, 1000),
            Event("repro.wait", 1000, 2000),
            Event("repro.sink.d2h", 2000, 2500),
            Event("repro.sink.compact", 2400, 3000),
            Event("repro.sink.assemble", 3000, 3500),
            # graph 2: nothing built
            Event("graph", 5000, 9900), Event("plan", 5000, 5100),
            Event("repro.plan", 5010, 5090),
            Event("generate", 5100, 9900),
            Event("repro.generate", 5110, 9800),
            Event("repro.dispatch", 5200, 5700),
            Event("repro.sink.d2h", 6000, 6400),
            Event("repro.sink.assemble", 7000, 7300),
            # after the window
            Event("repro.generate", 10_100, 11_000),
            Event("repro.build", 10_200, 10_300),
            Event("repro.sink.d2h", 10_300, 10_900)]
    ops = [Event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", s, t)
           for s, t in ((1200, 2200), (2300, 3200), (5500, 6200),
                        (6300, 9500))]
    return Trace(ops={DEV: ops}, modules={DEV: []},
                 host=sorted(host, key=lambda e: (e.start_ns, -e.end_ns)))


def test_graphs_are_the_program_generate_spans_in_the_window():
    assert programspans.graphs(_run(_trace())) == 2
    assert programspans.graphs(_run(None)) == 0


def test_union_merges_overlaps_and_takes_prefixes():
    run = _run(_trace())
    assert programspans.union(run, ("repro.build", "repro.dispatch")) == [
        (220, 1000), (5200, 5700)]
    assert programspans.union(run, ("repro.sink.",)) == [
        (2000, 3500), (6000, 6400), (7000, 7300)]
    assert programspans.union(run, ("repro.sink",)) == []


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(5, 15)], 5),
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [(10, 20)], 0),
    ([(2, 3), (4, 5), (6, 9)], [(0, 100)], 5),
    ([], [(0, 1)], 0),
])
def test_overlap_of_interval_lists(a, b, want):
    assert programspans.overlap_ns(a, b) == want
    assert programspans.overlap_ns(b, a) == want


def test_front_door_host_ms_by_hand():
    """Plans 80 + 80 ns, build and dispatch 780 (overlapping) + 500 ns,
    over 2 graphs: 720 ns a graph."""
    got = _metric("front_door_host_ms").read(_run(_trace()))
    assert got == pytest.approx(720 / 1e6)


def test_programs_built_by_hand():
    """One build in the window over 2 graphs; the build after the window
    is not counted."""
    assert _metric("programs_built").read(_run(_trace())) == 0.5


def test_programs_built_reads_zero_not_none():
    tr = _trace()
    tr.host = [e for e in tr.host if e.name != "repro.build"]
    assert _metric("programs_built").read(_run(tr)) == 0.0


def test_host_sink_ms_by_hand():
    """Sink spans 1500 (d2h and compact overlapping, then assemble) +
    400 + 300 ns over 2 graphs; the wait is not part of it."""
    got = _metric("host_sink_ms").read(_run(_trace()))
    assert got == pytest.approx(1100 / 1e6)


def test_sink_exposed_ms_by_hand():
    """Idle inside the sink: (2200, 2300) and (3200, 3500) in graph 1,
    (6200, 6300) in graph 2; (7000, 7300) is busy. 500 ns over 2
    graphs."""
    got = _metric("sink_exposed_ms").read(_run(_trace()))
    assert got == pytest.approx(250 / 1e6)


def test_per_graph_division_follows_the_window():
    """A window holding only the first graph divides by one."""
    run = _run(_trace(), span=(0, 5000))
    assert _metric("programs_built").read(run) == 1.0
    assert _metric("host_sink_ms").read(run) == pytest.approx(1500 / 1e6)
    assert _metric("front_door_host_ms").read(run) == pytest.approx(
        860 / 1e6)


@pytest.mark.parametrize("name", READERS)
def test_none_without_program_spans(name):
    reader = _metric(name)
    assert reader.read(_run(None)) is None
    tr = _trace()
    tr.host = [e for e in tr.host if not e.name.startswith("repro.")]
    assert reader.read(_run(tr)) is None


def _recorded_run(tmp_path, workload):
    src = os.path.join(os.path.dirname(__file__), "traces",
                       f"{workload}.xplane.pb.gz")
    dst = tmp_path / f"{workload}.xplane.pb"
    with gzip.open(src) as f:
        dst.write_bytes(f.read())
    tr = tracereduce.load(str(dst))
    return _run(tr, span=tracereduce.window(tr))


@pytest.mark.parametrize("workload", ["pba_table1.memory",
                                      "rmat_graph500.memory"])
def test_none_on_traces_of_a_program_without_spans(tmp_path, workload):
    """The committed chip traces were recorded before the program had
    spans: every reader finds nothing there, as on the parent commit."""
    run = _recorded_run(tmp_path, workload)
    assert tracereduce.host_count(run.trace, "graph", *run.span) >= 1
    for name in READERS:
        assert _metric(name).read(run) is None, name
