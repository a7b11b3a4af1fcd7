"""The generator's host spans (repro.runtime.spans) in a real profiler
trace: where each opens, how many per round, the graph id they share, and
that tracing leaves the edges as they are."""
from __future__ import annotations

import glob
import os
import sys

import jax
import numpy as np
import pytest

from repro import api
from repro.api import GraphSpec, Topology
from repro.core import cfree as cfree_lib
from repro.core import stream as stream_lib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import tracereduce  # noqa: E402

PBA = GraphSpec(model="pba", procs=4, vertices_per_proc=300,
                edges_per_vertex=3, exchange_rounds=4, seed=1301,
                execution="streamed", topology=Topology.flat(1))
RMAT = GraphSpec(model="rmat", cfree_vertices=1 << 10, cfree_edges=1 << 13,
                 seed=1302, execution="sharded", topology=Topology.flat(1))
HARNESS_NAMES = ("window", "graph", "plan", "generate")


def _generate(spec):
    res = api.generate(api.plan(spec))
    if res.edges is None:
        return None, res.stats
    return (np.asarray(res.edges.src), np.asarray(res.edges.dst)), res.stats


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two PBA graphs (the first builds its programs), two R-MAT graphs
    and one PBA graph into shards, under the profiler, in a ``window``
    span; then the same PBA and R-MAT graphs with the profiler off."""
    stream_lib._sharded_setup_fn.cache_clear()
    stream_lib._sharded_grant_fns.cache_clear()
    cfree_lib._expand_program.cache_clear()
    cfree_lib.words_fn.cache_clear()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    shards = PBA.replace(sink="shards",
                         out_dir=str(tmp_path_factory.mktemp("shards")))
    calls = [PBA, PBA, RMAT, RMAT, shards]
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            traced = [_generate(spec) for spec in calls]
    finally:
        jax.profiler.stop_trace()
    untraced = {"pba": _generate(PBA)[0], "rmat": _generate(RMAT)[0]}
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    trace = tracereduce.load(path)
    generates = [e for e in trace.host if e.name == "repro.generate"]
    assert len(generates) == len(calls)
    return {"trace": trace, "path": path, "traced": traced,
            "untraced": untraced, "generates": generates}


def _inside(outer, e):
    return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns


def _spans_of(rec, k, name):
    g = rec["generates"][k]
    return [e for e in rec["trace"].host if e.name == name and _inside(g, e)]


def test_program_spans_nest_inside_generate_or_plan(recorded):
    host = recorded["trace"].host
    roots = [e for e in host if e.name in ("repro.generate", "repro.plan")]
    program = [e for e in host if e.name.startswith("repro.")]
    assert {e.name for e in program} >= {
        "repro.plan", "repro.generate", "repro.build", "repro.dispatch",
        "repro.wait", "repro.sink.d2h", "repro.sink.compact",
        "repro.sink.assemble", "repro.sink.write"}
    for e in program:
        assert any(_inside(r, e) for r in roots), e


def test_no_program_span_takes_a_harness_name(recorded):
    """The benchmark's reduction needs exactly one ``window`` and counts
    its own ``graph`` spans: the program opens none of those names."""
    names = [e.name for e in recorded["trace"].host]
    assert [n for n in names if n in HARNESS_NAMES] == ["window"]


@pytest.mark.parametrize("k", [0, 1, 4])
def test_one_wait_copy_and_compaction_per_round(recorded, k):
    """Each round of a streamed PBA graph waits, copies and compacts once;
    the stream's set-up waits once more, with no round."""
    rounds = recorded["traced"][k][1].exchange_rounds
    assert rounds > 1
    for name in ("repro.sink.d2h", "repro.sink.compact"):
        assert len(_spans_of(recorded, k, name)) == rounds, name
    assert len(_spans_of(recorded, k, "repro.wait")) == rounds + 1
    dispatches = _spans_of(recorded, k, "repro.dispatch")
    assert len(dispatches) == rounds + 2      # set-up, pools, rounds
    assert len(_spans_of(recorded, k, "repro.sink.assemble")) == (
        0 if k == 4 else 1)
    assert len(_spans_of(recorded, k, "repro.sink.write")) == (
        rounds if k == 4 else 0)


def test_programs_built_per_graph(recorded):
    """PBA builds its set-up and grant programs on the first graph of a
    spec and finds them cached after; R-MAT builds its words and expansion
    programs on the first graph and finds them cached after."""
    built = [len(_spans_of(recorded, k, "repro.build")) for k in range(4)]
    assert built == [2, 0, 2, 0]


def _stats_by_span(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != tracereduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


def test_spans_of_one_generate_share_one_graph_id(recorded):
    spans = _stats_by_span(recorded["path"])
    ids = []
    for name, lo, hi, stats in spans:
        if name != "repro.generate":
            continue
        ids.append(stats["graph"])
        assert isinstance(stats["executor"], str)
        inner = [s for n, s0, s1, s in spans
                 if lo <= s0 and s1 <= hi and n != "repro.generate"]
        assert inner
        assert {s["graph"] for s in inner} == {stats["graph"]}
    assert len(ids) == 5 and len(set(ids)) == 5
    rounds = {s.get("round") for n, _, _, s in spans
              if n == "repro.sink.compact"}
    assert rounds == set(range(len(rounds)))
    assert all(s["edges"] >= 0 for n, _, _, s in spans
               if n == "repro.sink.compact")
    assert all(s["bytes"] > 0 for n, _, _, s in spans
               if n.startswith("repro.sink.") and n != "repro.sink.compact")


def test_edges_identical_with_and_without_the_profiler(recorded):
    traced, untraced = recorded["traced"], recorded["untraced"]
    for k, model in ((0, "pba"), (1, "pba"), (2, "rmat"), (3, "rmat")):
        for got, want in zip(traced[k][0], untraced[model]):
            np.testing.assert_array_equal(got, want)


def test_graph_ids_are_fresh_and_scoped():
    """Each ``graph()`` takes a new id and gives it back on exit, so a
    span outside any graph carries none."""
    from repro.runtime import spans
    assert spans._GRAPH.get() is None
    with spans.graph() as a:
        with spans.graph() as b:
            assert spans._GRAPH.get() == b
        assert spans._GRAPH.get() == a
    assert spans._GRAPH.get() is None
    assert b > a
