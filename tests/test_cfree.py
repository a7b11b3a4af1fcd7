"""Cross-executor differential suite for the communication-free family.

The cfree contract is total determinism in (seed, edge index): every
executor path — host, sharded over any topology and any logical rank
count, streamed at any slab size, memory or shards sink — must emit
bit-identical edges for the same spec. This suite pins that matrix (the
multi-device legs out-of-process via run_with_devices, mirroring
tests/test_api.py), plus the serial Batagelj–Brandes oracle identity and
mid-manifest resume parity.
"""
from __future__ import annotations

import os
import tempfile

import numpy as np
import pytest

from repro import api
from repro.core import cfree as cfree_lib
from repro.core import storage
from tests.helpers import run_with_devices

MODELS = (
    ("ba_cfree", {"cfree_vertices": 500, "ba_degree": 3}),
    ("rmat", {"cfree_vertices": 256, "cfree_edges": 1500}),
    ("er", {"cfree_vertices": 300, "cfree_edges": 1200}),
)


def _spec(model: str, kw: dict, **overrides) -> api.GraphSpec:
    return api.GraphSpec(model=model, seed=11, **kw).replace(**overrides)


def _host_edges(model: str, kw: dict) -> tuple[np.ndarray, np.ndarray]:
    res = api.generate(_spec(model, kw, execution="host"))
    return res.edges.to_numpy()


# --- serial oracle ------------------------------------------------------------

def test_ba_cfree_matches_serial_batagelj_brandes():
    """The CHAIN_BOUND-unrolled vectorized chain must equal the serial
    M-array construction bit-for-bit."""
    cfg = cfree_lib.CFreeConfig(model="ba_cfree", vertices=500, ba_degree=3,
                                seed=11)
    ou, ov = cfree_lib.serial_ba_cfree_reference(cfg)
    edges, stats = cfree_lib.generate_cfree_host(cfg)
    assert np.array_equal(np.asarray(edges.src), ou)
    assert np.array_equal(np.asarray(edges.dst), ov)
    assert stats.exchange_rounds == 0


def test_ba_cfree_destinations_in_range():
    cfg = cfree_lib.CFreeConfig(model="ba_cfree", vertices=2048,
                                ba_degree=2, seed=5)
    edges, _ = cfree_lib.generate_cfree_host(cfg)
    src, dst = np.asarray(edges.src), np.asarray(edges.dst)
    # BA attachment: edge t's destination is a vertex that already exists
    # when its source t // d arrives.
    assert (dst >= 0).all()
    assert (dst <= src).all()


# --- logical-rank-count independence (single device) --------------------------

@pytest.mark.parametrize("model,kw", MODELS, ids=[m for m, _ in MODELS])
def test_p1_vs_p8_bit_identical(model, kw):
    """P is pure partitioning: any logical rank count emits the identical
    edge sequence (stronger than the issue's same-multiset ask)."""
    hs, hd = _host_edges(model, kw)
    for procs in (1, 8):
        res = api.generate(_spec(model, kw, execution="sharded",
                                 procs=procs))
        ss, sd = res.edges.to_numpy()
        assert np.array_equal(hs, ss), (model, procs)
        assert np.array_equal(hd, sd), (model, procs)


# --- one expansion program per shape ------------------------------------------

def _builds(monkeypatch) -> list:
    """The ``program`` of every ``repro.build`` span opened from now on."""
    from repro.runtime import spans
    real, built = spans.span, []

    def span(name, **args):
        if name == "repro.build":
            built.append(args["program"])
        return real(name, **args)
    monkeypatch.setattr(spans, "span", span)
    cfree_lib._expand_program.cache_clear()
    cfree_lib.words_fn.cache_clear()
    return built


@pytest.mark.parametrize("model,kw", MODELS, ids=[m for m, _ in MODELS])
def test_sharded_program_built_once_per_spec(model, kw, monkeypatch):
    """The first graph of a spec builds its words and expansion programs;
    the second finds both cached and gives the same edges."""
    built = _builds(monkeypatch)
    spec = _spec(model, kw, execution="sharded", procs=4)
    first = api.generate(spec).edges.to_numpy()
    assert built == ["cfree_expand", "cfree_words"]
    second = api.generate(spec).edges.to_numpy()
    assert built == ["cfree_expand", "cfree_words"]
    for a, b in zip(first, second):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("model,kw", MODELS, ids=[m for m, _ in MODELS])
def test_new_seed_builds_only_its_words(model, kw, monkeypatch):
    """The seed lives in the words program alone: two seeds of one shape
    share one expansion program, and each gives the host path's edges for
    its own seed, which differ between the seeds."""
    built = _builds(monkeypatch)
    out = {}
    for seed in (11, 2**31 + 12345):
        spec = _spec(model, kw, execution="sharded", procs=4, seed=seed)
        out[seed] = api.generate(spec).edges.to_numpy()
        host, _ = cfree_lib.generate_cfree_host(api.plan(spec).config)
        assert np.array_equal(out[seed][0], np.asarray(host.src)), seed
        assert np.array_equal(out[seed][1], np.asarray(host.dst)), seed
    assert built == ["cfree_expand", "cfree_words", "cfree_words"]
    assert not np.array_equal(out[11][1], out[2**31 + 12345][1])


# --- slab-boundary independence -----------------------------------------------

@pytest.mark.parametrize("model,kw", MODELS, ids=[m for m, _ in MODELS])
def test_slab_boundary_independence(model, kw):
    hs, hd = _host_edges(model, kw)
    for slab in (64, 977):
        res = api.generate(_spec(model, kw, execution="streamed",
                                 slab_edges=slab))
        ss, sd = res.edges.to_numpy()
        assert np.array_equal(hs, ss), (model, slab)
        assert np.array_equal(hd, sd), (model, slab)
        assert res.stats.exchange_rounds == 0


# --- shards sink + mid-manifest resume ----------------------------------------

@pytest.mark.parametrize("model,kw", MODELS, ids=[m for m, _ in MODELS])
def test_shards_sink_equals_memory(model, kw):
    hs, hd = _host_edges(model, kw)
    with tempfile.TemporaryDirectory() as d:
        res = api.generate(_spec(model, kw, sink="shards", out_dir=d,
                                 slab_edges=97))
        src, dst, man = storage.read_shards(d)
        assert sorted(zip(src.tolist(), dst.tolist())) \
            == sorted(zip(hs.tolist(), hd.tolist()))
        assert res.stats.emitted_edges == len(hs)


@pytest.mark.parametrize("model,kw", MODELS, ids=[m for m, _ in MODELS])
def test_mid_manifest_resume_parity(model, kw):
    """Interrupt after a few shards; the front-door resume regenerates
    exactly the missing blocks and the result equals an uninterrupted run."""
    hs, hd = _host_edges(model, kw)
    spec = _spec(model, kw, sink="shards", out_dir="IGNORED", slab_edges=97)
    with tempfile.TemporaryDirectory() as d:
        stream = cfree_lib.CFreeStream(
            api.plan(spec.replace(out_dir=d)).config, slab_edges=97)
        writer = storage.ShardWriter(d, stream.num_vertices,
                                     stream.num_blocks, meta=stream.meta())
        first = writer.missing()[:3]
        for i in first:
            writer.write_block(i, *stream.block(i))
        mtimes = {i: os.path.getmtime(
            os.path.join(d, f"shard_{i:05d}.npz")) for i in first}

        res = api.generate(spec.replace(out_dir=d))
        assert sorted(res.manifest["complete"]) \
            == list(range(stream.num_blocks))
        # completed shards were never rewritten
        for i in first:
            assert os.path.getmtime(
                os.path.join(d, f"shard_{i:05d}.npz")) == mtimes[i]
        src, dst, _ = storage.read_shards(d)
        assert sorted(zip(src.tolist(), dst.tolist())) \
            == sorted(zip(hs.tolist(), hd.tolist()))


def test_resume_rejects_different_spec():
    model, kw = MODELS[0]
    with tempfile.TemporaryDirectory() as d:
        api.generate(_spec(model, kw, sink="shards", out_dir=d,
                           slab_edges=97))
        with pytest.raises(ValueError):
            api.generate(_spec(model, kw, sink="shards", out_dir=d,
                               slab_edges=97, seed=12))


# --- multi-device matrix ------------------------------------------------------

def test_cross_executor_matrix_8_devices():
    """host == flat(8) == pods(2,4) == pods(4,2), memory and shards sinks,
    sharded and device-sharded-streamed — all bit-identical."""
    run_with_devices("""
        import numpy as np, tempfile
        from repro import api
        from repro.core import storage
        from repro.runtime.topology import Topology

        MODELS = (("ba_cfree", {"cfree_vertices": 500, "ba_degree": 3}),
                  ("rmat", {"cfree_vertices": 256, "cfree_edges": 1500}),
                  ("er", {"cfree_vertices": 300, "cfree_edges": 1200}))
        for model, kw in MODELS:
            spec = api.GraphSpec(model=model, seed=11, **kw)
            hs, hd = api.generate(
                spec.replace(execution="host")).edges.to_numpy()
            for topo in (Topology.flat(8), Topology.pods(2, 4),
                         Topology.pods(4, 2)):
                for procs in (0, 32):
                    res = api.generate(spec.replace(
                        topology=topo, procs=procs, execution="sharded"))
                    ss, sd = res.edges.to_numpy()
                    assert np.array_equal(hs, ss), (model, topo.label, procs)
                    assert np.array_equal(hd, sd), (model, topo.label, procs)
                    assert res.stats.exchange_rounds == 0
            with tempfile.TemporaryDirectory() as d:
                pl = api.plan(spec.replace(sink="shards", out_dir=d,
                                           slab_edges=97))
                assert pl.executor == "cfree_stream_sharded", pl.executor
                api.generate(pl)
                src, dst, man = storage.read_shards(d)
                assert sorted(zip(src.tolist(), dst.tolist())) \\
                    == sorted(zip(hs.tolist(), hd.tolist())), model
            print(model, "OK")
        """, 8)


# --- plan validation ----------------------------------------------------------

def test_plan_validation_errors():
    with pytest.raises(ValueError, match="power of two"):
        api.plan(api.GraphSpec(model="rmat", cfree_vertices=100,
                               cfree_edges=10))
    with pytest.raises(ValueError, match="int32"):
        api.plan(api.GraphSpec(model="ba_cfree", cfree_vertices=2**30,
                               ba_degree=4))
    with pytest.raises(ValueError, match="edges"):
        api.plan(api.GraphSpec(model="er", cfree_vertices=10))
    with pytest.raises(ValueError, match="ba_degree"):
        api.plan(api.GraphSpec(model="ba_cfree", cfree_vertices=10,
                               ba_degree=0))
    with pytest.raises(ValueError, match="probabilities"):
        api.plan(api.GraphSpec(model="rmat", cfree_vertices=16,
                               cfree_edges=10, rmat_a=0.9, rmat_b=0.2))


def test_presets_plan():
    pl = api.plan(api.preset("rmat_smoke"))
    assert pl.model == "rmat" and pl.requested_edges == 1 << 16
    pl = api.plan(api.preset("ba_cfree_1b"))
    assert pl.model == "ba_cfree"
    assert pl.requested_edges == 1_000_000_000
    assert pl.execution == "streamed" and pl.exchange_rounds == 0


def test_edge_slices_partition_exact():
    for e, p in ((0, 4), (1, 4), (7, 3), (64, 8), (100, 7), (5, 8)):
        slices = cfree_lib.edge_slices(e, p)
        assert len(slices) == p
        covered = [t for lo, hi in slices for t in range(lo, hi)]
        assert covered == list(range(e)), (e, p)
