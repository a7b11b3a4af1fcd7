"""Sharded storage (resume semantics) + distributed on-device analysis."""
import json
import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (FactionSpec, PBAConfig, degree_counts,
                        generate_pba, make_factions)
from repro.core.graph import EdgeList
from repro.core.storage import iter_shards, read_shards, write_shards
from repro.runtime import Topology

from helpers import run_with_devices


def _graph():
    table = make_factions(4, FactionSpec(2, 2, 3, seed=0))
    return generate_pba(PBAConfig(500, 4, seed=3), table,
                        Topology.host())[0]


def test_write_read_roundtrip(tmp_path):
    edges = _graph()
    man = write_shards(edges, str(tmp_path), num_shards=4, meta={"gen": "pba"})
    assert sorted(man["complete"]) == [0, 1, 2, 3]
    s, d, man2 = read_shards(str(tmp_path))
    s0, d0 = edges.to_numpy()
    np.testing.assert_array_equal(np.sort(s), np.sort(s0))
    np.testing.assert_array_equal(np.sort(d), np.sort(d0))
    assert man2["meta"]["gen"] == "pba"


def test_resume_skips_complete_shards(tmp_path):
    edges = _graph()
    write_shards(edges, str(tmp_path), num_shards=4)
    # simulate preemption: drop two shards from the manifest + disk
    with open(tmp_path / "manifest.json") as f:
        man = json.load(f)
    man["complete"] = [0, 1]
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump(man, f)
    os.remove(tmp_path / "shard_00002.npz")
    mtime0 = os.path.getmtime(tmp_path / "shard_00000.npz")
    man2 = write_shards(edges, str(tmp_path), num_shards=4)
    assert sorted(man2["complete"]) == [0, 1, 2, 3]
    # completed shards untouched (resume, not rewrite)
    assert os.path.getmtime(tmp_path / "shard_00000.npz") == mtime0


def test_iter_shards_streams(tmp_path):
    edges = _graph()
    write_shards(edges, str(tmp_path), num_shards=3)
    total = sum(len(s) for s, _ in iter_shards(str(tmp_path)))
    s0, _ = edges.to_numpy()
    assert total == len(s0)


def test_invalid_slots_dropped_on_write(tmp_path):
    e = EdgeList(src=jnp.asarray([0, -1, 2], jnp.int32),
                 dst=jnp.asarray([1, 5, -1], jnp.int32), num_vertices=6)
    write_shards(e, str(tmp_path), num_shards=1)
    s, d, _ = read_shards(str(tmp_path))
    assert len(s) == 1 and s[0] == 0 and d[0] == 1


def test_manifest_written_atomically(tmp_path, monkeypatch):
    """A crash during the manifest dump must leave the previous manifest
    intact (tmp + os.replace), not a truncated JSON."""
    from repro.core import storage as storage_mod
    edges = _graph()
    write_shards(edges, str(tmp_path), num_shards=2)
    with open(tmp_path / "manifest.json") as f:
        before = f.read()

    real_replace = os.replace

    def exploding_replace(src, dst):
        if dst.endswith("manifest.json"):
            raise RuntimeError("simulated preemption mid-manifest")
        return real_replace(src, dst)

    monkeypatch.setattr(storage_mod.os, "replace", exploding_replace)
    man = json.loads(before)
    man["complete"] = [0]
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump(man, f)
    os.remove(tmp_path / "shard_00001.npz")
    with pytest.raises(RuntimeError):
        write_shards(edges, str(tmp_path), num_shards=2)
    # the crash happened *after* the tmp write but before the swap: the
    # live manifest still parses and still says shard 1 is missing
    with open(tmp_path / "manifest.json") as f:
        recovered = json.load(f)
    assert recovered["complete"] == [0]
    monkeypatch.undo()
    man2 = write_shards(edges, str(tmp_path), num_shards=2)
    assert sorted(man2["complete"]) == [0, 1]


def test_resume_validates_num_vertices(tmp_path):
    edges = _graph()
    write_shards(edges, str(tmp_path), num_shards=2)
    wrong = EdgeList(src=edges.src, dst=edges.dst,
                     num_vertices=edges.num_vertices + 1)
    with pytest.raises(ValueError, match="num_vertices mismatch"):
        write_shards(wrong, str(tmp_path), num_shards=2)
    from repro.core.storage import ShardWriter
    with pytest.raises(ValueError, match="num_vertices mismatch"):
        ShardWriter(str(tmp_path), edges.num_vertices + 1, num_shards=2)
    with pytest.raises(ValueError, match="shard count mismatch"):
        ShardWriter(str(tmp_path), edges.num_vertices, num_shards=3)


def test_shard_writer_blocks_resume(tmp_path):
    from repro.core.storage import ShardWriter
    w = ShardWriter(str(tmp_path), num_vertices=10, num_shards=3)
    w.write_block(0, np.array([0, 1]), np.array([1, 2]))
    w.write_block(2, np.array([3, -1]), np.array([4, 5]))  # -1 dropped
    assert w.missing() == [1]
    assert w.edges_written == 3
    # a fresh writer sees the same state and double-writes are no-ops
    w2 = ShardWriter(str(tmp_path), num_vertices=10, num_shards=3)
    assert w2.missing() == [1]
    mtime0 = os.path.getmtime(tmp_path / "shard_00000.npz")
    w2.write_block(0, np.array([9]), np.array([9]))
    assert os.path.getmtime(tmp_path / "shard_00000.npz") == mtime0
    w2.write_block(1, np.array([5]), np.array([6]))
    assert w2.missing() == []
    s, d, man = read_shards(str(tmp_path))
    assert len(s) == 4 and man["counts"]["2"] == 1


def test_degree_counts_sharded_matches_host_4dev():
    run_with_devices("""
        import numpy as np, jax.numpy as jnp
        from repro.core import (make_factions, FactionSpec, PBAConfig,
                                generate_pba, degree_counts)
        from repro.core.distributed_analysis import (degree_counts_sharded,
                                                     edge_count_sharded,
                                                     max_degree_sharded)
        from repro.runtime import Topology
        table = make_factions(4, FactionSpec(2, 2, 3, seed=1))
        cfg = PBAConfig(vertices_per_proc=400, edges_per_vertex=3, seed=5)
        edges, stats = generate_pba(cfg, table, Topology.flat(4))
        want = np.asarray(degree_counts(edges))
        got = np.asarray(degree_counts_sharded(edges))
        np.testing.assert_array_equal(got, want)
        assert edge_count_sharded(edges) == stats.emitted_edges
        assert max_degree_sharded(edges) == want.max()
        print("OK")
    """, 4)
