"""The shard-sink cell on the CPU at scale 12: every graph is written into
a fresh directory, read back from disk and compared with the reference;
a writer that loses or skips edges, or a kernel that alters one, reads
``correct: false``; nothing is left on disk after a run."""
from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import control, harness, shards
from repro.core import storage
from repro.kernels import ops as kops

CELL = "rmat_graph500.shards"
EDGES = 16 << bench_tiny.RMAT_SCALE


@pytest.fixture
def root(tmp_path):
    return bench_tiny.make_root(tmp_path)


def _run(root, seed=7, seconds=0.3, trace=0):
    rc, lines, err = bench_tiny.run(root, [
        "--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace)])
    assert rc == 0, err[-3000:]
    assert not os.path.exists(os.path.join(root, shards.DIR))
    return json.loads(lines[-1]), err


def _record_read_backs(monkeypatch) -> list:
    """(shard files on disk, edges read back) of every graph read back."""
    seen = []
    real = shards.read_back

    def read_back(graph, ordered):
        files = [f for f in os.listdir(graph.out_dir) if f.endswith(".npz")]
        why = real(graph, ordered)
        seen.append((len(files), graph.emitted))
        return why
    monkeypatch.setattr(shards, "read_back", read_back)
    return seen


def test_sound_run_reads_every_graph_back_whole(root, monkeypatch):
    seen = _record_read_backs(monkeypatch)
    line, _ = _run(root, seed=3000000001)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert 1 <= line["attempted"] <= shards.MAX_GRAPHS
    # The warm-up graph and every graph of the window, each in 16 shards.
    assert seen == [(bench_tiny.SHARDS, EDGES)] * (line["attempted"] + 1)
    disk = line["metrics"]["disk_bytes_per_edge"]
    assert disk["unit"] == "B/edge" and 0 < disk["value"] < 8
    # peak_device_gb needs a device that reports its memory: not the CPU.
    assert set(line["metrics"]) - {"peak_device_gb"} == {
        "edges_per_s", "setup_s", "disk_bytes_per_edge"}


def test_traced_run_reports_the_cells_per_layer_metrics(root):
    line, _ = _run(root, seed=5, trace=1)
    assert line["correct"] is True
    assert set(line["metrics"]) <= {"window_compile_s", "device_idle_pct",
                                    "host_sink_ms"}
    assert "window_compile_s" in line["metrics"]
    assert line["metrics"]["host_sink_ms"]["value"] > 0


def test_leftover_directory_does_not_let_a_graph_skip_its_writes(
        root, monkeypatch):
    """A complete graph of the same spec where the run's first graph goes:
    the run clears it, and every graph writes all of its shards."""
    from repro import api
    cell = harness.resolve(root, CELL)
    spec = harness.graph_spec(cell, 7)
    first = os.path.join(root, shards.DIR, "graph_00000")
    left = api.generate(api.plan(dataclasses.replace(spec, out_dir=first)))
    assert sorted(left.manifest["complete"]) == list(range(bench_tiny.SHARDS))

    writes = []
    real = storage.ShardWriter.write_block

    def counted(self, i, src, dst):
        writes.append(i)
        return real(self, i, src, dst)
    monkeypatch.setattr(storage.ShardWriter, "write_block", counted)
    line, _ = _run(root, seed=7)
    assert line["correct"] is True
    assert len(writes) == bench_tiny.SHARDS * (line["attempted"] + 1)


def test_shards_reference_agrees_on_every_seed(root):
    cell = harness.resolve(root, CELL)
    got = list(control.readings(cell, [0, 7, 2**31 + 12345],
                                require_chip=False))
    assert [r["program_graphs_wrong"] for r in got] == [0, 0, 0]
    assert all(r["edges_missing"] == 0 and r["edges_dropped"] == 0
               for r in got)
    assert not os.path.exists(os.path.join(root, shards.DIR))


# --- faults underneath the timed path -----------------------------------------

def _altered(mp):
    real = kops.cfree_expand

    def bump(t, w, **kw):
        u, v = real(t, w, **kw)
        return u, v.at[(0,) * v.ndim].add(1)
    mp.setattr(kops, "cfree_expand", bump)


def _marked_not_written(mp):
    """Shard 3 is marked complete, with its count, but never written."""
    real = storage.ShardWriter.write_block

    def skip(self, i, src, dst):
        if i != 3:
            return real(self, i, src, dst)
        self.manifest["complete"].append(i)
        self._done.add(i)
        self.manifest["counts"][str(i)] = int(len(src))
        storage._dump_manifest(self.out_dir, self.manifest)
    mp.setattr(storage.ShardWriter, "write_block", skip)


def _half_of_one_shard(mp):
    """Shard 5 loses its second half before it is written and counted."""
    real = storage.ShardWriter.write_block

    def half(self, i, src, dst):
        if i == 5:
            src, dst = src[: len(src) // 2], dst[: len(dst) // 2]
        return real(self, i, src, dst)
    mp.setattr(storage.ShardWriter, "write_block", half)


def _half_written_fully_counted(mp):
    """Shard 5's file holds half its edges; the manifest counts them all."""
    real = storage._write_shard_file

    def half(out_dir, i, src, dst):
        if i != 5:
            return real(out_dir, i, src, dst)
        real(out_dir, i, src[: len(src) // 2], dst[: len(dst) // 2])
        return int(len(src))
    mp.setattr(storage, "_write_shard_file", half)


FAULTS = {
    "altered": _altered,
    "marked_not_written": _marked_not_written,
    "half_of_one_shard": _half_of_one_shard,
    "half_written_fully_counted": _half_written_fully_counted,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_shard_path_is_not_correct(root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    line, err = _run(root, seed=11)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert line["checks"]["graphs_wrong"]["value"] == line["attempted"] + 1
    if fault in ("marked_not_written", "half_written_fully_counted"):
        assert "cannot be read back" in err


def test_read_graph_follows_the_manifest_in_index_order(tmp_path):
    """Shards are hashed at running offsets in index order, whatever
    order the manifest lists them in."""
    from bench.edges import fingerprint, to_host
    u = np.arange(10, dtype=np.int32)
    v = (u * 3) % 10
    for i, (lo, hi) in enumerate([(0, 4), (4, 7), (7, 10)]):
        np.savez_compressed(tmp_path / f"shard_{i:05d}.npz", src=u[lo:hi],
                            dst=v[lo:hi])
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump({"complete": [2, 0, 1],
                   "counts": {"0": 4, "1": 3, "2": 3}}, f)
    fp, order, edges, nbytes = shards.read_graph(str(tmp_path), True)
    assert fp == order == to_host(fingerprint(jnp.asarray(u), jnp.asarray(v),
                                              ordered=True))
    assert edges == 10
    assert nbytes == sum(os.path.getsize(tmp_path / f"shard_{i:05d}.npz")
                         for i in range(3))
    fp_any, order_any, _, _ = shards.read_graph(str(tmp_path), False)
    assert order_any == fp and fp_any == to_host(fingerprint(
        jnp.asarray(u), jnp.asarray(v), ordered=False))
