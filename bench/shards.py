"""The shard sink: graphs the program writes to disk, read back plainly.

A cell whose traffic says ``"sink": "shards"`` gets every graph, the
warm-up graph included, written by the program into a fresh, empty
directory of its own under ``<checkout>/.bench_shards/``. A directory is
never reused: the program's shard writer resumes a directory whose
manifest reads complete without writing anything, so a reused one would
time nothing.

After the window each graph is read back from what is on disk, with
nothing of the program: the manifest (``manifest.json``: ``complete``,
the indices of the shards written, and ``counts``, each one's edges) and
every shard it lists (``shard_<i:05d>.npz`` with int32 arrays ``src`` and
``dst``), in index order. The shards are fingerprinted at running edge
offsets (``bench/edges.py``), so the graph on disk is compared with the
reference edge for edge, in order. A listed shard that is missing or
unreadable, or whose length disagrees with ``counts``, makes the graph
wrong. Then the directory goes.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import shutil
import zipfile

import numpy as np

#: Where the graphs of a run are written, inside the checkout.
DIR = ".bench_shards"
#: The window closes once it holds this many graphs on disk (or at
#: ``--seconds``), so that a fast program cannot fill the disk.
MAX_GRAPHS = 16
#: The fingerprint of a graph that could not be read back: its count can
#: never match a reference's.
UNREADABLE = (-1, 0, 0)
#: What a shard that cannot be read raises.
_UNREADABLE_ERRORS = (OSError, ValueError, KeyError, EOFError,
                      zipfile.BadZipFile)


@contextlib.contextmanager
def disk_for(cell):
    """The run's :class:`Disk` where ``cell``'s traffic sinks its graphs
    into shards, else None; removed, with every graph in it, on exit."""
    if cell.traffic["spec"].get("sink") != "shards":
        yield None
        return
    disk = Disk(cell.root)
    try:
        yield disk
    finally:
        disk.close()


class Disk:
    """The run's directory of graph directories, emptied when made and
    removed by :meth:`close`."""

    def __init__(self, root: str):
        self.path = os.path.join(root, DIR)
        shutil.rmtree(self.path, ignore_errors=True)   # a killed run's
        os.makedirs(self.path)
        self._made = 0

    def fresh(self) -> str:
        """A new, empty directory for one graph."""
        path = os.path.join(self.path, f"graph_{self._made:05d}")
        self._made += 1
        os.makedirs(path)
        if os.listdir(path):
            raise RuntimeError(f"graph directory {path} is not empty")
        return path

    def close(self) -> None:
        shutil.rmtree(self.path, ignore_errors=True)


@functools.cache
def _fingerprinter():
    import jax
    from bench.edges import block_fingerprint
    return jax.jit(block_fingerprint, static_argnames="ordered")


def read_graph(out_dir: str, ordered: bool) -> tuple:
    """(fingerprint, ordered fingerprint, edges, shard bytes) of the graph
    in ``out_dir``; raises one of ``_UNREADABLE_ERRORS`` where the
    manifest or a shard it lists cannot be read or disagrees with it."""
    from bench.edges import combine, to_host
    with open(os.path.join(out_dir, "manifest.json")) as f:
        man = json.load(f)
    block = _fingerprinter()
    parts, order_parts = [], []
    t0 = nbytes = 0
    for i in sorted(man["complete"]):
        path = os.path.join(out_dir, f"shard_{i:05d}.npz")
        with np.load(path) as z:
            u, v = z["src"], z["dst"]
        if u.shape != v.shape or u.ndim != 1:
            raise ValueError(f"shard {i}: src {u.shape}, dst {v.shape}")
        if len(u) != man["counts"][str(i)]:
            raise ValueError(f"shard {i}: {len(u)} edges, the manifest "
                             f"counts {man['counts'][str(i)]}")
        at = np.uint32(t0 & 0xFFFFFFFF)
        parts.append(to_host(block(u, v, at, ordered=ordered)))
        if not ordered:
            order_parts.append(to_host(block(u, v, at, ordered=True)))
        t0 += len(u)
        nbytes += os.path.getsize(path)
    fp = combine(parts)
    return fp, fp if ordered else combine(order_parts), t0, nbytes


def read_back(graph, ordered: bool) -> str:
    """Fill ``graph``'s fingerprints, ``emitted`` and ``disk_bytes`` from
    its directory, then remove it. Returns why the graph could not be
    read, or an empty string."""
    try:
        fp, order, edges, nbytes = read_graph(graph.out_dir, ordered)
        why = ""
    except _UNREADABLE_ERRORS as e:
        fp = order = UNREADABLE
        edges, nbytes = 0, 0
        why = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(graph.out_dir, ignore_errors=True)
    graph.fingerprint, graph.order = fp, order
    graph.emitted, graph.disk_bytes = edges, nbytes
    return why
