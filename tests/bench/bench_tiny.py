"""A copy of the benchmark with its cells cut to sizes the CPU runs in
seconds, for the tests of tests/bench.

``make_root(dst)`` copies ``BENCHMARK.json`` and ``bench/`` under ``dst``,
links the program's ``src/`` beside them, and shrinks each configuration's
scale (PBA: 8 ranks x 500 vertices; R-MAT: scale 12). ``run(root, argv)``
drives ``bench/harness.main`` in this process without its look for a chip
and returns (exit code, stdout lines, stderr).
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import harness  # noqa: E402

PBA_PROCS = 8
PBA_VERTICES = 500
RMAT_SCALE = 12


def make_root(dst: str, rmat_scale: int = RMAT_SCALE) -> str:
    dst = str(dst)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    os.symlink(os.path.join(REPO, "src"), os.path.join(dst, "src"))
    edit_config(dst, "pba_table1", procs=PBA_PROCS,
                vertices_per_proc=PBA_VERTICES)
    path = os.path.join(dst, "bench", "configs", "pba_table1.json")
    with open(path) as f:
        cfg = json.load(f)
    # The generator's default faction layout for P ranks.
    p = PBA_PROCS
    cfg["reference_params"]["factions"].update(
        num_factions=max(p // 2, 1), min_size=min(2, p),
        max_size=min(max(p // 2, 2), p))
    with open(path, "w") as f:
        json.dump(cfg, f)
    edit_config(dst, "rmat_graph500", cfree_vertices=1 << rmat_scale,
                cfree_edges=16 << rmat_scale)
    return dst


def edit_config(root: str, name: str, **spec) -> None:
    path = os.path.join(root, "bench", "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["spec"].update(spec)
    with open(path, "w") as f:
        json.dump(cfg, f)


def run(root: str, argv: list) -> tuple[int, list, str]:
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.main(argv, t0=time.perf_counter(), root=root,
                          require_chip=False)
    return rc, out.getvalue().splitlines(), err.getvalue()


def result(root: str, workload: str, seed: int = 7, seconds: float = 0.5,
           trace: int = 0) -> dict:
    rc, lines, err = run(root, ["--workload", workload, "--seed", str(seed),
                                "--seconds", str(seconds),
                                "--trace", str(trace)])
    assert rc == 0, err[-3000:]
    return json.loads(lines[-1])
