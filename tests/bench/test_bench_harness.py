"""The benchmark harness on the CPU: cells resolve by name, the result line
has its contract's shape, and nothing is printed without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_tiny
from bench import harness

REPO = bench_tiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _run_script(cwd, argv):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH", "REPRO_PALLAS")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_benchmark_json_keys_and_names():
    b = _benchmark()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert b["command"][1] == "bench/run.py"
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["better"] == "higher"


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      _benchmark()["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = harness.resolve(REPO, workload)
    wl = next(w for w in _benchmark()["workloads"] if w["name"] == workload)
    assert cell.config["name"] == wl["config"]
    sink = cell.traffic["spec"]["sink"]
    assert sink in ("memory", "shards")
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e >= {"setup_s", "edges_per_s"}
    assert ("disk_bytes_per_edge" in e2e) == (sink == "shards")
    assert cell.per_layer, "every cell reports a per-layer metric"
    for traced in (False, True):
        for entry, reader in cell.readers(traced):
            assert callable(reader.read), entry["name"]
    ref = cell.reference()
    assert callable(ref.reference) and isinstance(ref.ORDERED, bool)


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.resolve(REPO, "no_such.cell")


def test_traffic_the_generator_cannot_send_is_an_error():
    cell = harness.resolve(REPO, "rmat_graph500.memory")
    cell.traffic = dict(cell.traffic, callers=4)
    with pytest.raises(harness.BenchError, match="not supported"):
        harness.graph_spec(cell, 1)


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.load_peaks(REPO, "TPU v99 imaginary")


@pytest.mark.parametrize("where", ["checkout", "benchmark_alone"])
def test_run_refuses_without_a_tpu(tmp_path, where):
    """No CPU fallback: exit 2, a reason on stderr, nothing on stdout;
    also in a directory holding only BENCHMARK.json and the bench paths."""
    cwd = REPO
    if where == "benchmark_alone":
        for p in _benchmark()["paths"]:
            shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
        cwd = tmp_path
    proc = _run_script(cwd, ["bench/run.py", "--workload",
                             "rmat_graph500.memory", "--seed", "1",
                             "--seconds", "1", "--trace", "0"])
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_result_line_shape(tmp_path, trace):
    root = bench_tiny.make_root(tmp_path)
    rc, lines, err = bench_tiny.run(root, [
        "--workload", "rmat_graph500.memory", "--seed", "3000000001",
        "--seconds", "0.3", "--trace", str(trace)])
    assert rc == 0, err[-3000:]
    line = json.loads(lines[-1])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(line) == keys + ["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert set(line["device"]) >= {"busy_s", "window_s"}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "window_compile_s" in line["metrics"]
    else:
        assert set(line["metrics"]) >= {"edges_per_s", "setup_s"}
        assert all(set(m) == {"value", "unit"}
                   for m in line["metrics"].values())
    assert set(line["checks"]) == {"graphs_wrong", "graphs_reordered",
                                   "edges_dropped", "edges_missing"}
    tail = err.strip().splitlines()[-4:]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_compile_cache_is_pinned_inside_the_checkout(tmp_path):
    """The run keeps JAX's compile cache at a fixed path in its checkout,
    whatever the environment says, and caches every program."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]);"
            "from bench import harness;"
            "path = harness.pin_compile_cache(sys.argv[2]);"
            "import jax;"
            "from repro.runtime import spmd;"
            "print(path, spmd.enable_compile_cache(),"
            " jax.config.jax_compilation_cache_dir,"
            " jax.config.jax_persistent_cache_min_compile_time_secs)")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "elsewhere"))
    proc = subprocess.run([sys.executable, "-c", code, REPO, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert proc.stdout.split() == [want, want, want, "0.0"]


def test_fixture_cell_is_added_with_files_and_entries_only(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric need
    new files and new entries in BENCHMARK.json, and no edit of a file
    that is already there."""
    root = bench_tiny.make_root(tmp_path)
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = open(p, "rb").read()

    def add(rel, text):
        path = os.path.join(root, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    add("bench/configs/fixture_rmat.json", json.dumps({
        "name": "fixture_rmat", "reference": "rmat",
        "spec": {"model": "rmat", "cfree_vertices": 1 << 10,
                 "cfree_edges": 1 << 13, "rmat_a": 0.45, "rmat_b": 0.15,
                 "rmat_c": 0.15}}))
    add("bench/traffic/fixture_mix.json", json.dumps({
        "why": "fixture", "loop": "closed", "callers": 1,
        "graph_seed": "run", "spec": {"execution": "sharded",
                                      "sink": "memory"},
        "topology": {"kind": "flat", "devices": 1}}))
    add("bench/metrics/fixture_graphs_traced.py",
        "def read(run):\n    return float(len(run.graphs))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    old = json.loads(json.dumps(b))
    b["configs"].append({"name": "fixture_rmat", "source": "fixture",
                         "file": "bench/configs/fixture_rmat.json",
                         "reduced": [], "why": "fixture"})
    b["workloads"].append({"name": "fixture_rmat.mix",
                           "config": "fixture_rmat",
                           "traffic": "fixture_mix", "chips": 1,
                           "why": "fixture"})
    b["per_layer"].append({"name": "fixture_graphs_traced", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "device", "moves": "edges_per_s",
                           "workloads": ["fixture_rmat.mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    for key, entries in old.items():   # old entries all kept, unchanged
        if isinstance(entries, list) and entries \
                and isinstance(entries[0], dict):
            assert b[key][:len(entries)] == entries
    for p, data in before.items():
        assert open(p, "rb").read() == data, p

    kept = tmp_path / "trace"
    rc, lines, err = bench_tiny.run(root, [
        "--workload", "fixture_rmat.mix", "--seed", "7", "--seconds", "0.3",
        "--trace", "1", "--trace-dir", str(kept)])
    assert rc == 0, err[-3000:]
    assert list(kept.rglob("*.xplane.pb")), "--trace-dir keeps the trace"
    line = json.loads(lines[-1])
    assert line["correct"] is True
    assert line["metrics"]["fixture_graphs_traced"]["value"] \
        == line["attempted"]
    # Metrics that list their cells do not reach a cell they do not list.
    assert set(line["metrics"]) == {"fixture_graphs_traced"}
