"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, so a cell, configuration,
traffic mix or metric is added by adding files and entries only:

- ``BENCHMARK.json``: the cells, and the metrics with the cells they read;
- ``bench/configs/<config>.json``: a deployment, its ``spec`` (fields of
  the generator's ``GraphSpec``) and the name of its plain reference;
- ``bench/references/<reference>.py``: ``reference(config, seed)``, the
  fingerprint (``bench/edges.py``) of the graph the deployment defines,
  and ``ORDERED``, whether edge order is part of it;
- ``bench/traffic/<mix>.json``: how graphs are requested: the execution,
  topology and sink fields of the spec, and how graphs are seeded;
- ``bench/end_to_end/<metric>.py`` and ``bench/metrics/<metric>.py``: one
  reader per metric, ``read(run) -> float | None`` over a :class:`Run`;
- ``bench/peaks.json``: the chip's published peaks, by ``device_kind``.

A run: set-up (imports, plan, one warm-up graph of the cell's spec, which
compiles), then a closed-loop window: one caller requests whole graphs
through the front door, ``api.generate(api.plan(spec))``, back to back,
waits for each graph's edges in the sink, fingerprints them on the
device and drops them, until ``--seconds`` have passed; the graph in
flight finishes. With ``--trace 1`` the window runs under the profiler
and the per-layer metrics are read from the trace; otherwise the
end-to-end ones are reported. After the window the plain reference
computes the fingerprint every graph must have, and ``correct`` says
whether all of them did, in the warm-up graph's order, with no edge
dropped or missing. Every graph of a run takes the run's ``--seed``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks`` (each number compared, with its limit). Without a
TPU, or with fewer chips than the cell asks for, nothing is printed
there and the exit code is 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def pin_compile_cache(root: str) -> str:
    """Keep JAX's persistent compile cache at ``<root>/.jax_cache`` and put
    every program into it, however fast it compiled. Call before JAX is
    imported: JAX reads both settings from the environment then, and
    ``spmd.enable_compile_cache()`` takes the directory from it. Only the
    first run of a graph seed in a checkout then compiles; every later run
    of it, and every graph of a run, finds each program in the cache, with
    nothing left to how long one compile happened to take."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return path


class BenchError(Exception):
    """The cell cannot be run as described: no result is printed."""


class NoChip(BenchError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(root: str, device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device not in the table
    is an error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str

    def reference(self):
        ref = self.config["reference"]
        return load_module(os.path.join(self.root, "bench", "references",
                                        f"{ref}.py"), f"bench_ref_{ref}")

    def readers(self, traced: bool) -> list:
        """(metric entry, reader module) of the metrics this run reports."""
        sub, entries = (("metrics", self.per_layer) if traced
                        else ("end_to_end", self.end_to_end))
        return [(m, load_module(os.path.join(self.root, "bench", sub,
                                             f"{m['name']}.py"),
                                f"bench_{sub}_{m['name']}"))
                for m in entries]


def _find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"unknown {what} {name!r}: one of "
                     f"{sorted(e['name'] for e in entries)}")


def resolve(root: str, workload: str) -> Cell:
    """The cell named ``workload``, with its configuration, traffic mix
    and the metrics it reports, every file found by name under ``root``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = _find(bench["workloads"], workload, "workload")
    entry = _find(bench["configs"], wl["config"], "configuration")
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{wl['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (workload in m["workloads"] if "workloads" in m
               else m["moves"] in moved)]
    cell = Cell(name=workload, chips=int(wl["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per, root=root)
    for traced in (False, True):   # every reader file is there
        cell.readers(traced)
    cell.reference()
    return cell


@dataclasses.dataclass
class Graph:
    """One generated graph as the window saw it."""

    requested: int
    emitted: int
    dropped: int
    rounds: int
    seconds: float
    fingerprint: object = None     # device value until the window closes
    order: object = None           # ordered fingerprint, likewise

    @property
    def whole(self) -> bool:
        return self.dropped == 0 and self.emitted == self.requested


@dataclasses.dataclass
class Run:
    """What a metric reader sees. ``plan`` is the cell's ``GenPlan``;
    ``graphs`` the window's graphs; ``trace`` a ``tracereduce.Trace`` of
    the window (traced runs) and ``span`` the window's (start, end) in it;
    ``compile_s`` the backend-compile seconds JAX reported inside the
    window; ``peaks`` the chip's row of ``bench/peaks.json``."""

    cell: Cell
    plan: object
    setup_s: float
    window_s: float
    graphs: list
    compile_s: float
    peak_bytes: Optional[int]
    peaks: Optional[dict]
    trace: object = None
    span: tuple = (0.0, 0.0)


def graph_spec(cell: Cell, seed: int):
    """The cell's GraphSpec for graph seed ``seed``."""
    from repro.api import GraphSpec, Topology
    traffic = cell.traffic
    loop = (traffic.get("loop"), traffic.get("callers"),
            traffic.get("graph_seed"))
    if loop != ("closed", 1, "run"):
        raise BenchError(f"traffic (loop, callers, graph_seed) = {loop} is "
                         "not supported: the generator runs a closed loop "
                         "of one caller whose graphs take the run's seed")
    topo = traffic["topology"]
    if topo.get("kind") != "flat" or int(topo["devices"]) != cell.chips:
        raise BenchError(f"topology {topo} does not match the cell's "
                         f"{cell.chips} chip(s)")
    return GraphSpec(**cell.config["spec"], **traffic["spec"], seed=seed,
                     topology=Topology.flat(cell.chips))


def one_graph(api, spec, ordered: bool) -> tuple:
    """Request one graph through the front door and wait for its edges.
    Returns (Graph, plan); the graph's fingerprints are left in flight."""
    import jax
    from bench.edges import fingerprint
    with jax.profiler.TraceAnnotation("graph"):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("plan"):
            pl = api.plan(spec)
        with jax.profiler.TraceAnnotation("generate"):
            res = api.generate(pl)
            jax.block_until_ready((res.edges.src, res.edges.dst))
        took = time.perf_counter() - t
        st = res.stats
        fp = fingerprint(res.edges.src, res.edges.dst, ordered=ordered)
        g = Graph(requested=st.requested_edges, emitted=st.emitted_edges,
                  dropped=st.dropped_edges, rounds=st.exchange_rounds,
                  seconds=took, fingerprint=fp,
                  order=fp if ordered else fingerprint(
                      res.edges.src, res.edges.dst, ordered=True))
        del res
    return g, pl


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # JAX's own host spans stay; no per-call
    opts.enable_hlo_proto = False
    return opts


def execute(cell: Cell, seed: int, seconds: float, traced: bool, t0: float,
            require_chip: bool = True, trace_dir: Optional[str] = None
            ) -> dict:
    """Run ``cell`` and return its result line as a dict (``checks``
    last). Raises NoChip before any work when the chip is missing."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devices)} {dev.platform} device(s) "
                     f"({dev.device_kind})")
    peaks = load_peaks(cell.root, dev.device_kind) if require_chip else None
    if not 0 <= seed < 2**32:
        raise BenchError(f"--seed {seed} is outside [0, 2**32)")

    sys.path.insert(0, os.path.join(cell.root, "src"))
    from repro import api
    from repro.runtime import spmd
    from bench.edges import to_host
    spmd.enable_compile_cache()

    compiles: list = []
    cache_events: list = []

    def listen(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiles.append((time.perf_counter(), duration))

    def count(event, **_):
        if event in (CACHE_HIT, CACHE_MISS):
            cache_events.append((time.perf_counter(), event))

    jax.monitoring.register_event_duration_secs_listener(listen)
    jax.monitoring.register_event_listener(count)
    ref = cell.reference()
    try:
        t = time.perf_counter()
        spec = graph_spec(cell, seed)
        warm, plan = one_graph(api, spec, ref.ORDERED)
        warm.fingerprint = to_host(warm.fingerprint)
        warm.order = to_host(warm.order)
        setup_s = time.perf_counter() - t0
        say(f"set-up {setup_s:.3f} s: imports {t - t0:.3f} s, warm-up "
            f"graph {warm.seconds:.3f} s in {warm.rounds} exchange rounds, "
            f"programs compiled "
            f"{sum(e == CACHE_MISS for _, e in cache_events)}, loaded from "
            f"the compile cache {sum(e == CACHE_HIT for _, e in cache_events)}")

        made_dir = traced and trace_dir is None
        if made_dir:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        if traced:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profiler_options())
        graphs = []
        w0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("window"):
                while time.perf_counter() - w0 < seconds:
                    graphs.append(one_graph(api, spec, ref.ORDERED)[0])
                for g in graphs:
                    g.fingerprint = to_host(g.fingerprint)
                    g.order = to_host(g.order)
            w1 = time.perf_counter()
        finally:
            if traced:
                jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        jax.monitoring.unregister_event_listener(count)
    compile_s = sum(d for t, d in compiles if w0 <= t <= w1)
    in_window = [e for t, e in cache_events if w0 <= t <= w1]
    used = devices[:cell.chips]
    stats = [d.memory_stats() for d in used]
    peak = (max(s["peak_bytes_in_use"] for s in stats)
            if all(stats) else None)

    run = Run(cell=cell, plan=plan,
              setup_s=setup_s, window_s=w1 - w0, graphs=graphs,
              compile_s=compile_s, peak_bytes=peak, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    breakdown = None
    if traced:
        from bench import tracereduce
        try:
            run.trace = tracereduce.load(trace_dir)
        finally:
            if made_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = run.span = tracereduce.window(run.trace)
        busy = tracereduce.mean_busy_ns(run.trace, lo, hi)
        device["busy_s"] = busy / 1e9 if busy is not None else None
        device["window_s"] = (hi - lo) / 1e9
        breakdown = tracereduce.breakdown(run.trace, lo, hi)

    metrics = {}
    for entry, reader in cell.readers(traced):
        value = reader.read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    # The reference runs once the window's state is gone.
    run.trace = None
    gc.collect()
    t = time.perf_counter()
    want = tuple(ref.reference(cell.config, seed))
    say(f"{len(graphs)} graphs in {w1 - w0:.3f} s, programs compiled "
        f"{in_window.count(CACHE_MISS)}, loaded from the compile cache "
        f"{in_window.count(CACHE_HIT)}; reference "
        f"{time.perf_counter() - t:.3f} s")
    wrong = [tuple(g.fingerprint) != want for g in [warm] + graphs]
    # Every graph of a run has the same seed, so the same edges in the same
    # order, whether or not the reference pins the order.
    reordered = [tuple(g.order) != tuple(warm.order) for g in graphs]
    failed = sum(1 for g, bad, moved in zip(graphs, wrong[1:], reordered)
                 if bad or moved or not g.whole)
    checks = {
        "graphs_wrong": {"value": sum(wrong), "limit": 0},
        "graphs_reordered": {"value": sum(reordered), "limit": 0},
        "edges_dropped": {"value": sum(g.dropped for g in [warm] + graphs),
                          "limit": 0},
        "edges_missing": {"value": sum(g.requested - g.emitted
                                       for g in [warm] + graphs),
                          "limit": 0},
    }
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    result = {"correct": correct, "attempted": len(graphs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def parse_args(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell on the chip (see bench/harness.py).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the window's profiler trace here")
    return ap.parse_args(argv)


def main(argv, t0: float, root: str, require_chip: bool = True) -> int:
    args = parse_args(argv)
    try:
        cell = resolve(root, args.workload)
        result = execute(cell, args.seed, args.seconds, bool(args.trace), t0,
                         require_chip=require_chip, trace_dir=args.trace_dir)
    except BenchError as e:
        say(str(e))
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
