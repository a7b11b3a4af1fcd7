"""Multi-device massive-graph generation with checkpoint/restart (the paper's
end-to-end scenario: the generator as a cluster service).

One front door: every scenario is a ``repro.api.GraphSpec`` compiled by
``api.plan`` (inspect it with --dry-run — no JAX compilation) and executed
by ``api.generate``. Run with N host devices to exercise the real
shard_map collectives:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/generate_massive.py --procs 8

Demonstrates: distributed PBA + PK, the multi-round streaming exchange
(--exchange-rounds: zero dropped edges with a 1/R-size exchange buffer),
out-of-core generation straight to resumable shards (--out-dir: the graph
only has to fit on disk; on D > 1 devices the stream runs device-sharded
— combine with --pods for the hierarchical exchange, and --no-overlap to
serialize the double-buffered rounds), preset scenarios (--preset paper_smoke,
paper_1b_5b, ...), plan inspection (--dry-run), generation-state
checkpointing (seed + partition is the whole state — regeneration beats
storage at >100M edges/s), and restart.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax

from repro import api
from repro.core import degree_counts, fit_power_law
from repro.runtime import spmd


def build_specs(args, state, n_dev):
    """(pba_spec, pk_spec) for the CLI flags + checkpoint state."""
    out_of_core = args.out_dir is not None
    topology = None
    if args.pods:
        # Works in-memory (hierarchical single-shot exchange) and
        # out-of-core (the device-sharded stream drives the same two-hop
        # transpose per round).
        from repro.runtime import Topology
        rows, cols = (int(x) for x in args.pods.lower().split("x"))
        if rows * cols != n_dev:
            raise SystemExit(f"--pods {args.pods} needs {rows * cols} "
                             f"devices, have {n_dev}")
        topology = Topology.pods(rows, cols)

    pba = api.GraphSpec(
        model="pba", procs=state["procs"],
        vertices_per_proc=state["vpp"], edges_per_vertex=state["k"],
        interfaction_prob=0.05, pair_capacity=args.pair_capacity,
        exchange_rounds=args.exchange_rounds, seed=state["seed"],
        topology=topology, overlap=args.overlap,
        execution="streamed" if out_of_core else "auto",
        sink="shards" if out_of_core else "memory",
        out_dir=os.path.join(args.out_dir, "pba") if out_of_core else None)
    pk = api.GraphSpec(
        model="pk", levels=args.pk_levels, noise=0.05, seed=3,
        slab_edges=args.pk_slab_edges,
        execution="streamed" if out_of_core else "auto",
        sink="shards" if out_of_core else "memory",
        out_dir=os.path.join(args.out_dir, "pk") if out_of_core else None)
    return pba, pk


def main() -> None:
    spmd.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default=None, choices=sorted(api.PRESETS),
                    help="run a named scenario (overrides the scale flags)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the resolved plan(s) and exit without "
                         "generating (no JAX compilation)")
    ap.add_argument("--procs", type=int, default=len(jax.devices()),
                    help="logical processors; may exceed device count "
                         "(paper: 1000 ranks) as long as it divides evenly")
    ap.add_argument("--vertices-per-proc", type=int, default=100_000)
    ap.add_argument("--edges-per-vertex", type=int, default=5)
    ap.add_argument("--pair-capacity", type=int, default=None,
                    help="per-(sender,receiver) exchange budget C; default "
                         "heuristic from faction sizes")
    ap.add_argument("--exchange-rounds", type=int, default=None,
                    help="stream exchange 2 over R rounds of capacity "
                         "ceil(C/R) — zero dropped edges, 1/R exchange "
                         "memory; default: legacy single-shot exchange")
    ap.add_argument("--pods", default=None, metavar="RxC",
                    help="run the exchange over a hierarchical RxC pod "
                         "topology (e.g. 2x4: two-hop intra-pod/cross-pod "
                         "all_to_all; bit-identical output, pod-local "
                         "bulk traffic); default: flat 1-D mesh")
    ap.add_argument("--pk-levels", type=int, default=4)
    ap.add_argument("--out-dir", default=None,
                    help="out-of-core mode: stream per-round PBA blocks and "
                         "per-slab PK blocks to resumable shards here "
                         "instead of materializing edge lists")
    ap.add_argument("--pk-slab-edges", type=int, default=1 << 20)
    ap.add_argument("--overlap", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="sharded-streamed out-of-core mode: double-buffer "
                         "rounds (dispatch round r+1's device grant while "
                         "round r's block is written back); --no-overlap "
                         "serializes them for comparison")
    ap.add_argument("--ckpt", default="/tmp/repro_gen_ckpt.json")
    args = ap.parse_args()
    n_dev = len(jax.devices())

    if args.preset:
        spec = api.preset(args.preset)
        if args.out_dir:
            spec = spec.replace(execution="streamed", sink="shards",
                                overlap=args.overlap,
                                out_dir=os.path.join(args.out_dir,
                                                     spec.model))
        pl = api.plan(spec)
        print(f"preset {args.preset}:")
        print(pl.describe())
        if args.dry_run:
            return
        t0 = time.perf_counter()
        res = api.generate(pl)
        t = time.perf_counter() - t0
        tag = "PBA" if spec.model == "pba" else "PK"
        where = f" -> {res.out_dir}" if res.out_dir else ""
        print(f"{tag}: {res.stats.emitted_edges:,} edges{where} in {t:.2f}s "
              f"({res.stats.emitted_edges / t:.3e} edges/s) "
              f"drops={res.stats.dropped_edges} "
              f"rounds={res.stats.exchange_rounds}")
        return

    procs = args.procs
    if procs % n_dev:
        procs = max((procs // n_dev) * n_dev, n_dev)
    print(f"devices: {n_dev}, logical processors: {procs}")

    # --- checkpoint = the generation spec; restart resumes deterministically
    state = {"seed": 7, "procs": procs,
             "vpp": args.vertices_per_proc, "k": args.edges_per_vertex}
    if os.path.exists(args.ckpt):
        with open(args.ckpt) as f:
            state = json.load(f)
        print(f"restarted from {args.ckpt}: {state}")
        # The checkpointed logical-proc count defines the graph; it cannot
        # be re-derived without generating a *different* graph, so restarts
        # on hardware that cannot host it must fail loudly, not crash deep
        # inside split_logical. Out-of-core mode without an explicit
        # topology is exempt: the planner falls back to the host-driven
        # stream, which handles any logical-proc count (and emits the
        # identical blocks). An explicit --pods topology has no fallback,
        # so it keeps the loud checkpoint-aware error.
        if state["procs"] % n_dev and (args.pods or not args.out_dir):
            raise SystemExit(
                f"checkpoint {args.ckpt} was written for "
                f"{state['procs']} logical processors, which does not "
                f"divide over the {n_dev} devices present. Restart on a "
                f"device count that divides {state['procs']}, delete the "
                "checkpoint to start a new generation, or resume "
                "out-of-core with --out-dir.")
    elif not args.dry_run:
        # a dry run is pure inspection — it must not seed restart state
        with open(args.ckpt, "w") as f:
            json.dump(state, f)

    pba_spec, pk_spec = build_specs(args, state, n_dev)
    pba_plan = api.plan(pba_spec)
    pk_plan = api.plan(pk_spec)
    if args.dry_run:
        print(pba_plan.describe())
        print(pk_plan.describe())
        return

    t0 = time.perf_counter()
    res = api.generate(pba_plan)
    if res.edges is not None:
        jax.block_until_ready(res.edges.src)
    t = time.perf_counter() - t0
    stats = res.stats
    where = f" -> {res.out_dir}" if res.out_dir else ""
    rounds = (f" rounds={stats.exchange_rounds}"
              if args.exchange_rounds or args.out_dir else "")
    print(f"PBA: {stats.emitted_edges:,} edges{where}, {state['procs']} "
          f"logical procs on {n_dev} devices in {t:.2f}s "
          f"({stats.emitted_edges / t:.3e} edges/s) "
          f"drops={stats.dropped_edges}{rounds}")

    if res.edges is not None:
        deg = np.asarray(degree_counts(res.edges))
        fit = fit_power_law(deg, kmin=5)
        print(f"     gamma_mle={fit.gamma_mle:.2f}, max_degree={deg.max()}")

    t0 = time.perf_counter()
    pk_res = api.generate(pk_plan)
    if pk_res.edges is not None:
        jax.block_until_ready(pk_res.edges.src)
    t = time.perf_counter() - t0
    where = f" -> {pk_res.out_dir}" if pk_res.out_dir else ""
    print(f"PK:  {pk_res.stats.emitted_edges:,} edges{where} in {t:.2f}s "
          f"({pk_res.stats.emitted_edges / t:.3e} edges/s, "
          f"zero communication)")


if __name__ == "__main__":
    main()
