"""Per-round perf trajectory of the sharded stream's round program.

Sweeps the compiled device-sharded round program (``pba_stream_round_block``
under shard_map — grant, blocked transpose, band gather/count/compaction)
over P x R x capacity and records, per configuration:

  * **jnp leg**: the round program compiled with the kernel dispatch forced
    off, i.e. the historical pure-XLA formulation (take_along_axis grants,
    argsort band compaction). HLO flops / bytes accessed / collective bytes
    come from ``repro.launch.hlo_stats.collect_hlo_costs``.
  * **fused leg**: the same program with the Pallas kernels in the hot
    path. Only the per-provider census is a kernel (``histogram.py``): the
    grant/band gathers and the band compaction are XLA on every backend,
    so they count as glue. Interpret-mode Pallas compiles to the
    *interpreter's* HLO (and on TPU the kernels are opaque custom-calls),
    so the leg is split: the XLA glue is compiled with every
    ``pl.pallas_call`` swapped for a dependency-keeping stub (reduce
    inputs, broadcast into the outputs — a zeros stub would let XLA
    dead-code the surrounding program), and the kernel's HBM traffic is
    added from ``histogram_traffic_bytes``.

The resulting ``BENCH_round_block.json`` is committed at the repo root as
the count baseline (HLO bytes from a CPU compile, not speed);
scripts/collective_gate.py re-measures it and fails on a >1.25x per-round
byte/flop regression.

Usage (the committed baseline is recorded on the 8-device host mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m benchmarks.round_block [--smoke] [--out PATH]

``--smoke`` runs the first sweep point only and validates the emitted
record's schema against the committed baseline's keys (the CI bench-smoke
job) instead of writing anything.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import jax

from repro import api
from repro.api import GraphSpec
from repro.core.pba import stream_block_capacity
from repro.kernels import dispatch
from repro.kernels.histogram import histogram_traffic_bytes
from repro.launch.bench import compile_sharded_stream_round
from repro.launch.hlo_stats import collect_hlo_costs

BASELINE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_round_block.json")

# P x R x capacity sweep (P = procs over the 8-device host mesh).
SWEEP = (
    {"procs": 8, "rounds": 2, "pair_capacity": 64},
    {"procs": 8, "rounds": 8, "pair_capacity": 64},
    {"procs": 8, "rounds": 4, "pair_capacity": 256},
    {"procs": 16, "rounds": 4, "pair_capacity": 128},
)
VPP, K = 200, 3  # vertices/proc, edges/vertex — e_local = VPP * K

#: pl.pallas_call sites one round program traces: the per-provider
#: histogram, once per resident row.
EXPECTED_KERNELS = ("_hist_kernel",)


def _round_spec(procs: int, rounds: int, pair_capacity: int) -> GraphSpec:
    return GraphSpec(model="pba", procs=procs, vertices_per_proc=VPP,
                     edges_per_vertex=K, seed=7,
                     pair_capacity=pair_capacity, exchange_rounds=rounds,
                     execution="streamed")


@contextlib.contextmanager
def _stub_pallas_calls(calls: list):
    """Swap ``pl.pallas_call`` for a dependency-keeping stub.

    Each stubbed call reduces every input and broadcasts the scalar into
    correctly shaped outputs, so the surrounding XLA program keeps its real
    data dependencies (nothing upstream or downstream is dead-code
    eliminated) while the kernel bodies contribute ~no HLO traffic — their
    HBM bytes are accounted analytically by :func:`kernel_round_traffic`.
    Appends (kernel_name, arg_shapes) per traced call to ``calls``.
    """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def fake(kernel, *, out_shape=None, grid=None, in_specs=None,
             out_specs=None, **kwargs):
        shapes = (list(out_shape) if isinstance(out_shape, (tuple, list))
                  else [out_shape])
        name = getattr(kernel, "func", kernel).__name__

        def runner(*args):
            calls.append((name, tuple(a.shape for a in args)))
            acc = jnp.int32(0)
            for a in args:
                acc = acc + jnp.sum(a).astype(jnp.int32)
            outs = tuple(jnp.zeros(s.shape, s.dtype) + acc.astype(s.dtype)
                         for s in shapes)
            return outs if isinstance(out_shape, (tuple, list)) else outs[0]

        return runner

    real = pl.pallas_call   # spmdlint: disable=RPR007 — glue-measuring stub
    pl.pallas_call = fake   # spmdlint: disable=RPR007 — glue-measuring stub
    try:
        yield calls
    finally:
        pl.pallas_call = real  # spmdlint: disable=RPR007 — restore


def kernel_round_traffic(pl: "api.GenPlan") -> float:
    """Analytic HBM bytes of the Pallas kernels one round program issues
    (per-device module: each of the lp resident rows runs the vmapped
    census kernel)."""
    return pl.lp * histogram_traffic_bytes(pl.config.edges_per_proc,
                                           pl.num_procs)


def _leg_record(hlo: str) -> dict:
    c = collect_hlo_costs(hlo)
    return {"flops": c.flops, "bytes_accessed": c.hbm_bytes,
            "collective_bytes": c.collective.total_bytes}


def measure(entry: dict) -> dict:
    """Both legs of one sweep point; returns the JSON record."""
    from repro.core import stream as stream_mod

    pl = api.plan(_round_spec(**entry))
    assert pl.executor == "pba_stream_sharded", pl.executor

    def compiled_hlo() -> str:
        fn, args = compile_sharded_stream_round(pl)
        return fn.lower(*args).compile().as_text()

    stream_mod._sharded_grant_fns.cache_clear()
    with dispatch.forced_mode("off"):
        jnp_leg = _leg_record(compiled_hlo())

    stream_mod._sharded_grant_fns.cache_clear()
    calls: list = []
    with dispatch.forced_mode("interpret"), _stub_pallas_calls(calls):
        fused = _leg_record(compiled_hlo())
    stream_mod._sharded_grant_fns.cache_clear()

    names = tuple(sorted(name for name, _ in calls))
    if names != tuple(sorted(EXPECTED_KERNELS)):
        raise AssertionError(
            f"round program traced kernels {names}, expected "
            f"{tuple(sorted(EXPECTED_KERNELS))} — a hot-path call site "
            "stopped routing through the Pallas kernels")

    kernel_bytes = kernel_round_traffic(pl)
    fused["glue_bytes"] = fused["bytes_accessed"]
    fused["kernel_bytes"] = kernel_bytes
    fused["kernel_calls"] = len(calls)
    fused["bytes_accessed"] = fused["glue_bytes"] + kernel_bytes

    name = (f"p{entry['procs']}_r{entry['rounds']}"
            f"_c{entry['pair_capacity']}")
    return {"name": name, **entry, "lp": pl.lp,
            "round_capacity": pl.round_capacity,
            "block_cap": stream_block_capacity(
                pl.config.edges_per_proc, pl.num_procs, pl.round_capacity),
            "jnp": jnp_leg, "fused": fused,
            "fused_over_jnp_bytes": (fused["bytes_accessed"]
                                     / max(jnp_leg["bytes_accessed"], 1.0))}


def run_sweep(entries=SWEEP) -> dict:
    records = []
    for entry in entries:
        rec = measure(entry)
        print(f"round_block {rec['name']}: jnp "
              f"{rec['jnp']['bytes_accessed']:.0f} B -> fused "
              f"{rec['fused']['bytes_accessed']:.0f} B "
              f"({rec['fused_over_jnp_bytes']:.2f}x), collective "
              f"{rec['jnp']['collective_bytes']:.0f} B", flush=True)
        records.append(rec)
    return {"schema": 1, "devices": len(jax.devices()),
            "vertices_per_proc": VPP, "edges_per_vertex": K,
            "sweep": records}


def smoke() -> int:
    """One sweep point + schema validation against the committed baseline."""
    record = run_sweep(SWEEP[:1])
    if not os.path.exists(BASELINE):
        print(f"round_block smoke FAILED: committed baseline {BASELINE} "
              "is missing", file=sys.stderr)
        return 1
    with open(BASELINE) as f:
        base = json.load(f)
    problems = []
    if set(base) != set(record):
        problems.append(f"top-level keys {sorted(record)} != committed "
                        f"{sorted(base)}")
    committed = {e["name"]: e for e in base.get("sweep", [])}
    for rec in record["sweep"]:
        ref = committed.get(rec["name"])
        if ref is None:
            problems.append(f"sweep point {rec['name']} not in baseline "
                            f"{sorted(committed)}")
            continue
        if set(ref) != set(rec):
            problems.append(f"{rec['name']}: entry keys {sorted(rec)} != "
                            f"committed {sorted(ref)}")
        for leg in ("jnp", "fused"):
            if set(ref.get(leg, {})) != set(rec.get(leg, {})):
                problems.append(
                    f"{rec['name']}.{leg}: keys {sorted(rec.get(leg, {}))} "
                    f"!= committed {sorted(ref.get(leg, {}))}")
    for p in problems:
        print(f"round_block smoke FAILED: {p}", file=sys.stderr)
    if not problems:
        print("round_block smoke OK: record schema matches "
              f"{os.path.basename(BASELINE)}")
    return 1 if problems else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="first sweep point only; validate schema against "
                         "the committed baseline, write nothing")
    ap.add_argument("--out", default=BASELINE,
                    help="output JSON path (default: the committed "
                         "BENCH_round_block.json)")
    ns = ap.parse_args(argv)
    if ns.smoke:
        return smoke()
    record = run_sweep()
    with open(ns.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"round_block: wrote {ns.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
