"""Collective-bytes regression gate (ROADMAP open item), per-topology.

Compiles the real sharded PBA exchange program on the forced-host-device
mesh (scenario configuration resolved through the ``repro.api`` front
door: GraphSpec -> plan) and reads its total 'bytes accessed' through the
version-portable ``repro.runtime.spmd.cost_analysis`` shim. Four
mechanical checks:

  1. Capacity scaling (flat topology): shrinking ``pair_capacity`` 4x must
     shrink the compiled program's bytes accessed — if the exchange buffers
     ever stop depending on the capacity knob (e.g. an accidental full-size
     materialization sneaks in), this inequality breaks immediately and
     version-independently. The same inequality holds for the
     device-sharded stream's per-round program over the rounds knob (1b):
     its buffers are (lp, P, C_r) with C_r = ceil(C / R).
  2. Hierarchical locality at pod scale: at P = 1000 logical ranks over the
     2-D pods topologies, the two-hop transpose's *cross-pod wire bytes*
     (the (g-1)/g fraction of the strided-replica-group all_to_alls — what
     the thin cross-pod fabric actually carries) must stay <= the flat
     all_to_all's total wire bytes at equal (P, C). This is the whole point
     of the topology-aware exchange; if a layout change ever routes bulk
     bytes over the cross-pod hop, the gate trips.
  2b. Communication-free head-to-head at matched (P, E): the cfree sharded
     program (benchmarks/cfree_expand.py measures the same pair) must
     compile to exactly zero all_to_all instructions and zero wire bytes
     on every gate topology, while the PBA exchange at the same logical
     rank count and edge count moves real wire bytes — the paper-family
     contrast the cfree executors exist to provide, pinned structurally.

  3. Baseline drift, per topology: bytes accessed at the reference config
     must stay within TOLERANCE of scripts/collective_bytes_baseline.json
     (committed — results/ is gitignored, and a baseline that vanishes on
     every fresh clone would make this half of the gate vacuous). Missing
     baselines are (re)written and reported, so the gate bootstraps itself;
     delete the file to re-baseline after an intentional exchange change.

  4. Compiled-collective audit + drift (repro.analysis.audit): the exchange
     programs must pass the SPMD-uniformity audit (all-reduced while
     predicates, topology-matching all_to_all counts), and their per-kind
     HLO collective *instruction* counts must not grow over the committed
     results/collective_audit_baseline.json — a new collective in a
     compiled program is a reviewed, intentional diff (delete the baseline
     to re-baseline after one).

  5. Kernel inventory drift (repro.analysis.kernelcheck): pallascheck's
     static checks must pass over the registry, and the structural view of
     its inventory (grids, block shapes, VMEM estimates, derived caps) must
     match the committed results/kernel_audit_baseline.json exactly — a
     grid or BlockSpec change in a Pallas kernel is a reviewed diff (delete
     the baseline to re-baseline after one).

  6. Flow inventory drift (repro.analysis.flowcheck): the jaxpr dataflow
     verifier must pass over every front-door program (RNG lineage from
     the declared determinism roots, blocked-layout axis roles on every
     all_to_all, spec-digest soundness per GraphSpec field), and the
     structural view of its inventory (verified transpose signatures,
     per-program RNG-primitive multisets and collective routes, digest
     field classes) must match the committed
     results/flow_audit_baseline.json exactly — a new draw site or
     collective route in a front-door program is a reviewed diff (delete
     the baseline to re-baseline after one).

  7. Round-program perf trajectory (benchmarks/round_block.py): re-measure
     the committed BENCH_round_block.json sweep and fail if any sweep
     point's per-round HLO bytes or flops regress past 1.25x the committed
     value (either leg). Skipped when the device count differs from the
     committed record's.

Exits 0 with a notice when the backend offers no cost analysis.

Usage (see scripts/verify.sh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python scripts/collective_gate.py
"""
from __future__ import annotations

import json
import os
import sys

import jax

from repro import api
from repro.api import GraphSpec
from repro.core import FactionSpec
from repro.launch.bench import (compile_sharded_cfree, compile_sharded_pba,
                                compile_sharded_stream_round)
from repro.launch.hlo_stats import all_to_all_span_bytes
from repro.runtime import Topology, spmd

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "collective_bytes_baseline.json")
AUDIT_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", "collective_audit_baseline.json")
KERNEL_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", "kernel_audit_baseline.json")
FLOW_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", "flow_audit_baseline.json")
TOLERANCE = 0.25  # fractional drift allowed before the gate trips
BENCH_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_round_block.json")
BENCH_TOLERANCE = 0.25  # per-round byte/flop regression bound (1.25x)

# Pod-scale reference: the paper's 1000 MPI ranks as logical processors
# over the forced host devices (lp = 1000 / D).
POD_SCALE_P = 1000


def _spec(procs: int, vpp: int, k: int, pair_capacity, topo: Topology
          ) -> GraphSpec:
    return GraphSpec(
        model="pba", procs=procs, vertices_per_proc=vpp, edges_per_vertex=k,
        seed=7, pair_capacity=pair_capacity,
        factions=FactionSpec(max(procs // 2, 1), 2, max(procs // 2, 2),
                             seed=1),
        topology=topo, execution="sharded")


def compile_exchange(pl: "api.GenPlan"):
    """Compiled sharded PBA program for a plan (lp = P / D per device)."""
    fn, args = compile_sharded_pba(pl)
    return fn.lower(*args).compile()


def compiled_bytes(pl: "api.GenPlan") -> float:
    compiled = compile_exchange(pl)
    return float(spmd.cost_analysis(compiled).get("bytes accessed", 0.0))


def gate_topologies(n_dev: int) -> list[Topology]:
    topos = [Topology.flat(n_dev)]
    if n_dev >= 4 and n_dev % 2 == 0:
        topos.append(Topology.pods(2, n_dev // 2))
        topos.append(Topology.pods(n_dev // 2, 2))
    return topos


def main() -> int:
    n_dev = len(jax.devices())
    flat = Topology.flat(n_dev)

    # --- 1: capacity scaling on the flat topology ---------------------------
    big = compiled_bytes(api.plan(_spec(n_dev, 200, 3, 256, flat)))
    small = compiled_bytes(api.plan(_spec(n_dev, 200, 3, 64, flat)))
    if big == 0.0:
        print("collective gate: backend offers no cost analysis — skipped")
        return 0
    print(f"collective gate: bytes accessed C=256 -> {big:.0f}, "
          f"C=64 -> {small:.0f}")
    if small >= big:
        print("collective gate FAILED: exchange bytes do not scale with "
              f"pair_capacity (C=64: {small:.0f} >= C=256: {big:.0f}) — "
              "a full-size buffer is being materialized somewhere",
              file=sys.stderr)
        return 1

    # --- 1b: streamed round buffers scale with 1/R --------------------------
    # One round of the device-sharded stream carries (lp, P, C_r) buffers;
    # doubling the configured rounds must shrink the compiled round
    # program. If it stops scaling, a full-capacity buffer is being
    # materialized inside the per-round path.
    def stream_round_bytes(rounds: int) -> float:
        pl = api.plan(_spec(n_dev, 200, 3, 256, flat).replace(
            execution="streamed", exchange_rounds=rounds))
        assert pl.executor == "pba_stream_sharded", pl.executor
        fn, args = compile_sharded_stream_round(pl)
        return float(spmd.cost_analysis(
            fn.lower(*args).compile()).get("bytes accessed", 0.0))

    stream_r2 = stream_round_bytes(2)
    stream_r8 = stream_round_bytes(8)
    print(f"collective gate: stream round bytes R=2 -> {stream_r2:.0f}, "
          f"R=8 -> {stream_r8:.0f}")
    if stream_r8 >= stream_r2:
        print("collective gate FAILED: sharded-stream round bytes do not "
              f"scale with rounds (R=8: {stream_r8:.0f} >= R=2: "
              f"{stream_r2:.0f}) — the per-round program is materializing "
              "a full-capacity buffer", file=sys.stderr)
        return 1

    # --- 2: pod-scale hierarchical locality at P = 1000 ---------------------
    topos = gate_topologies(n_dev)
    if POD_SCALE_P % n_dev:
        print(f"collective gate: {POD_SCALE_P} ranks do not divide over "
              f"{n_dev} devices — skipping the pod-scale leg")
        pod_bytes: dict[str, float] = {}
    else:
        pod_bytes = {}
        spans = {}
        for topo in topos:
            pl = api.plan(_spec(POD_SCALE_P, 40, 2, 8, topo))
            compiled = compile_exchange(pl)
            pod_bytes[topo.label] = float(
                spmd.cost_analysis(compiled).get("bytes accessed", 0.0))
            spans[topo.label] = all_to_all_span_bytes(compiled.as_text())
        flat_span = spans[flat.label]
        flat_wire = flat_span["local_wire"] + flat_span["cross_wire"]
        print(f"collective gate: P={POD_SCALE_P} flat all_to_all wire bytes "
              f"{flat_wire:.0f}")
        for topo in topos[1:]:
            cross = spans[topo.label]["cross_wire"]
            print(f"collective gate: P={POD_SCALE_P} {topo.label} "
                  f"cross-pod wire bytes {cross:.0f}")
            if cross > flat_wire:
                print(f"collective gate FAILED: {topo.label} cross-pod wire "
                      f"bytes {cross:.0f} exceed the flat all_to_all's "
                      f"{flat_wire:.0f} at equal (P, C) — the hierarchical "
                      "transpose is routing bulk bytes over the thin "
                      "cross-pod fabric", file=sys.stderr)
                return 1
            if spans[topo.label]["n_cross"] == 0:
                print(f"collective gate FAILED: {topo.label} compiled to no "
                      "strided-replica-group all_to_all — the cross-pod hop "
                      "is missing", file=sys.stderr)
                return 1

    # --- 2b: communication-free head-to-head at matched (P, E) --------------
    # PBA at (P, vpp=40, k=2) requests E = 80 * P edges; ba_cfree with
    # n = 40 * P vertices at degree 2 emits the identical count. Same
    # logical ranks, same edges — the exchange moves wire bytes, the
    # cfree program must move exactly none on any topology.
    p_match = POD_SCALE_P if POD_SCALE_P % n_dev == 0 else n_dev
    pba_span = all_to_all_span_bytes(
        compile_exchange(api.plan(_spec(p_match, 40, 2, 8, flat))).as_text())
    pba_wire = pba_span["local_wire"] + pba_span["cross_wire"]
    if n_dev > 1 and pba_wire <= 0:
        print("collective gate FAILED: the matched PBA exchange reports no "
              "all_to_all wire bytes — the head-to-head has no baseline to "
              "contrast against", file=sys.stderr)
        return 1
    for topo in topos:
        cpl = api.plan(GraphSpec(
            model="ba_cfree", cfree_vertices=40 * p_match, ba_degree=2,
            procs=p_match, seed=7, topology=topo, execution="sharded"))
        fn, args = compile_sharded_cfree(cpl)
        cspan = all_to_all_span_bytes(fn.lower(*args).compile().as_text())
        cwire = cspan["local_wire"] + cspan["cross_wire"]
        ncoll = cspan["n_local"] + cspan["n_cross"]
        print(f"collective gate: head-to-head P={p_match} "
              f"E={cpl.requested_edges} {topo.label}: cfree wire bytes "
              f"{cwire:.0f} ({ncoll} all_to_alls) vs pba exchange "
              f"{pba_wire:.0f}")
        if cwire != 0 or ncoll != 0:
            print(f"collective gate FAILED: {topo.label} cfree program "
                  f"compiled to {ncoll} all_to_alls / {cwire:.0f} wire "
                  "bytes — the communication-free contract is zero of "
                  "both", file=sys.stderr)
            return 1

    # --- 3: per-topology baseline drift -------------------------------------
    record = {"config": {"devices": n_dev, "vertices_per_proc": 200,
                         "edges_per_vertex": 3, "pair_capacity": 256,
                         "pod_scale_p": POD_SCALE_P,
                         "pod_scale_pair_capacity": 8},
              "topologies": {"flat_c256": big,
                             "flat_stream_round_r8": stream_r8,
                             **pod_bytes},
              "jax_version": jax.__version__}
    if not os.path.exists(BASELINE):
        with open(BASELINE, "w") as f:
            json.dump(record, f, indent=2)
        print(f"collective gate: wrote new baseline {BASELINE} "
              f"({sorted(record['topologies'])})")
        return 0

    with open(BASELINE) as f:
        base = json.load(f)
    base_topos = base.get("topologies")
    if base_topos is None:  # pre-topology schema: migrate in place
        base_topos = {flat.label: base["bytes_accessed"]}
    stale = False
    for label, measured in record["topologies"].items():
        if label not in base_topos:
            base_topos[label] = measured
            stale = True
            print(f"collective gate: baselined new topology {label} "
                  f"({measured:.0f} bytes)")
            continue
        limit = base_topos[label] * (1 + TOLERANCE)
        if measured > limit:
            print(f"collective gate FAILED: {label} bytes accessed "
                  f"{measured:.0f} exceeds baseline {base_topos[label]:.0f} "
                  f"(+{TOLERANCE:.0%} limit {limit:.0f}; baseline jax "
                  f"{base.get('jax_version')}). If the exchange-volume "
                  f"increase is intentional, delete {BASELINE} to "
                  "re-baseline.", file=sys.stderr)
            return 1
        print(f"collective gate OK: {label} {measured:.0f} <= {limit:.0f} "
              f"(baseline {base_topos[label]:.0f} +{TOLERANCE:.0%})")
    if stale:
        # Persist only the newly baselined labels — committed baselines win
        # over this run's measurements (otherwise within-tolerance drift
        # would ratchet into the baseline on every run that adds a label).
        base["topologies"] = {**record["topologies"], **base_topos}
        with open(BASELINE, "w") as f:
            json.dump(base, f, indent=2)

    # --- 4: compiled-collective audit + instruction-count drift -------------
    rc = audit_gate(n_dev, topos)
    if rc:
        return rc

    # --- 5: kernel inventory drift ------------------------------------------
    rc = kernel_gate()
    if rc:
        return rc

    # --- 6: flow inventory drift --------------------------------------------
    rc = flow_gate()
    if rc:
        return rc

    # --- 7: round-program perf trajectory -----------------------------------
    return bench_gate()


def audit_gate(n_dev: int, topos: list) -> int:
    """SPMD-uniformity audit of every gate program, then per-kind HLO
    collective instruction counts diffed against the committed baseline.
    Counts are static (no trip multiplication), so they only move when a
    collective is added to or removed from a compiled program — exactly
    the diff that should be a reviewed change."""
    from repro.analysis import audit as audit_lib

    flat = topos[0]
    audits = []
    for topo in topos:
        pl = api.plan(_spec(n_dev, 200, 3, 256, topo).replace(
            exchange_rounds=4))
        audits.append(audit_lib.audit_exchange(
            pl, label=f"{topo.label}/exchange_r4"))
    stream_pl = api.plan(_spec(n_dev, 200, 3, 256, flat).replace(
        execution="streamed", exchange_rounds=4))
    audits.append(audit_lib.audit_stream_round(stream_pl))
    # communication-free programs: the zero-all_to_all pin enters the same
    # drift baseline — a collective appearing in a cfree program is a
    # contract break, not just drift
    for topo in topos:
        for model, kw in (
                ("ba_cfree", {"cfree_vertices": 64 * n_dev, "ba_degree": 2}),
                ("rmat", {"cfree_vertices": 256,
                          "cfree_edges": 128 * n_dev}),
                ("er", {"cfree_vertices": 101, "cfree_edges": 128 * n_dev})):
            cpl = api.plan(GraphSpec(model=model, seed=7, topology=topo,
                                     execution="sharded", **kw))
            audits.append(audit_lib.audit_cfree(cpl))

    failed = False
    for a in audits:
        a2a = (f"all_to_alls {a.hlo_all_to_alls} "
               f"(expect {a.expected_all_to_alls})")
        print(f"collective gate: audit {a.label}: {a.hlo_collectives} {a2a}")
        for p in a.problems:
            print(f"collective gate FAILED: audit {a.label}: {p}",
                  file=sys.stderr)
            failed = True
    if failed:
        return 1

    inv = audit_lib.inventory(audits, extra={"devices": n_dev})
    if not os.path.exists(AUDIT_BASELINE):
        os.makedirs(os.path.dirname(AUDIT_BASELINE), exist_ok=True)
        with open(AUDIT_BASELINE, "w") as f:
            json.dump(inv, f, indent=2)
        print(f"collective gate: wrote new audit baseline {AUDIT_BASELINE} "
              f"({sorted(inv['programs'])})")
        return 0

    with open(AUDIT_BASELINE) as f:
        base = json.load(f)
    base_programs = base.get("programs", {})
    stale = False
    for label, prog in inv["programs"].items():
        counts = prog.get("hlo_collectives") or {}
        if label not in base_programs:
            base_programs[label] = prog
            stale = True
            print(f"collective gate: baselined new audit program {label} "
                  f"({counts})")
            continue
        base_counts = base_programs[label].get("hlo_collectives") or {}
        for kind, n in counts.items():
            if n > base_counts.get(kind, 0):
                print(f"collective gate FAILED: {label} compiles to {n} "
                      f"{kind} instruction(s), baseline has "
                      f"{base_counts.get(kind, 0)} — a new collective in a "
                      f"compiled program must be a reviewed diff (delete "
                      f"{AUDIT_BASELINE} to re-baseline)", file=sys.stderr)
                failed = True
        for kind, n in base_counts.items():
            if counts.get(kind, 0) < n:
                print(f"collective gate: note — {label} dropped to "
                      f"{counts.get(kind, 0)} {kind} (baseline {n}); "
                      f"re-baseline to lock in the improvement")
    if failed:
        return 1
    if stale:
        base["programs"] = base_programs
        with open(AUDIT_BASELINE, "w") as f:
            json.dump(base, f, indent=2)
    print(f"collective gate OK: audit counts match {AUDIT_BASELINE}")
    return 0


def kernel_gate() -> int:
    """pallascheck over the kernel registry (static checks only — the
    differential sanitizer runs in its own verify leg), then the
    structural view of the inventory diffed against the committed
    baseline. ANY structural difference fails: a kernel's grid, block
    shapes, VMEM estimate, or derived cap only moves via a reviewed
    re-commit of the baseline."""
    from repro.analysis import kernelcheck

    findings, inv = kernelcheck.run_registry(execute=False)
    for f in findings:
        print(f"collective gate FAILED: pallascheck {f.format()}",
              file=sys.stderr)
    if findings:
        return 1
    n_cases = sum(len(k["cases"]) for k in inv["kernels"].values())
    print(f"collective gate: pallascheck clean over "
          f"{len(inv['kernels'])} kernel(s), {n_cases} case(s)")

    view = kernelcheck.structural_view(inv)
    if not os.path.exists(KERNEL_BASELINE):
        os.makedirs(os.path.dirname(KERNEL_BASELINE), exist_ok=True)
        with open(KERNEL_BASELINE, "w") as f:
            json.dump(inv, f, indent=2)
        print(f"collective gate: wrote new kernel baseline "
              f"{KERNEL_BASELINE} ({sorted(inv['kernels'])})")
        return 0

    with open(KERNEL_BASELINE) as f:
        base = json.load(f)
    drift = kernelcheck.diff_paths(kernelcheck.structural_view(base), view)
    if drift:
        for path in drift[:20]:
            print(f"collective gate FAILED: kernel inventory drift at "
                  f"{path}", file=sys.stderr)
        if len(drift) > 20:
            print(f"collective gate FAILED: ... and {len(drift) - 20} more "
                  "drifted path(s)", file=sys.stderr)
        print("collective gate FAILED: a Pallas kernel's grid/BlockSpec/"
              "VMEM structure changed — if intentional, delete "
              f"{KERNEL_BASELINE} to re-baseline", file=sys.stderr)
        return 1
    print(f"collective gate OK: kernel inventory matches {KERNEL_BASELINE}")
    return 0


def flow_gate() -> int:
    """flowcheck over the front-door programs, then the structural view
    of the flow inventory diffed against the committed baseline. ANY
    structural difference fails: a program's RNG-primitive multiset, its
    all_to_all routes, a transpose's verified signatures, or a GraphSpec
    field's digest class only move via a reviewed re-commit of the
    baseline."""
    from repro.analysis import flowcheck

    findings, inv = flowcheck.run_flow()
    for f in findings:
        print(f"collective gate FAILED: flowcheck {f.format()}",
              file=sys.stderr)
    if findings:
        return 1
    print(f"collective gate: flowcheck clean over "
          f"{len(inv['programs'])} program(s), "
          f"{len(inv['digest_fields'])} digest field(s)")

    view = flowcheck.structural_view(inv)
    if not os.path.exists(FLOW_BASELINE):
        os.makedirs(os.path.dirname(FLOW_BASELINE), exist_ok=True)
        with open(FLOW_BASELINE, "w") as f:
            json.dump(inv, f, indent=2)
        print(f"collective gate: wrote new flow baseline {FLOW_BASELINE} "
              f"({sorted(inv['programs'])})")
        return 0

    with open(FLOW_BASELINE) as f:
        base = json.load(f)
    drift = flowcheck.diff_paths(flowcheck.structural_view(base), view)
    if drift:
        for path in drift[:20]:
            print(f"collective gate FAILED: flow inventory drift at "
                  f"{path}", file=sys.stderr)
        if len(drift) > 20:
            print(f"collective gate FAILED: ... and {len(drift) - 20} more "
                  "drifted path(s)", file=sys.stderr)
        print("collective gate FAILED: a front-door program's dataflow "
              "structure (RNG draws, collective routes, digest classes) "
              "changed — if intentional, delete "
              f"{FLOW_BASELINE} to re-baseline", file=sys.stderr)
        return 1
    print(f"collective gate OK: flow inventory matches {FLOW_BASELINE}")
    return 0


def bench_gate() -> int:
    """Per-round byte/flop regression against BENCH_round_block.json.

    Re-measures the committed sweep with the benchmark's own harness (both
    legs per point) and trips when a measurement exceeds the committed
    value by more than BENCH_TOLERANCE. These are HLO byte counts from a
    CPU compile, not speed."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import round_block

    if not os.path.exists(BENCH_BASELINE):
        record = round_block.run_sweep()
        with open(BENCH_BASELINE, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"collective gate: wrote new bench baseline {BENCH_BASELINE} "
              f"({[e['name'] for e in record['sweep']]})")
        return 0

    with open(BENCH_BASELINE) as f:
        base = json.load(f)
    n_dev = len(jax.devices())
    if base.get("devices") != n_dev:
        print(f"collective gate: bench baseline was recorded on "
              f"{base.get('devices')} devices, running on {n_dev} — "
              "skipping the perf-trajectory leg")
        return 0

    committed = {e["name"]: e for e in base.get("sweep", [])}
    failed = False
    for name, ref in committed.items():
        rec = round_block.measure(
            {k: ref[k] for k in ("procs", "rounds", "pair_capacity")})
        for leg in ("jnp", "fused"):
            for metric in ("bytes_accessed", "flops"):
                got, want = rec[leg][metric], ref[leg][metric]
                limit = want * (1 + BENCH_TOLERANCE)
                if got > limit:
                    print(f"collective gate FAILED: round_block {name} "
                          f"{leg}.{metric} {got:.0f} exceeds committed "
                          f"{want:.0f} (+{BENCH_TOLERANCE:.0%} limit "
                          f"{limit:.0f}) — if the per-round cost increase "
                          f"is intentional, re-run benchmarks/round_block "
                          f"and commit the new {BENCH_BASELINE}",
                          file=sys.stderr)
                    failed = True
    if failed:
        return 1
    print(f"collective gate OK: round-block perf within "
          f"+{BENCH_TOLERANCE:.0%} of {BENCH_BASELINE} "
          f"({sorted(committed)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
