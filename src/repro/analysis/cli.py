"""Command line for the static-analysis subsystem.

  python -m repro.analysis [paths...] [--format text|github|json|sarif]
      lint the repo's configured paths (exit 1 on any violation)

  python -m repro.analysis audit [--out FILE] [--no-hlo]
      trace + compile the gate topologies' exchange programs on the
      current device set and run the SPMD-uniformity audit (exit 1 on
      any structural problem). Run under forced host devices to audit
      multi-device structure, e.g.
      XLA_FLAGS=--xla_force_host_platform_device_count=8.

  python -m repro.analysis kernels [--out FILE] [--backend B]
                                   [--static-only]
      pallascheck: statically verify every registered pl.pallas_call
      (grid/BlockSpec partition + race, VMEM working set vs budget,
      ref-oracle parity, interpret differential) and emit the kernel
      inventory JSON the drift gate diffs. Exit 1 on any finding.

  python -m repro.analysis flow [--out FILE] [--no-digest]
      flowcheck: jaxpr dataflow verifier over the front-door programs —
      RNG lineage vs the declared determinism roots (FC001), blocked-
      layout axis-role typing of every all_to_all (FC002), and
      spec-digest soundness per GraphSpec field (FC003). Exit 1 on any
      finding. Run under forced host devices for multi-device structure.

The audit/kernels/flow subcommands all take ``--format text|json|sarif``;
their SARIF logs merge with spmdlint's via scripts/merge_sarif.py into
one code-scanning artifact. The lint path imports no JAX — it stays fast
enough for a pre-commit hook.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence

from repro.analysis.linter import (Violation, find_repo_root, lint_paths,
                                   load_config)


def format_violations(violations: Sequence[Violation], fmt: str) -> str:
    if fmt == "github":
        return "\n".join(
            f"::error file={v.path},line={v.line},col={v.col},"
            f"title={v.rule}::{v.message}" for v in violations)
    if fmt == "json":
        return json.dumps([vars(v) for v in violations], indent=2)
    if fmt == "sarif":
        return json.dumps(_sarif(violations), indent=2)
    return "\n".join(v.format() for v in violations)


def _sarif(violations: Sequence[Violation]) -> dict:
    """SARIF 2.1.0 log for code-scanning upload (one run, tool spmdlint)."""
    from repro.analysis.rules import all_rules
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                    "master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "spmdlint",
                "informationUri": "https://example.invalid/repro/analysis",
                "rules": [{"id": r.id,
                           "shortDescription": {"text": r.title}}
                          for r in all_rules()],
            }},
            "results": [{
                "ruleId": v.rule,
                "level": "error",
                "message": {"text": v.message},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {"uri": v.path},
                    "region": {"startLine": v.line,
                               "startColumn": v.col + 1},
                }}],
            } for v in violations],
        }],
    }


def _validate_out(ap: argparse.ArgumentParser, out: Optional[str]) -> None:
    """Fail --out fast (before JAX import / long traces) when the target
    cannot be written: nonexistent or unwritable parent directory, the
    target being a directory, or an existing read-only target."""
    if out is None:
        return
    import os
    path = os.path.abspath(out)
    parent = os.path.dirname(path)
    if not os.path.isdir(parent):
        ap.error(f"--out {out}: parent directory {parent} does not exist")
    if not os.access(parent, os.W_OK):
        ap.error(f"--out {out}: parent directory {parent} is not writable")
    if os.path.isdir(path):
        ap.error(f"--out {out}: is a directory, not a writable file path")
    if os.path.exists(path) and not os.access(path, os.W_OK):
        ap.error(f"--out {out}: existing file is not writable")


def _generic_sarif(tool_name: str, rules: dict, results) -> dict:
    """SARIF 2.1.0 log for the JAX-backed analyzers (audit/kernels/flow).

    ``rules`` maps rule id -> short title; ``results`` is an iterable of
    (rule_id, message, uri) where uri names the analyzed artifact (a
    program label or kernel case — these findings locate in traced
    programs, not source lines).
    """
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                    "master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": tool_name,
                "informationUri": "https://example.invalid/repro/analysis",
                "rules": [{"id": rid,
                           "shortDescription": {"text": title}}
                          for rid, title in sorted(rules.items())],
            }},
            "results": [{
                "ruleId": rid,
                "level": "error",
                "message": {"text": msg},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {"uri": uri},
                }}],
            } for rid, msg, uri in results],
        }],
    }


def lint_main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="spmdlint: SPMD invariant linter (rules RPR001..)")
    ap.add_argument("paths", nargs="*",
                    help="repo-relative paths (default: pyproject config)")
    ap.add_argument("--format", choices=("text", "github", "json", "sarif"),
                    default="text")
    ap.add_argument("--root", default=None,
                    help="repo root (default: nearest pyproject.toml)")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule IDs to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ns = ap.parse_args(argv)

    from repro.analysis.rules import all_rules, rules_by_id
    if ns.list_rules:
        for rule in all_rules():
            print(f"{rule.id}  {rule.title}")
        return 0
    root = ns.root or find_repo_root()
    rules = (rules_by_id(ns.select.split(",")) if ns.select else None)
    violations = lint_paths(root, paths=ns.paths or None, rules=rules,
                            config=load_config(root))
    if violations:
        print(format_violations(violations, ns.format))
        if ns.format != "json":
            print(f"spmdlint: {len(violations)} violation(s)",
                  file=sys.stderr)
        return 1
    if ns.format == "json":
        print("[]")
    elif ns.format == "sarif":
        print(json.dumps(_sarif(())))
    else:
        print("spmdlint: clean")
    return 0


def audit_main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis audit",
        description="compiled-collective SPMD-uniformity audit")
    ap.add_argument("--out", default=None,
                    help="write the JSON inventory here")
    ap.add_argument("--no-hlo", action="store_true",
                    help="jaxpr-level checks only (no compile)")
    ap.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text")
    ns = ap.parse_args(argv)
    _validate_out(ap, ns.out)

    import jax

    from repro import api
    from repro.analysis import audit as audit_lib
    from repro.core import FactionSpec

    n_dev = len(jax.devices())
    from repro.runtime import Topology
    topos = [Topology.flat(n_dev)]
    if n_dev >= 4 and n_dev % 2 == 0:
        topos.append(Topology.pods(2, n_dev // 2))

    audits = []
    for topo in topos:
        spec = api.GraphSpec(
            model="pba", procs=n_dev, vertices_per_proc=200,
            edges_per_vertex=3, seed=7, pair_capacity=256,
            factions=FactionSpec(max(n_dev // 2, 1), 2,
                                 max(n_dev // 2, 2), seed=1),
            topology=topo, execution="sharded")
        audits.append(audit_lib.audit_exchange(
            api.plan(spec), with_hlo=not ns.no_hlo))
        # multi-round + streamed configs share one r4 spec (planned once
        # per execution mode: the residual while_loop + per-round program)
        r4 = spec.replace(exchange_rounds=4)
        audits.append(audit_lib.audit_exchange(
            api.plan(r4), with_hlo=not ns.no_hlo,
            label=f"{topo.label}/exchange_r4"))
        streamed = api.plan(r4.replace(execution="streamed"))
        if streamed.executor == "pba_stream_sharded":
            audits.append(audit_lib.audit_stream_round(
                streamed, with_hlo=not ns.no_hlo))
        # communication-free family: pinned to zero collectives per topo
        for model, kw in (("ba_cfree", {"cfree_vertices": 64 * n_dev,
                                        "ba_degree": 2}),
                          ("rmat", {"cfree_vertices": 256,
                                    "cfree_edges": 128 * n_dev}),
                          ("er", {"cfree_vertices": 101,
                                  "cfree_edges": 128 * n_dev})):
            cspec = api.GraphSpec(model=model, seed=7, topology=topo,
                                  execution="sharded", **kw)
            audits.append(audit_lib.audit_cfree(
                api.plan(cspec), with_hlo=not ns.no_hlo))

    inv = audit_lib.inventory(audits, extra={"devices": n_dev})
    if ns.out:
        with open(ns.out, "w") as f:
            json.dump(inv, f, indent=2)
        print(f"audit: wrote {ns.out}", file=sys.stderr)
    rc = 0
    for a in audits:
        status = "OK " if a.ok else "FAIL"
        hlo = ("" if a.hlo_all_to_alls is None else
               f" all_to_alls={a.hlo_all_to_alls}"
               f"(expect {a.expected_all_to_alls})")
        if ns.format == "text":
            print(f"audit {status} {a.label}: "
                  f"jaxpr={a.jaxpr_collectives}{hlo}")
        for p in a.problems:
            print(f"  problem: {p}", file=sys.stderr)
            rc = 1
    if ns.format == "json":
        print(json.dumps(inv, indent=2))
    elif ns.format == "sarif":
        print(json.dumps(_generic_sarif(
            "spmd-audit",
            {"SPMD-AUDIT": "compiled collective structure violates the "
                           "SPMD-uniformity contract"},
            [("SPMD-AUDIT", p, a.label)
             for a in audits for p in a.problems]), indent=2))
    return rc


def kernels_main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis kernels",
        description="pallascheck: static grid/BlockSpec race & VMEM "
                    "verifier over the kernel registry")
    ap.add_argument("--out", default=None,
                    help="write the kernel inventory JSON here")
    ap.add_argument("--backend", default="tpu",
                    help="VMEM budget model to check against (default: tpu)")
    ap.add_argument("--static-only", action="store_true",
                    help="skip the interpret-vs-ref differential sanitizer")
    ap.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text")
    ns = ap.parse_args(argv)
    _validate_out(ap, ns.out)

    from repro.analysis import kernelcheck

    findings, inv = kernelcheck.run_registry(backend=ns.backend,
                                             execute=not ns.static_only)
    if ns.out:
        with open(ns.out, "w") as f:
            json.dump(inv, f, indent=2)
        print(f"pallascheck: wrote {ns.out}", file=sys.stderr)
    if ns.format == "json":
        print(json.dumps(inv, indent=2))
    elif ns.format == "sarif":
        print(json.dumps(_generic_sarif(
            "pallascheck", kernelcheck.KIND_TITLES,
            [(f.kind, f.message, f"{f.kernel}/{f.case}")
             for f in findings]), indent=2))
    else:
        n_cases = sum(len(k["cases"]) for k in inv["kernels"].values())
        n_calls = sum(len(c["calls"]) for k in inv["kernels"].values()
                      for c in k["cases"].values())
        print(f"pallascheck: {len(inv['kernels'])} kernel(s), {n_cases} "
              f"case(s), {n_calls} pallas_call(s) against "
              f"{inv['budget']['vmem_bytes']} B VMEM budget "
              f"({inv['budget']['backend']})")
    if findings:
        for f in findings:
            print(f"pallascheck FAIL {f.format()}", file=sys.stderr)
        print(f"pallascheck: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    if ns.format == "text":
        print("pallascheck: clean")
    return 0


def flow_main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis flow",
        description="flowcheck: jaxpr dataflow verifier (RNG lineage, "
                    "blocked-layout axis roles, spec-digest soundness)")
    ap.add_argument("--out", default=None,
                    help="write the flow inventory JSON here")
    ap.add_argument("--no-digest", action="store_true",
                    help="skip the FC003 spec-digest soundness pass "
                    "(faster; FC001/FC002 only)")
    ap.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text")
    ns = ap.parse_args(argv)
    _validate_out(ap, ns.out)

    from repro.analysis import flowcheck

    findings, inv = flowcheck.run_flow(digest=not ns.no_digest)
    if ns.out:
        with open(ns.out, "w") as f:
            json.dump(inv, f, indent=2)
        print(f"flowcheck: wrote {ns.out}", file=sys.stderr)
    if ns.format == "json":
        print(json.dumps(inv, indent=2))
    elif ns.format == "sarif":
        print(json.dumps(_generic_sarif(
            "flowcheck", flowcheck.KIND_TITLES,
            [(f.kind, f.message, f"{f.program}/{f.where}")
             for f in findings]), indent=2))
    else:
        for label, p in sorted(inv["programs"].items()):
            rng = ",".join(f"{k}x{v}"
                           for k, v in sorted(p.get("rng_prims",
                                                    {}).items()))
            print(f"flowcheck {'OK  ' if p.get('ok') else 'FAIL'} {label}: "
                  f"rng=[{rng}] all_to_all={p.get('all_to_all', [])}")
        for topo, entries in sorted(inv["transposes"].items()):
            ok = all(e["ok"] for e in entries.values())
            print(f"flowcheck {'OK  ' if ok else 'FAIL'} {topo}: roles "
                  f"verified for {sorted(entries)}")
        if inv["digest_fields"]:
            n = len(inv["digest_fields"])
            print(f"flowcheck: digest soundness over {n} GraphSpec "
                  "field(s)")
    if findings:
        for f in findings:
            print(f"flowcheck FAIL {f.format()}", file=sys.stderr)
        print(f"flowcheck: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    if ns.format == "text":
        print("flowcheck: clean")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "audit":
        return audit_main(argv[1:])
    if argv and argv[0] == "kernels":
        return kernels_main(argv[1:])
    if argv and argv[0] == "flow":
        return flow_main(argv[1:])
    return lint_main(argv)
