"""Compiled-collective auditor: static SPMD-uniformity checks on GenPlans.

The streaming exchange's correctness argument (runtime/streaming.py) is
structural: every device must execute the *same* collective sequence — the
same all_to_alls per round, a while_loop trip count driven by a globally
all-reduced residual, no collective hiding on one branch of a ``lax.cond``.
This module verifies those properties without executing on devices, at two
levels:

  jaxpr level (``jax.make_jaxpr`` — no compile, no devices beyond mesh
  construction): recursive walk over sub-jaxprs finds every collective
  primitive; ``cond`` branches must carry identical collective multisets;
  any ``while`` whose body contains a collective must have a predicate
  whose backward slice is *uniform* — every carry slot the condition reads
  either comes out of a full ``psum`` over the topology's axes (the
  all-reduced residual) or is a pure carry/literal recurrence (the round
  counter). This generalizes tests/test_weak_scaling.py's hand-pinned
  structure to any program a plan can produce.

  HLO level (``lower().compile()`` — still no execution): the optimized
  module's all-to-all instruction count must match the declared Topology —
  a blocked transpose is one all_to_all per mesh axis, the exchange runs
  two transposes, so flat = 2 and pods two-hop = 4, with the pods split
  into contiguous (intra-pod) and strided (cross-pod) replica groups
  (``launch.hlo_stats.all_to_all_span_bytes``). Counts by kind feed the
  drift gate in scripts/collective_gate.py.

``inventory()`` emits the machine-readable JSON the gate baselines
(results/collective_audit_baseline.json).

The check is conservative/structural, not a proof: a psum anywhere in a
carry slot's backward slice counts as all-reducing that slot. It is exactly
strong enough to hold the repo's streaming contract and catch the failure
modes that matter (a predicate reading raw residuals, a collective moved
under one cond branch, an extra transpose sneaking into the loop).
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Iterable, Iterator, Optional

import jax

from repro.launch.hlo_stats import all_to_all_span_bytes

COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "all_to_all", "all_gather", "ppermute", "pshuffle",
    "psum_scatter", "reduce_scatter", "pmax", "pmin", "pbroadcast",
})
# psum variants whose result is replicated across the reduced axes —
# the primitives that make a carried value uniform.
_REDUCING_PRIMS = frozenset({"psum", "psum2"})


# --- jaxpr walking -----------------------------------------------------------

def _sub_jaxprs(params: dict) -> Iterator:
    for value in params.values():
        items = value if isinstance(value, (list, tuple)) else (value,)
        for item in items:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr        # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item              # bare Jaxpr


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in a jaxpr, recursing through sub-jaxpr params
    (pjit bodies, cond branches, while cond/body, scan, custom calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def collective_counts(jaxpr) -> dict:
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            c[eqn.primitive.name] += 1
    return dict(c)


def _is_literal(var) -> bool:
    return hasattr(var, "val")   # core.Literal carries its value


def _axis_names_of(eqn) -> tuple:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


@dataclasses.dataclass
class SliceInfo:
    """Backward slice of one jaxpr output: what its value depends on."""

    prims: Counter                  # primitive name -> count in the slice
    carry_leaves: set               # carry-relative slot indexes (>= nconsts)
    const_leaves: set               # invar indexes < nconsts (closed data)
    psum_axes: list                 # axis-name tuples of psums in the slice

    def reduced_over(self, required_axes: Iterable[str]) -> bool:
        req = set(required_axes)
        return any(req <= set(axes) for axes in self.psum_axes)

    @property
    def has_collective(self) -> bool:
        return any(p in COLLECTIVE_PRIMS for p in self.prims)


def backward_slice(jaxpr, outvar, nconsts: int = 0) -> SliceInfo:
    producers = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[id(ov)] = eqn
    invar_index = {id(v): i for i, v in enumerate(jaxpr.invars)}
    const_ids = {id(v) for v in jaxpr.constvars}

    info = SliceInfo(Counter(), set(), set(), [])
    seen_vars: set = set()
    seen_eqns: set = set()
    stack = [outvar]
    while stack:
        var = stack.pop()
        if _is_literal(var) or id(var) in seen_vars:
            continue
        seen_vars.add(id(var))
        if id(var) in invar_index:
            idx = invar_index[id(var)]
            if idx >= nconsts:
                info.carry_leaves.add(idx - nconsts)
            else:
                info.const_leaves.add(idx)
            continue
        if id(var) in const_ids:
            info.const_leaves.add(f"const:{getattr(var, 'count', '?')}")
            continue
        eqn = producers.get(id(var))
        if eqn is None:
            info.const_leaves.add("unknown")
            continue
        if id(eqn) not in seen_eqns:
            seen_eqns.add(id(eqn))
            info.prims[eqn.primitive.name] += 1
            if eqn.primitive.name in _REDUCING_PRIMS:
                info.psum_axes.append(_axis_names_of(eqn))
            # collectives inside nested calls (pjit/closed_call) count too
            for sub in _sub_jaxprs(eqn.params):
                for name, n in collective_counts(sub).items():
                    info.prims[name] += n
                for sub_eqn in iter_eqns(sub):
                    if sub_eqn.primitive.name in _REDUCING_PRIMS:
                        info.psum_axes.append(_axis_names_of(sub_eqn))
            stack.extend(eqn.invars)
    return info


# --- structural checks -------------------------------------------------------

def cond_branch_mismatches(jaxpr) -> list:
    """lax.cond equations whose branches carry different collectives."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "cond":
            continue
        branches = eqn.params.get("branches", ())
        counts = [collective_counts(b.jaxpr) for b in branches]
        if any(c != counts[0] for c in counts[1:]):
            out.append("lax.cond branches disagree on collectives: "
                       f"{counts} — a data-dependent branch must issue the "
                       "identical collective sequence on every device")
    return out


@dataclasses.dataclass
class WhileAudit:
    """One while_loop's collective content and predicate uniformity."""

    body_collectives: dict
    cond_carry_slots: tuple
    uniform_predicate: bool
    notes: tuple

    def to_json(self) -> dict:
        return {"body_collectives": self.body_collectives,
                "cond_carry_slots": list(self.cond_carry_slots),
                "uniform_predicate": self.uniform_predicate,
                "notes": list(self.notes)}


def while_audits(jaxpr, required_axes: Iterable[str] = ()) -> list:
    """Audit every while_loop reachable from ``jaxpr``.

    A loop with a collective-free body is trivially uniform (trip count may
    vary per device but no device waits on another). Otherwise the
    predicate's carry slots must each be uniform: produced by a full psum
    over ``required_axes`` (the all-reduced residual), or a pure
    literal/carry recurrence over uniform slots (the round counter) —
    computed as a greatest fixed point over the carry, so mutually
    recurrent counters stay uniform and anything touching closed-over
    device data or a non-reducing collective poisons its slot.
    """
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "while":
            continue
        cond_jaxpr = eqn.params["cond_jaxpr"].jaxpr
        body_jaxpr = eqn.params["body_jaxpr"].jaxpr
        cond_nconsts = eqn.params["cond_nconsts"]
        body_nconsts = eqn.params["body_nconsts"]
        body_coll = collective_counts(body_jaxpr)

        cond_slice = backward_slice(cond_jaxpr, cond_jaxpr.outvars[0],
                                    cond_nconsts)
        carry_slots = tuple(sorted(i for i in cond_slice.carry_leaves
                                   if isinstance(i, int)))

        if not body_coll:
            out.append(WhileAudit(body_coll, carry_slots, True,
                                  ("collective-free body",)))
            continue

        # predicate reduced inside the cond jaxpr itself covers everything
        if cond_slice.reduced_over(required_axes):
            out.append(WhileAudit(body_coll, carry_slots, True,
                                  ("predicate all-reduced in cond",)))
            continue

        ncarry = len(body_jaxpr.outvars)
        slices = {i: backward_slice(body_jaxpr, body_jaxpr.outvars[i],
                                    body_nconsts) for i in range(ncarry)}
        uniform = {i: True for i in range(ncarry)}
        notes = []
        changed = True
        while changed:
            changed = False
            for i in range(ncarry):
                if not uniform[i]:
                    continue
                sl = slices[i]
                if sl.reduced_over(required_axes):
                    continue            # all-reduced slot (the residual)
                bad = None
                if sl.has_collective:
                    colls = {k: v for k, v in sl.prims.items()
                             if k in COLLECTIVE_PRIMS}
                    bad = (f"carry[{i}] sees collectives {colls} "
                           "without a covering psum")
                elif sl.const_leaves:
                    bad = (f"carry[{i}] reads closed-over data "
                           "(device-varying) without a covering psum")
                else:
                    for leaf in sl.carry_leaves:
                        if isinstance(leaf, int) and not uniform.get(
                                leaf, True):
                            bad = (f"carry[{i}] depends on non-uniform "
                                   f"carry[{leaf}]")
                            break
                if bad:
                    uniform[i] = False
                    notes.append(bad)
                    changed = True
        ok = all(uniform[i] for i in carry_slots)
        out.append(WhileAudit(body_coll, carry_slots, ok, tuple(notes)))
    return out


# --- program-level audit -----------------------------------------------------

def expected_all_to_alls(topo, program: str) -> int:
    """Structural pin: a blocked transpose is one all_to_all per topology
    axis (flat: 1, pods two-hop: 2); the exchange program runs two
    transposes (counts + payload), a stream round runs one. The
    communication-free generators are pinned to **zero** on every
    topology — that absence is their contract, audited as strictly as the
    exchange's presence."""
    hops = max(topo.ndim, 1)
    return {"exchange": 2 * hops, "stream_round": hops, "cfree": 0}[program]


@dataclasses.dataclass
class ProgramAudit:
    label: str
    program: str
    topology: str
    num_devices: int
    jaxpr_collectives: dict
    cond_mismatches: list
    whiles: list
    problems: list
    hlo_collectives: Optional[dict] = None
    hlo_all_to_alls: Optional[int] = None
    expected_all_to_alls: Optional[int] = None
    hlo_span: Optional[dict] = None
    cost_bytes: Optional[float] = None

    @property
    def ok(self) -> bool:
        return not self.problems

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["whiles"] = [w.to_json() for w in self.whiles]
        d["ok"] = self.ok
        return d


def audit_program(fn, args, topo, label: str, program: str,
                  with_hlo: bool = True) -> ProgramAudit:
    """Trace (and optionally compile) one SPMD program and verify the
    uniformity contract against its declared topology. Never executes."""
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    required = () if topo.is_host else topo.axis_names
    mismatches = cond_branch_mismatches(jaxpr)
    whiles = while_audits(jaxpr, required_axes=required)
    problems = list(mismatches)
    for w in whiles:
        if w.body_collectives and not w.uniform_predicate:
            problems.append(
                "while_loop body carries collectives "
                f"{w.body_collectives} but its predicate is not globally "
                f"all-reduced: {'; '.join(w.notes) or 'no uniform slot'}")

    audit = ProgramAudit(
        label=label, program=program, topology=topo.label,
        num_devices=topo.num_devices,
        jaxpr_collectives=collective_counts(jaxpr),
        cond_mismatches=mismatches, whiles=whiles, problems=problems)

    if with_hlo:
        from repro.runtime import spmd
        compiled = fn.lower(*args).compile()
        hlo = compiled.as_text()
        audit.hlo_collectives = static_collective_counts(hlo)
        span = all_to_all_span_bytes(hlo)
        audit.hlo_span = span
        audit.hlo_all_to_alls = span["n_local"] + span["n_cross"]
        audit.expected_all_to_alls = expected_all_to_alls(topo, program)
        try:
            audit.cost_bytes = float(
                spmd.cost_analysis(compiled).get("bytes accessed", 0.0))
        except Exception:
            audit.cost_bytes = None
        # XLA elides collectives on a 1-device mesh; the structural pin
        # only binds on real multi-device meshes.
        if topo.num_devices > 1:
            if audit.hlo_all_to_alls != audit.expected_all_to_alls:
                problems.append(
                    f"{topo.label} {program} compiled to "
                    f"{audit.hlo_all_to_alls} all_to_alls, expected "
                    f"{audit.expected_all_to_alls} (one per mesh axis per "
                    "blocked transpose)")
            if (audit.expected_all_to_alls > 0 and topo.ndim == 2
                    and span["n_cross"] == 0):
                problems.append(
                    f"{topo.label} {program}: no strided-replica-group "
                    "all_to_all — the cross-pod hop is missing")
    return audit


def static_collective_counts(hlo: str) -> dict:
    """Per-kind collective *instruction* counts in optimized HLO text —
    no while-trip multiplication, so the number is stable under
    exchange_rounds changes (what the drift baseline wants)."""
    from repro.launch import hlo_stats
    counts: Counter = Counter()
    for ln in hlo.splitlines():
        if "/*" in ln:
            ln = hlo_stats._COMMENT_RE.sub("", ln)
        m = hlo_stats._COLL_LINE_RE.search(ln)
        if m:
            counts[m.group("op")] += 1
    return dict(counts)


def audit_exchange(pl, with_hlo: bool = True,
                   label: Optional[str] = None) -> ProgramAudit:
    """Audit a sharded plan's full exchange program (phase1 + both
    transposes; streamed configs include the residual while_loop)."""
    from repro.launch.bench import compile_sharded_pba
    fn, args = compile_sharded_pba(pl)
    return audit_program(fn, args, pl.topology,
                         label or f"{pl.topology.label}/exchange",
                         "exchange", with_hlo=with_hlo)


def audit_stream_round(pl, with_hlo: bool = True,
                       label: Optional[str] = None) -> ProgramAudit:
    """Audit one round of a streamed plan's device-sharded exchange-2."""
    from repro.launch.bench import compile_sharded_stream_round
    fn, args = compile_sharded_stream_round(pl)
    return audit_program(fn, args, pl.topology,
                         label or f"{pl.topology.label}/stream_round",
                         "stream_round", with_hlo=with_hlo)


def audit_cfree(pl, with_hlo: bool = True,
                label: Optional[str] = None) -> ProgramAudit:
    """Audit a communication-free plan's sharded expansion program —
    expected collective count: zero, on any topology."""
    from repro.launch.bench import compile_sharded_cfree
    fn, args = compile_sharded_cfree(pl)
    return audit_program(fn, args, pl.topology,
                         label or f"{pl.topology.label}/cfree_{pl.model}",
                         "cfree", with_hlo=with_hlo)


def audit_plan(pl, with_hlo: bool = True) -> list:
    """Every SPMD program a resolved GenPlan will launch, audited.

    Host-execution plans have no SPMD program and audit to an empty list.
    """
    if pl.topology.is_host:
        return []
    from repro.core.spec import CFREE_MODELS
    if pl.model in CFREE_MODELS:
        return [audit_cfree(pl, with_hlo=with_hlo)]
    audits = [audit_exchange(pl, with_hlo=with_hlo)]
    if pl.executor == "pba_stream_sharded":
        audits.append(audit_stream_round(pl, with_hlo=with_hlo))
    return audits


def inventory(audits: Iterable[ProgramAudit], extra: Optional[dict] = None
              ) -> dict:
    """Machine-readable audit inventory (the baseline/CI artifact)."""
    progs = {a.label: a.to_json() for a in audits}
    out = {"jax_version": jax.__version__,
           "programs": progs,
           "ok": all(a.ok for a in audits)}
    if extra:
        out.update(extra)
    return out
