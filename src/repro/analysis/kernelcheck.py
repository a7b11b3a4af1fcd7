"""pallascheck: static grid/BlockSpec race & VMEM verifier for Pallas kernels.

Third layer of the analysis subsystem (``python -m repro.analysis kernels``).
The collectives are pinned structurally by the compiled-collective auditor;
the Pallas kernels get the same treatment here, without TPU execution: every
registered ``pl.pallas_call`` (repro.kernels.registry) is traced under
``jax.eval_shape`` with a capture shim in place of the real primitive, so
the exact grid / BlockSpec / out_shape the library would hand Mosaic is
introspected — then mechanically verified over a swept size grid:

  KC001 grid race        an output block revisited *non-consecutively* in
                         grid iteration order (last grid dim fastest). TPU
                         Pallas keeps an output block resident only across
                         consecutive steps; a separated revisit re-fetches
                         undefined data and the two writes race.
  KC002 output gap       the distinct output blocks fail to cover the padded
                         output — some elements are never written.
  KC003 OOB block        an index map sends any operand's block outside the
                         padded array (block-index convention: the map
                         returns block indices, scaled by block_shape).
  KC004 VMEM budget      per-grid-step working-set estimate (resident blocks
                         once + gridded blocks double-buffered) exceeds the
                         per-backend budget (dispatch.vmem_budget_bytes) —
                         the derived bound that replaced the hand-maintained
                         MAX_VMEM_ENTRIES constant.
  KC005 oracle parity    abstract-eval (shape/dtype) disagreement between
                         the kernel entry point and its ref.py oracle.
  KC006 differential     interpret-mode execution disagrees with the oracle
                         on seeded inputs (the sanitizer; only runs when the
                         static checks pass and the case opts in).
  KC000 capture error    the entry point issued no pallas_call / malformed
                         spec (index-map arity, non-integer indices).

``inventory()`` emits the machine-readable JSON that
``results/kernel_audit_baseline.json`` commits and scripts/collective_gate.py
diffs (``structural_view`` strips the non-structural fields first), so a
grid or block-shape change is a deliberate baseline re-commit — the same
drift-gate contract the collective auditor established.

Like the auditor, this module imports JAX lazily (on first use); the lint
layer stays dependency-free.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
from typing import Callable, Iterable, Iterator, Optional

KIND_TITLES = {
    "KC000": "capture error",
    "KC001": "grid race: non-consecutive output-block revisit",
    "KC002": "output gap: padded output not fully covered",
    "KC003": "out-of-bounds block",
    "KC004": "VMEM working set exceeds budget",
    "KC005": "shape/dtype parity mismatch vs ref oracle",
    "KC006": "interpret-vs-ref differential mismatch",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verified defect, addressed by (kind, kernel, case, operand)."""

    kind: str          # KC000..KC006
    kernel: str        # registry entry name
    case: str          # size-sweep label, e.g. "m4097"
    operand: str       # "in[0]" / "out[1]" / "" for call-level findings
    message: str

    def format(self) -> str:
        where = f"[{self.operand}]" if self.operand else ""
        return (f"{self.kernel}/{self.case}{where}: {self.kind} "
                f"{KIND_TITLES.get(self.kind, '')} — {self.message}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CapturedCall:
    """Everything one ``pl.pallas_call`` handed the (shimmed) primitive."""

    kernel_name: str
    grid: tuple
    in_specs: list
    out_specs: list
    in_shapes: list     # jax.ShapeDtypeStruct per positional operand
    out_shapes: list


@contextlib.contextmanager
def capture_pallas_calls(calls: list) -> Iterator[list]:
    """Swap ``pl.pallas_call`` for a recorder that returns correctly shaped
    zeros, so tracing the real kernel wrappers under ``jax.eval_shape``
    captures grid/BlockSpecs/out_shape without lowering or executing."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def fake(kernel, **kw):
        def runner(*args):
            out_shape = kw.get("out_shape")
            out_list = (list(out_shape)
                        if isinstance(out_shape, (tuple, list))
                        else [out_shape])
            out_specs = kw.get("out_specs")
            grid = kw.get("grid", ())
            calls.append(CapturedCall(
                kernel_name=getattr(getattr(kernel, "func", kernel),
                                    "__name__", str(kernel)),
                grid=(tuple(grid) if isinstance(grid, (tuple, list))
                      else (grid,)),
                in_specs=list(kw.get("in_specs") or []),
                out_specs=(list(out_specs)
                           if isinstance(out_specs, (tuple, list))
                           else [out_specs]),
                in_shapes=[jax.ShapeDtypeStruct(jnp.shape(a), a.dtype)
                           for a in args],
                out_shapes=out_list))
            outs = tuple(jnp.zeros(s.shape, s.dtype) for s in out_list)
            return outs if isinstance(out_shape, (tuple, list)) else outs[0]
        return runner

    real = pl.pallas_call       # spmdlint: disable=RPR007 — capture shim
    pl.pallas_call = fake       # spmdlint: disable=RPR007 — capture shim
    try:
        yield calls
    finally:
        pl.pallas_call = real   # spmdlint: disable=RPR007 — restore


# --- per-call static checks --------------------------------------------------

def _grid_points(grid: tuple) -> list:
    """Full grid enumeration in iteration order (last dimension fastest —
    the TPU Pallas order the accumulation pattern relies on)."""
    return list(itertools.product(*[range(int(g)) for g in grid])) or [()]


def _block_index_seq(spec, shape: tuple, grid_points: list):
    """Concrete index-map evaluation: (sequence of block-index tuples,
    per-dim block counts, error message or None)."""
    bs = tuple(int(b) if b is not None else int(d)
               for b, d in zip(spec.block_shape, shape))
    if len(bs) != len(shape):
        return None, None, (f"block_shape rank {len(bs)} != operand rank "
                            f"{len(shape)}")
    nblocks = tuple(-(-int(d) // b) for d, b in zip(shape, bs))
    seq = []
    for gp in grid_points:
        try:
            idx = spec.index_map(*gp)
        except TypeError as exc:
            return None, None, f"index map arity mismatch at {gp}: {exc}"
        if not isinstance(idx, tuple):
            idx = (idx,)
        try:
            idx = tuple(int(i) for i in idx)
        except TypeError:
            return None, None, f"non-integer block index {idx!r} at {gp}"
        if len(idx) != len(bs):
            return None, None, (f"index map returned rank {len(idx)} for "
                                f"block rank {len(bs)} at {gp}")
        seq.append(idx)
    return seq, nblocks, None


def _first_oob(seq, nblocks, grid_points):
    for gp, idx in zip(grid_points, seq):
        if any(i < 0 or i >= n for i, n in zip(idx, nblocks)):
            return gp, idx
    return None


def _nonconsecutive_revisit(seq):
    """First block index written in two separated runs, or None. Block
    indices are aligned (disjoint unless identical), so an overlapping
    write IS a separated revisit of one block."""
    closed = set()
    prev = object()
    for idx in seq:
        if idx != prev:
            if idx in closed:
                return idx
            if prev is not object:
                closed.add(prev)
            prev = idx
    return None


def check_call(call: CapturedCall, kernel: str, case: str, budget: int
               ) -> tuple[list, dict]:
    """Static checks on one captured pallas_call; returns (findings, the
    structural report that feeds the inventory/baseline)."""
    findings: list = []
    grid_points = _grid_points(call.grid)
    operands = []
    resident_bytes = 0
    gridded_bytes = 0

    roles = ([(f"in[{i}]", s, sd, False)
              for i, (s, sd) in enumerate(zip(call.in_specs, call.in_shapes))]
             + [(f"out[{i}]", s, sd, True)
                for i, (s, sd) in enumerate(zip(call.out_specs,
                                                call.out_shapes))])
    if len(call.in_specs) != len(call.in_shapes):
        findings.append(Finding(
            "KC000", kernel, case, "",
            f"{len(call.in_specs)} in_specs for {len(call.in_shapes)} "
            "operands"))

    for role, spec, sd, is_out in roles:
        shape = tuple(int(d) for d in sd.shape)
        seq, nblocks, err = _block_index_seq(spec, shape, grid_points)
        if err is not None:
            findings.append(Finding("KC000", kernel, case, role, err))
            continue
        bs = tuple(int(b) if b is not None else int(d)
                   for b, d in zip(spec.block_shape, shape))
        oob = _first_oob(seq, nblocks, grid_points)
        if oob is not None:
            gp, idx = oob
            findings.append(Finding(
                "KC003", kernel, case, role,
                f"grid point {gp} maps to block {idx}, outside the "
                f"{nblocks}-block padded operand (shape {shape}, "
                f"block {bs})"))
        elif is_out:
            distinct = set(seq)
            expected = set(itertools.product(*[range(n) for n in nblocks]))
            missing = expected - distinct
            if missing:
                findings.append(Finding(
                    "KC002", kernel, case, role,
                    f"{len(missing)} of {len(expected)} output blocks never "
                    f"written (first missing: {sorted(missing)[0]}) — the "
                    "output blocks must partition the padded output"))
            race = _nonconsecutive_revisit(seq)
            if race is not None:
                findings.append(Finding(
                    "KC001", kernel, case, role,
                    f"output block {race} written by non-consecutive grid "
                    "steps — on TPU the block is flushed when the index "
                    "changes, so the separated revisit re-fetches undefined "
                    "data (overlapping writes)"))
        block_bytes = math.prod(bs) * sd.dtype.itemsize
        resident = len(set(seq)) <= 1
        if resident:
            resident_bytes += block_bytes
        else:
            gridded_bytes += block_bytes
        operands.append({
            "role": role, "shape": list(shape), "dtype": str(sd.dtype),
            "block_shape": list(bs), "blocks": list(nblocks),
            "resident": resident, "block_bytes": int(block_bytes)})

    # Per-grid-step working set: resident blocks stay put; gridded blocks
    # are double-buffered by the Mosaic pipeline (fetch next while
    # computing current).
    vmem_bytes = int(resident_bytes + 2 * gridded_bytes)
    if vmem_bytes > budget:
        findings.append(Finding(
            "KC004", kernel, case, "",
            f"working-set estimate {vmem_bytes} B (resident "
            f"{resident_bytes} + 2x gridded {gridded_bytes}) exceeds the "
            f"{budget} B VMEM budget"))

    report = {"kernel": call.kernel_name,
              "grid": [int(g) for g in call.grid],
              "steps": len(grid_points),
              "operands": operands,
              "vmem_bytes": vmem_bytes}
    return findings, report


# --- per-case / per-entry drivers --------------------------------------------

def check_case(kernel: str, case, backend: str = "tpu",
               execute: bool = True) -> tuple[list, dict]:
    """All checks for one KernelCase: capture + static verification, the
    abstract-eval oracle parity, and (opt-in) the interpret-vs-ref
    differential sanitizer."""
    import jax
    import numpy as np

    from repro.kernels.dispatch import vmem_budget_bytes

    findings: list = []
    calls: list = []
    budget = vmem_budget_bytes(backend)
    with capture_pallas_calls(calls):
        out = jax.eval_shape(case.fn, *case.args)
    if not calls:
        findings.append(Finding(
            "KC000", kernel, case.label, "",
            "no pl.pallas_call reached during abstract evaluation"))
    reports = [None] * len(calls)
    for i, call in enumerate(calls):
        f, reports[i] = check_call(call, kernel, case.label, budget)
        findings.extend(f)

    if case.ref is not None:
        want = jax.eval_shape(case.ref, *case.args)
        got_l = jax.tree_util.tree_leaves(out)
        want_l = jax.tree_util.tree_leaves(want)
        got_sig = [(tuple(x.shape), str(x.dtype)) for x in got_l]
        want_sig = [(tuple(x.shape), str(x.dtype)) for x in want_l]
        if got_sig != want_sig:
            findings.append(Finding(
                "KC005", kernel, case.label, "",
                f"kernel abstract-evals to {got_sig}, oracle to {want_sig}"))

    differential = "skipped"
    if (execute and case.execute and case.ref is not None and not findings):
        got = case.fn(*case.args, interpret=True)
        want = case.ref(*case.args)
        for i, (g, w) in enumerate(zip(jax.tree_util.tree_leaves(got),
                                       jax.tree_util.tree_leaves(want))):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                bad = int(np.flatnonzero(
                    np.asarray(g) != np.asarray(w)).reshape(-1)[0])
                findings.append(Finding(
                    "KC006", kernel, case.label, f"out[{i}]",
                    "interpret-mode kernel disagrees with the oracle on "
                    f"seeded inputs (first mismatch at flat index {bad})"))
        differential = "failed" if findings else "passed"

    report = {"calls": reports, "differential": differential,
              "ok": not findings}
    return findings, report


def check_entry(entry, backend: str = "tpu", execute: bool = True
                ) -> tuple[list, dict]:
    """Sweep one registry entry over its size grid."""
    findings: list = []
    cases: dict = {}
    for size in entry.sizes():
        case = entry.build(**size)
        f, cases[case.label] = check_case(entry.name, case, backend=backend,
                                          execute=execute)
        findings.extend(f)
    return findings, {"meta": entry.meta() if entry.meta else {},
                      "cases": cases}


def run_registry(backend: str = "tpu", execute: bool = True,
                 entries: Optional[Iterable] = None) -> tuple[list, dict]:
    """Check every registered kernel; returns (findings, inventory)."""
    import jax

    from repro.kernels import registry
    from repro.kernels.dispatch import vmem_budget_bytes

    entries = tuple(entries) if entries is not None else registry()
    findings: list = []
    kernels: dict = {}
    for entry in entries:
        f, kernels[entry.name] = check_entry(entry, backend=backend,
                                             execute=execute)
        findings.extend(f)

    inv = {
        "schema": 1,
        "jax_version": jax.__version__,
        "budget": {"backend": backend,
                   "vmem_bytes": vmem_budget_bytes(backend),
                   "model": "resident + 2x double-buffered gridded blocks"},
        "kernels": kernels,
        "findings": [f.to_json() for f in findings],
        "ok": not findings,
    }
    return findings, inv


# --- baseline diffing --------------------------------------------------------

def structural_view(inv: dict) -> dict:
    """The gate-comparable subtree of an inventory: grids, block shapes,
    VMEM estimates, budget, derived caps — everything that should only
    change via a reviewed baseline re-commit. Drops volatile fields
    (jax_version, differential status, counters, ok flags)."""
    budget = inv.get("budget", {})
    return {
        "budget": {"backend": budget.get("backend"),
                   "vmem_bytes": budget.get("vmem_bytes")},
        "kernels": {
            name: {"meta": k.get("meta", {}),
                   "cases": {label: c.get("calls", [])
                             for label, c in k.get("cases", {}).items()}}
            for name, k in inv.get("kernels", {}).items()},
    }


def diff_paths(base: dict, new: dict, prefix: str = "") -> list:
    """Dotted paths at which two (JSON-shaped) structures disagree."""
    import json

    base = json.loads(json.dumps(base))
    new = json.loads(json.dumps(new))
    out: list = []

    def walk(a, b, path):
        if type(a) is not type(b):
            out.append(path or "<root>")
        elif isinstance(a, dict):
            for key in sorted(set(a) | set(b)):
                p = f"{path}.{key}" if path else str(key)
                if key not in a or key not in b:
                    out.append(p)
                else:
                    walk(a[key], b[key], p)
        elif isinstance(a, list):
            if len(a) != len(b):
                out.append(path or "<root>")
            else:
                for i, (x, y) in enumerate(zip(a, b)):
                    walk(x, y, f"{path}[{i}]")
        elif a != b:
            out.append(path or "<root>")

    walk(base, new, prefix)
    return out
