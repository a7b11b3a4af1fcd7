"""pallascheck: broken-kernel fixture corpus (exact finding identity),
clean self-check over the real registry, VMEM bound derivation, the
differential sanitizer, inventory/structural-view plumbing, and the CLI.

Fixture convention (tests/kernel_fixtures/*.py): each module exports
``ENTRY`` (a KernelEntry isolating one defect) and ``EXPECT`` (the exact
``{(kind, operand)}`` set). The corpus compares set equality, so a false
positive fails as loudly as a miss.
"""
import importlib
import json
import pathlib

import pytest

from repro.analysis import kernelcheck as kc
from repro.kernels import KernelCase, KernelEntry, registry

FIXTURES = sorted(
    p.stem for p in (pathlib.Path(__file__).parent / "kernel_fixtures"
                     ).glob("*.py") if p.stem != "__init__")


def _identity(findings):
    return {(f.kind, f.operand) for f in findings}


@pytest.mark.parametrize("name", FIXTURES)
def test_fixture_corpus(name):
    mod = importlib.import_module(f"kernel_fixtures.{name}")
    findings, report = kc.check_entry(mod.ENTRY, execute=False)
    assert _identity(findings) == mod.EXPECT, (
        f"{name}: got {sorted(_identity(findings))}, "
        f"expected {sorted(mod.EXPECT)}:\n"
        + "\n".join(f.format() for f in findings))
    for f in findings:
        assert f.kernel == mod.ENTRY.name


def test_registry_self_check_clean():
    """The acceptance gate: every registered kernel passes the static
    checks over its full size sweep (including the MAX_VMEM_ENTRIES
    boundary case)."""
    findings, inv = kc.run_registry(execute=False)
    assert not findings, "\n".join(f.format() for f in findings)
    assert inv["ok"]
    assert set(inv["kernels"]) == {"edge_resolve", "band_compact",
                                   "histogram", "pk_expand", "cfree_expand"}


def test_registry_covers_every_kernel_module():
    """Drift tripwire: a new kernels/*.py module must register itself."""
    kdir = pathlib.Path(__file__).parents[1] / "src" / "repro" / "kernels"
    mods = {p.stem for p in kdir.glob("*.py")} - {
        "__init__", "ops", "ref", "dispatch"}
    assert mods == {e.name for e in registry()}


def test_differential_sanitizer_runs_and_passes():
    entry = next(e for e in registry() if e.name == "histogram")
    findings, report = kc.check_case(
        entry.name, entry.build(m=2048, nbins=512))
    assert not findings
    assert report["differential"] == "passed"


def test_differential_catches_wrong_kernel():
    """KC006 fires when interpret execution disagrees with the oracle."""
    import jax.numpy as jnp

    base = next(e for e in registry() if e.name == "histogram"
                ).build(m=2048, nbins=512)
    lying_ref = lambda v: base.ref(v) + 1
    case = KernelCase(fn=base.fn, args=base.args, ref=lying_ref,
                      label="lying", execute=True)
    findings, report = kc.check_case("histogram", case)
    assert _identity(findings) == {("KC006", "out[0]")}
    assert report["differential"] == "failed"


def test_abstract_parity_catches_wrong_shape():
    """KC005 fires on shape/dtype disagreement without executing."""
    import jax.numpy as jnp

    base = next(e for e in registry() if e.name == "histogram"
                ).build(m=2048, nbins=512)
    wrong_ref = lambda v: jnp.zeros((7,), jnp.float32)
    case = KernelCase(fn=base.fn, args=base.args, ref=wrong_ref,
                      label="wrongshape", execute=False)
    findings, _ = kc.check_case("histogram", case)
    assert _identity(findings) == {("KC005", "")}


def test_no_pallas_call_is_a_finding():
    case = KernelCase(fn=lambda x, interpret=None: x + 1,
                      args=(__import__("jax").ShapeDtypeStruct(
                          (4,), __import__("jax").numpy.int32),),
                      ref=None, label="nocall", execute=False)
    findings, _ = kc.check_case("ghost", case, execute=False)
    assert _identity(findings) == {("KC000", "")}


# --- derived VMEM bound ------------------------------------------------------

def test_max_resident_entries_saturates_budget():
    """The derived cap is tight: m = MAX fits the budget exactly under the
    working-set model, m = MAX + BLOCK does not."""
    from repro.kernels.dispatch import vmem_budget_bytes
    from repro.kernels.edge_resolve import BLOCK, max_resident_entries

    budget = vmem_budget_bytes("tpu")
    m = max_resident_entries("tpu")
    overhead = 2 * 2 * BLOCK * 4
    assert m % BLOCK == 0
    assert 4 * m + overhead <= budget < 4 * (m + BLOCK) + overhead


def test_registry_boundary_case_lands_on_budget():
    """The m = MAX_VMEM_ENTRIES sweep point's working-set estimate equals
    the budget exactly — the estimator and the derived cap share a model."""
    from repro.kernels.dispatch import vmem_budget_bytes
    from repro.kernels.edge_resolve import MAX_VMEM_ENTRIES

    entry = next(e for e in registry() if e.name == "edge_resolve")
    findings, report = kc.check_case(
        entry.name, entry.build(m=MAX_VMEM_ENTRIES), execute=False)
    assert not findings
    assert report["calls"][0]["vmem_bytes"] == vmem_budget_bytes("tpu")


# --- oversize gathers ---------------------------------------------------------

def test_oversize_resolve_fallback_is_counted(monkeypatch):
    """Gathers beyond the kernels' resident and chunked bounds trace in
    every mode: the XLA formulation is the path, not a detour. The routing
    is decided on static shapes at trace time, so eval_shape exercises it
    without allocating ~256 MiB."""
    import jax

    from repro.kernels import ops
    from repro.kernels.edge_resolve import (MAX_CHUNKED_ENTRIES,
                                            MAX_VMEM_ENTRIES)

    for mode in ("interpret", "off"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        for m in (MAX_VMEM_ENTRIES + 1, MAX_CHUNKED_ENTRIES + 1):
            spec = jax.ShapeDtypeStruct((m,), jax.numpy.int32)
            assert jax.eval_shape(ops.resolve_step, spec).shape == (m,)
            rows = jax.ShapeDtypeStruct((2, m), jax.numpy.int32)
            assert jax.eval_shape(ops.gather, rows, rows).shape == (2, m)


# --- inventory / gate plumbing -----------------------------------------------

def test_inventory_round_trips_and_structural_view():
    findings, inv = kc.run_registry(execute=False)
    inv2 = json.loads(json.dumps(inv))  # JSON-clean (no numpy scalars etc.)
    sv = kc.structural_view(inv2)
    assert sv["budget"]["vmem_bytes"] == inv["budget"]["vmem_bytes"]
    assert set(sv["kernels"]) == set(inv["kernels"])
    # volatile fields are stripped from the gate-compared view
    flat = json.dumps(sv)
    assert "jax_version" not in flat
    assert "differential" not in flat
    assert not kc.diff_paths(sv, kc.structural_view(inv))


def test_diff_paths_localizes_drift():
    findings, inv = kc.run_registry(execute=False)
    sv = kc.structural_view(inv)
    drifted = json.loads(json.dumps(sv))
    call = drifted["kernels"]["edge_resolve"]["cases"]["m127"][0]
    call["grid"] = [999]
    paths = kc.diff_paths(sv, drifted)
    assert paths == ["kernels.edge_resolve.cases.m127[0].grid[0]"]
    missing = json.loads(json.dumps(sv))
    del missing["kernels"]["histogram"]
    assert kc.diff_paths(sv, missing) == ["kernels.histogram"]


# --- CLI ---------------------------------------------------------------------

def test_cli_kernels_clean_and_writes_inventory(tmp_path, capsys):
    from repro.analysis.cli import main

    out = tmp_path / "inv.json"
    assert main(["kernels", "--static-only", "--out", str(out)]) == 0
    inv = json.loads(out.read_text())
    assert inv["ok"] and inv["schema"] == 1
    stdout = capsys.readouterr().out
    assert "pallascheck: clean" in stdout


def test_cli_out_fails_loudly_on_bad_parent(tmp_path):
    from repro.analysis.cli import audit_main, kernels_main

    bad = tmp_path / "no" / "such" / "dir" / "x.json"
    with pytest.raises(SystemExit) as exc:
        kernels_main(["--out", str(bad), "--static-only"])
    assert exc.value.code == 2
    with pytest.raises(SystemExit) as exc:
        audit_main(["--out", str(bad), "--no-hlo"])
    assert exc.value.code == 2
