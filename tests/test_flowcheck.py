"""flowcheck: broken-dataflow fixture corpus (exact finding identity),
clean self-check over the real front-door programs, taint/role/digest
engine unit tests, inventory/structural-view plumbing, and the CLI.

Fixture convention (tests/flow_fixtures/*.py): each module exports
``run()`` (build the broken program, return its findings) and ``EXPECT``
(the exact ``{(kind, where)}`` set). The corpus compares set equality, so
a false positive fails as loudly as a miss.
"""
import importlib
import json
import os
import pathlib

import pytest

from repro.analysis import flowcheck as fc

FIXTURES = sorted(
    p.stem for p in (pathlib.Path(__file__).parent / "flow_fixtures"
                     ).glob("*.py") if p.stem != "__init__")


def _identity(findings):
    return {(f.kind, f.where) for f in findings}


@pytest.mark.parametrize("name", FIXTURES)
def test_fixture_corpus(name):
    mod = importlib.import_module(f"flow_fixtures.{name}")
    findings = mod.run()
    assert _identity(findings) == mod.EXPECT, (
        f"{name}: got {sorted(_identity(findings))}, "
        f"expected {sorted(mod.EXPECT)}:\n"
        + "\n".join(f.format() for f in findings))
    for f in findings:
        assert f.program == mod.LABEL


# --- clean self-check over the real programs ---------------------------------

@pytest.fixture(scope="module")
def flow_run():
    return fc.run_flow()


def test_front_door_programs_are_clean(flow_run):
    """The acceptance gate: every registered front-door program passes
    all three passes (RNG lineage, axis roles, digest soundness) on the
    current device set."""
    findings, inv = flow_run
    assert not findings, "\n".join(f.format() for f in findings)
    assert inv["ok"]
    labels = set(inv["programs"])
    assert any(lbl.endswith("/exchange") for lbl in labels)
    assert any(lbl.endswith("/stream_setup") for lbl in labels)
    assert any(lbl.endswith("/stream_round") for lbl in labels)


def test_exchange_traces_rng_and_collectives(flow_run):
    """The passes are looking at real content: the exchange draws
    randomness and routes exactly the verified all_to_all signatures."""
    _, inv = flow_run
    exchange = next(p for lbl, p in inv["programs"].items()
                    if lbl.endswith("/exchange"))
    assert exchange["rng_prims"], "exchange program traced no RNG"
    assert exchange["all_to_all"], "exchange program traced no all_to_all"
    rnd = next(p for lbl, p in inv["programs"].items()
               if lbl.endswith("/stream_round"))
    assert not rnd["rng_prims"], "stream round must not redraw"


def test_cfree_draw_lives_in_the_words_program(flow_run):
    """Each communication-free model registers its stream-words program
    as the draw FC001 checks, and its expansion program as one that must
    draw nothing: the words reach it as its second input."""
    _, inv = flow_run
    registered = {p.label: p for p in fc.front_door_programs(1)}
    for model in ("ba_cfree", "rmat", "er"):
        words = registered[f"flat_1x1/cfree_words_{model}"]
        expand = registered[f"flat_1x1/cfree_{model}"]
        assert (words.program, words.rng_expected) == ("cfree_words", True)
        assert (expand.program, expand.rng_expected) == ("cfree", False)
        w = inv["programs"][f"flat_1x1/cfree_words_{model}"]
        x = inv["programs"][f"flat_1x1/cfree_{model}"]
        assert w["ok"] and w["rng_prims"].get("random_bits")
        assert x["ok"] and not x["rng_prims"]
        assert (w["invars"], x["invars"]) == (0, 2)


def test_verified_transposes_cover_both_entry_points(flow_run):
    _, inv = flow_run
    for topo_label, entries in inv["transposes"].items():
        assert set(entries) == {"transpose_counts", "transpose_payload"}
        for entry in entries.values():
            assert entry["ok"]
            assert entry["signatures"]


def test_digest_covers_every_graphspec_field(flow_run):
    """Every GraphSpec field the pba suite owns is classified and
    behaves per its class; routing + sink exactly partition the
    non-identity set so a new field cannot land unclassified."""
    import dataclasses

    from repro.core.spec import GraphSpec

    assert (set(GraphSpec._ROUTING_FIELDS) | set(GraphSpec._SINK_FIELDS)
            == set(GraphSpec._NON_IDENTITY_FIELDS))
    assert not (set(GraphSpec._ROUTING_FIELDS)
                & set(GraphSpec._SINK_FIELDS))
    _, inv = flow_run
    report = inv["digest_fields"]
    # the digest pass perturbs the pba base spec, so fields owned solely
    # by the other model suites (pk, ba_cfree, rmat, er) are out of scope
    other_owned = set().union(
        *(fields for model, fields in GraphSpec._MODEL_OWNED_FIELDS.items()
          if model != "pba"))
    for f in dataclasses.fields(GraphSpec):
        if f.name == "model" or f.name in other_owned:
            continue
        assert f.name in report, f"GraphSpec.{f.name} not flow-checked"


# --- FC001 taint interpreter -------------------------------------------------

def test_taint_flows_through_while_carry():
    """A value that becomes data-dependent inside a while loop taints a
    downstream key fold — the fixed point over the carry finds it."""
    import jax
    import jax.numpy as jnp

    def prog(x):
        def body(c):
            i, acc = c
            return i + 1, acc + x[i]

        i, acc = jax.lax.while_loop(lambda c: c[0] < 3, body,
                                    (jnp.int32(0), jnp.int32(0)))
        key = jax.random.fold_in(jax.random.key(0), acc)
        return jax.random.bits(key, (2,), jnp.uint32)

    closed = jax.make_jaxpr(prog)(jnp.zeros((8,), jnp.int32))
    findings = fc.rng_lineage_findings(closed, "t")
    assert _identity(findings) == {("FC001", "random_fold_in"),
                                   ("FC001", "random_bits")}


def test_draw_under_tainted_branch_is_flagged():
    """Context taint: even with a clean key, drawing only when a runtime
    predicate holds makes the draw schedule data-dependent."""
    import jax
    import jax.numpy as jnp

    def prog(flag):
        key = jax.random.key(0)
        return jax.lax.cond(
            flag > 0,
            lambda k: jax.random.bits(k, (2,), jnp.uint32),
            lambda k: jnp.zeros((2,), jnp.uint32), key)

    closed = jax.make_jaxpr(prog)(jnp.int32(1))
    findings = fc.rng_lineage_findings(closed, "t")
    assert _identity(findings) == {("FC001", "random_bits")}


def test_counter_derived_draws_stay_clean():
    """The legitimate pattern — keys folded with loop counters, runtime
    data only *consuming* the draws — raises nothing."""
    import jax
    import jax.numpy as jnp

    def prog(xs):
        def step(carry, x_):
            key = jax.random.fold_in(jax.random.key(3), carry)
            return carry + 1, x_ + jax.random.uniform(key)

        return jax.lax.scan(step, jnp.int32(0), xs)

    closed = jax.make_jaxpr(prog)(jnp.zeros((4,), jnp.float32))
    assert fc.rng_lineage_findings(closed, "t") == []


# --- FC002 role interpreter --------------------------------------------------

def test_correct_transpose_verifies_on_one_device():
    """The real blocked transposes role-check even on the degenerate
    1-device mesh (the d=1 reshape must type like the d=8 one)."""
    from repro.runtime.topology import Topology

    findings, sigs, report = fc.verified_transpose_signatures(
        Topology.flat(1))
    assert not findings, "\n".join(f.format() for f in findings)
    assert ("proc", 2, 0, False) in sigs
    assert report["transpose_counts"]["ok"]
    assert report["transpose_payload"]["ok"]


def test_unverified_signature_is_flagged():
    """FC002 part (b): a front-door program whose all_to_all signature is
    not in the role-verified set is an unreviewed collective route."""
    import jax
    import jax.numpy as jnp

    from repro.runtime.topology import Topology

    mod = importlib.import_module("flow_fixtures.misrouted_all_to_all")
    topo = Topology.flat(1)

    def build():
        from jax.sharding import PartitionSpec as P

        from repro.runtime import spmd

        def body(x):
            blocked = x[0].reshape(topo.num_devices, 2, 2)
            recv = jax.lax.all_to_all(blocked, "proc", split_axis=0,
                                      concat_axis=1, tiled=False)
            return recv.reshape(1, 2, 2)

        fn = jax.jit(spmd.shard_map(
            body, mesh=topo.build_mesh(),
            in_specs=(P("proc", None, None),),
            out_specs=P("proc", None, None), check_vma=False))
        return fn, (jnp.zeros((1, 2, 2), jnp.int32),)

    prog = fc.FlowProgram("t/rogue", "exchange", topo, build,
                          rng_expected=False)
    findings, report = fc.check_program(
        prog, {"flat_1x1": {("proc", 2, 0, False)}})
    assert _identity(findings) == {("FC002", "all_to_all")}


def test_register_programs_extends_the_front_door():
    calls = []

    def builder(n_dev):
        calls.append(n_dev)
        return []

    fc.register_programs(builder)
    try:
        labels = [p.label for p in fc.front_door_programs(1)]
        assert calls == [1]
        assert "flat_1x1/exchange" in labels
    finally:
        fc._EXTRA_BUILDERS.remove(builder)


# --- inventory / gate plumbing -----------------------------------------------

def test_inventory_round_trips_and_structural_view(flow_run):
    _, inv = flow_run
    inv2 = json.loads(json.dumps(inv))  # JSON-clean
    sv = fc.structural_view(inv2)
    assert set(sv["programs"]) == set(inv["programs"])
    assert sv["transposes"] == inv["transposes"]
    flat = json.dumps(sv)
    assert "jax_version" not in flat
    assert '"findings"' not in flat
    assert not fc.diff_paths(sv, fc.structural_view(inv))


def test_diff_paths_localizes_drift(flow_run):
    _, inv = flow_run
    sv = fc.structural_view(inv)
    drifted = json.loads(json.dumps(sv))
    label = sorted(drifted["programs"])[0]
    drifted["programs"][label]["all_to_all"] = [["rogue", 9, 9, True]]
    paths = fc.diff_paths(sv, drifted)
    assert paths and all(p.startswith(f"programs.{label}.all_to_all")
                         for p in paths)


# --- CLI ---------------------------------------------------------------------

def test_cli_flow_clean_and_writes_inventory(tmp_path, capsys):
    from repro.analysis.cli import main

    out = tmp_path / "flow.json"
    assert main(["flow", "--no-digest", "--out", str(out)]) == 0
    inv = json.loads(out.read_text())
    assert inv["ok"] and inv["schema"] == 1
    assert inv["digest_fields"] == {}
    assert "flowcheck: clean" in capsys.readouterr().out


def test_cli_flow_sarif_is_wellformed(tmp_path, capsys):
    from repro.analysis.cli import main

    assert main(["flow", "--no-digest", "--format", "sarif"]) == 0
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0"
    (run,) = log["runs"]
    assert run["tool"]["driver"]["name"] == "flowcheck"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} \
        == set(fc.KIND_TITLES)
    assert run["results"] == []


def test_cli_out_rejects_bad_targets(tmp_path):
    from repro.analysis.cli import audit_main, flow_main, kernels_main

    bad = tmp_path / "no" / "such" / "dir" / "x.json"
    for entry, args in ((flow_main, ["--no-digest"]),
                        (kernels_main, ["--static-only"]),
                        (audit_main, ["--no-hlo"])):
        with pytest.raises(SystemExit) as exc:
            entry(["--out", str(bad)] + args)
        assert exc.value.code == 2
        # the target being an existing directory fails just as fast
        with pytest.raises(SystemExit) as exc:
            entry(["--out", str(tmp_path)] + args)
        assert exc.value.code == 2


def test_merge_sarif_concatenates_runs(tmp_path, capsys):
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).parents[1] / "scripts"))
    try:
        import merge_sarif
    finally:
        sys.path.pop(0)

    def log(tool, n_results):
        return {"version": "2.1.0", "runs": [{
            "tool": {"driver": {"name": tool, "rules": []}},
            "results": [{"ruleId": "X", "level": "error",
                         "message": {"text": "m"}}] * n_results}]}

    a, b, out = tmp_path / "a.sarif", tmp_path / "b.sarif", \
        tmp_path / "merged.sarif"
    a.write_text(json.dumps(log("spmdlint", 2)))
    b.write_text(json.dumps(log("flowcheck", 0)))
    (tmp_path / "empty.sarif").write_text("")
    assert merge_sarif.main([str(out), str(a), str(b),
                             str(tmp_path / "empty.sarif"),
                             str(tmp_path / "missing.sarif")]) == 0
    merged = json.loads(out.read_text())
    assert merged["version"] == "2.1.0"
    assert [r["tool"]["driver"]["name"] for r in merged["runs"]] \
        == ["spmdlint", "flowcheck"]
    assert sum(len(r["results"]) for r in merged["runs"]) == 2
    with pytest.raises(SystemExit):
        bad = tmp_path / "bad.sarif"
        bad.write_text('{"not": "sarif"}')
        merge_sarif.merge([str(bad)])


@pytest.mark.skipif(os.geteuid() == 0,
                    reason="root bypasses permission bits")
def test_cli_out_rejects_unwritable_targets(tmp_path):
    from repro.analysis.cli import flow_main

    ro_file = tmp_path / "ro.json"
    ro_file.write_text("{}")
    ro_file.chmod(0o444)
    with pytest.raises(SystemExit) as exc:
        flow_main(["--out", str(ro_file), "--no-digest"])
    assert exc.value.code == 2

    ro_dir = tmp_path / "ro_dir"
    ro_dir.mkdir()
    ro_dir.chmod(0o555)
    try:
        with pytest.raises(SystemExit) as exc:
            flow_main(["--out", str(ro_dir / "x.json"), "--no-digest"])
        assert exc.value.code == 2
    finally:
        ro_dir.chmod(0o755)
