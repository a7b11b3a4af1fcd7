"""Compile the chip smoke run's hot programs for a described TPU v5e.

Nothing executes: each test lowers and compiles for ``v5e:2x2`` devices
that are described, not attached, and checks what only the TPU compiler
can refuse — Mosaic lowering, the (8, 128) block rule, device memory —
at the widths ``chip_smoke.py`` runs. The topology is described inside a
module fixture (never at import), so every test worker collects the same
tests and only the worker running this file loads the TPU library.

The last tests run ``chip_smoke.py`` itself on the CPU, where it must
refuse to run.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import api
from repro.api import GraphSpec, Topology
from repro.core import cfree as cfree_lib
from repro.core.pba import pba_stream_round_block, stream_block_capacity
from repro.kernels import dispatch
from repro.runtime import blocking, spmd, streaming

#: One v5e chip's HBM (Google Cloud, "TPU v5e").
V5E_HBM_BYTES = 16 * 10**9

#: chip_smoke.py's phase-1 spec (the paper's per-rank shape, 64 ranks).
PBA_SPEC = GraphSpec(model="pba", procs=64, vertices_per_proc=500_000,
                     edges_per_vertex=5, exchange_rounds=8, seed=7,
                     execution="streamed", topology=Topology.flat(1))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # A compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _kernel_program(name):
    """(jitted fn, [(shape, dtype)]) of one kernel dispatch at the width
    chip_smoke.py runs it."""
    from repro.kernels import ops
    if name == "histogram":
        # the round program's census: one rank's E tags into P bins
        pl = api.plan(PBA_SPEC)
        e, p = pl.config.edges_per_proc, pl.num_procs
        return (jax.jit(lambda v: ops.histogram(v, p)), [((e,), jnp.int32)])
    if name == "pk_expand":
        pl = api.plan(GraphSpec(model="pk", levels=8, seed=3,
                                execution="sharded",
                                topology=Topology.flat(1)))
        sg, lv = pl.seed_graph, pl.config.levels
        fn = jax.jit(lambda t, b, u, v: ops.pk_expand(
            t, b, u, v, sg.num_vertices, sg.num_edges, lv, 0.0, 0.0, 3, 0))
        return fn, [((pl.requested_edges,), jnp.int32), ((lv,), jnp.int32),
                    ((sg.num_edges,), jnp.int32), ((sg.num_edges,), jnp.int32)]
    pl = api.plan(GraphSpec(model="ba_cfree", cfree_vertices=25_000_000,
                            ba_degree=4, seed=7, execution="sharded",
                            topology=Topology.flat(1)))
    cfg = pl.config
    fn = jax.jit(lambda t: cfree_lib.cfree_endpoints(
        cfg, t, cfree_lib.cfree_words(cfg)))
    return fn, [((pl.requested_edges,), jnp.int32)]


@pytest.mark.parametrize("name", ["histogram", "pk_expand", "cfree_expand"])
def test_kernel_compiles_for_v5e(topo, name):
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    fn, shapes = _kernel_program(name)
    with dispatch.forced_mode("tpu"):
        compiled = fn.lower(*[_sds(s, d, one_chip) for s, d in shapes]
                            ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_cfree_program_keeps_the_kernel_signature(topo, monkeypatch):
    """The sharded R-MAT expansion as the front door builds it (scale 20,
    edgefactor 16), compiled for a described chip: the stream words, now
    an argument of the program, still reach the kernel as its (4,) uint32
    operand beside the (rows, 128) int32 edge indices, the custom call
    that ``cfree_expand_roofline`` reads."""
    monkeypatch.syspath_prepend(REPO)
    from bench import harness
    from repro.launch import bench as launch_bench
    pl = api.plan(GraphSpec(model="rmat", cfree_vertices=1 << 20,
                            cfree_edges=16 << 20, seed=7,
                            execution="sharded", topology=Topology.flat(1)))
    build_mesh = Topology.build_mesh
    monkeypatch.setattr(Topology, "build_mesh",
                        lambda self, devices=None: build_mesh(self,
                                                              topo.devices))
    cfree_lib._expand_program.cache_clear()
    try:
        with dispatch.forced_mode("tpu"):
            fn, (tok, words) = launch_bench.compile_sharded_cfree(pl)
            mesh = pl.topology.build_mesh()
            compiled = fn.lower(
                _sds(tok.shape, tok.dtype,
                     NamedSharding(mesh, P(pl.topology.spec_axes))),
                _sds(words.shape, words.dtype, NamedSharding(mesh, P()))
            ).compile()
    finally:
        cfree_lib._expand_program.cache_clear()
    roofline = harness.load_module(
        os.path.join(REPO, "bench", "metrics", "cfree_expand_roofline.py"),
        "cfree_expand_roofline")
    # The profiler names an operation by its HLO with each operand's
    # shape before the operand; as_text() gives the names alone.
    hlo = compiled.as_text()
    shapes = dict(re.findall(r"(%\S+) = (\S+) ", hlo))
    typed = re.sub(r"%[\w.\-]+(?=[,)])",
                   lambda m: f"{shapes.get(m.group(), '?')} {m.group()}", hlo)
    assert (words.shape, words.dtype) == ((4,), jnp.uint32)
    assert len(re.findall(roofline.KERNEL, typed)) == 1, hlo[-3000:]
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _round_program(devices, topology: Topology, spec: GraphSpec = PBA_SPEC,
                   round_cap: int = 0):
    """The streamed PBA round program of ``spec`` (planned on one device)
    on ``devices``, with ShapeDtypeStruct arguments sharded over them
    (built from pba_stream_round_block directly: api.plan checks the
    present device count). ``round_cap`` replaces the plan's C_r, a floor
    of the demand-sized one; the block takes the largest band that C_r
    allows, min(E, P*C_r)."""
    pl = api.plan(spec)
    cfg, p = pl.config, pl.num_procs
    e, urn = cfg.edges_per_proc, pl.urn_budget
    c_r = round_cap or pl.round_capacity
    d = topology.num_devices
    lp = topology.lp(p)
    block_cap = stream_block_capacity(e, p, c_r)
    mesh = Mesh(np.array(devices[:d]).reshape(topology.axis_sizes),
                topology.axis_names)
    ax = topology.spec_axes

    def body(r, a_blk, occ_blk, recv_blk, pool_blk):
        ranks = blocking.logical_ranks(lp, topology)
        u, v, counts = pba_stream_round_block(
            r, a_blk[0], occ_blk[0], recv_blk[0], pool_blk[0], ranks, cfg,
            p, c_r, urn, block_cap, topology)
        return u[None], v[None], counts[None]

    blocked = P(ax, None, None)
    fn = jax.jit(spmd.shard_map(body, mesh=mesh,
                                in_specs=(P(),) + (blocked,) * 4,
                                out_specs=(blocked,) * 3, check_vma=False))
    rows = NamedSharding(mesh, blocked)
    args = (_sds((), jnp.int32, NamedSharding(mesh, P())),
            _sds((d, lp, e), jnp.int32, rows),
            _sds((d, lp, e), jnp.int32, rows),
            _sds((d, lp, p), jnp.int32, rows),
            _sds((d, lp, e + urn), jnp.int32, rows))
    with dispatch.forced_mode("tpu"):
        return fn.lower(*args).compile()


def test_one_chip_round_program_compiles_and_fits(topo):
    compiled = _round_program(topo.devices, Topology.flat(1))
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1  # the histogram census
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_four_chip_round_program_compiles(topo):
    compiled = _round_program(topo.devices, Topology.flat(4))
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1
    assert "all-to-all" in hlo
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _weak4_spec() -> GraphSpec:
    """The ``pba_table1.weak4`` benchmark cell's spec, read from its
    configuration: 256 ranks of 20,000 vertices x 5 edges, R=8, urns of
    2E."""
    with open(os.path.join(REPO, "bench", "configs",
                           "pba_table1_weak4.json")) as f:
        spec = json.load(f)["spec"]
    return GraphSpec(**spec, seed=7, execution="streamed",
                     topology=Topology.flat(1))


#: The busiest (requester, provider) pair's demand at the four-chip cell's
#: shape, the largest over 32 seeds (PERF.md §6).
WEAK4_BUSIEST_PAIR = 1_933


def test_weak4_round_program_compiles_and_fits(topo):
    """The round program at the four-chip cell's shapes: lp=64 of P=256
    ranks per chip, E=100,000 local edges, urns of 2E, an all_to_all
    across flat(4), and the round capacity the stream derives from the
    busiest pair's demand, C_r = ceil(1,933 / 8) = 242 (the plan's
    heuristic floor is C=469, C_r=59), with a block of the widest band it
    allows."""
    pl = api.plan(_weak4_spec())
    assert (pl.num_procs, pl.config.edges_per_proc) == (256, 100_000)
    assert (pl.pair_capacity, pl.round_capacity) == (469, 59)
    assert pl.urn_budget == 200_000
    assert Topology.flat(4).lp(pl.num_procs) == 64
    c_r = streaming.round_capacity(WEAK4_BUSIEST_PAIR, 8)
    assert c_r == 242
    compiled = _round_program(topo.devices, Topology.flat(4), pl.spec,
                              round_cap=c_r)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1
    assert "all-to-all" in hlo
    assert _device_bytes(compiled) < V5E_HBM_BYTES


# --- chip_smoke.py without a chip ---------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(cwd, script, code=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH", "REPRO_PALLAS")}
    env["JAX_PLATFORMS"] = "cpu"
    argv = ["-c", code] if code else [script]
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """No CPU fallback: on a host without a TPU the script exits non-zero,
    names the platform it found, and prints no result line — also when it
    is copied away from the program it drives."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "script_alone":
        shutil.copy(script, tmp_path)
        script = str(tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path, script)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "checkout":
        assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]


def test_chip_smoke_imports_set_no_host_device_flags(tmp_path):
    """launch/dryrun.py and benchmarks/hillclimb.py force host devices
    through XLA_FLAGS as they are imported; nothing on the chip path may
    import them."""
    code = (f"import os, sys; sys.path.insert(0, {REPO!r}); "
            "import chip_smoke; "
            "bad = [m for m in sys.modules if m.endswith(('dryrun', "
            "'hillclimb'))]; "
            "assert 'XLA_FLAGS' not in os.environ and not bad, bad")
    proc = _run_smoke(tmp_path, None, code)
    assert proc.returncode == 0, proc.stderr[-2000:]
