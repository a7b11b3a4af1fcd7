"""SPMD runtime layer: version-shim, blocking primitives, API hygiene.

Covers the three device regimes (1 in-process, 2 and 8 via forced host
devices in subprocesses) and pins the repo-wide invariant that only
``repro.runtime`` touches JAX's raw shard_map / mesh-typing APIs.
"""
import pathlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.runtime import Topology, blocking, spmd

from helpers import run_with_devices

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src"


# --- API hygiene ------------------------------------------------------------

def test_no_raw_shard_map_outside_runtime():
    """Only src/repro/runtime/ may reference the raw version-drifting APIs
    and the raw collective-addressing APIs. Enforced by the AST linter
    (repro.analysis rules RPR001/RPR002) — unlike the regex this replaced,
    it resolves import aliases (``from jax.lax import all_to_all as a2a``,
    ``import jax.lax as L``) and ignores docstrings/comments."""
    from repro.analysis import lint_repo
    offenders = [v.format() for v in lint_repo(str(REPO))
                 if v.rule in ("RPR001", "RPR002")]
    assert not offenders, (
        "raw shard_map/mesh/collective APIs outside repro.runtime (route "
        "through repro.runtime.spmd / blocking):\n" + "\n".join(offenders))


def test_front_door_only_outside_src():
    """examples/, benchmarks/ and scripts/ must go through the repro.api
    front door (GraphSpec -> plan -> generate): the legacy per-model entry
    points and stream drivers are internal executors, not public surface.
    Enforced by AST linter rule RPR003 (import-alias aware)."""
    from repro.analysis import lint_repo
    offenders = [v.format() for v in lint_repo(str(REPO))
                 if v.rule == "RPR003"]
    assert not offenders, (
        "legacy generator entry points outside src/ (build a "
        "repro.api.GraphSpec and go through plan/generate):\n"
        + "\n".join(offenders))


def test_api_info_resolved():
    info = spmd.api_info()
    assert info == {"jax_version": jax.__version__,
                    "shard_map_impl": "jax.shard_map"}


# --- shim, single device ----------------------------------------------------

def _psum_fn(mesh):
    from jax.sharding import PartitionSpec as P

    def body(x):
        return jax.lax.psum(x, "proc")

    return body, P("proc"), P(None)


def test_shard_map_check_kwarg_aliases():
    mesh = spmd.make_proc_mesh(1)
    body, in_s, out_s = _psum_fn(mesh)
    x = jnp.arange(4, dtype=jnp.int32)
    for kw in ({"check_vma": False}, {"check_vma": True}, {}):
        out = jax.jit(spmd.shard_map(body, mesh=mesh, in_specs=in_s,
                                     out_specs=out_s, **kw))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_shard_map_rejects_both_check_kwargs():
    """The pre-0.6 spelling ``check_rep`` is not accepted any more."""
    mesh = spmd.make_proc_mesh(1)
    body, in_s, out_s = _psum_fn(mesh)
    with pytest.raises(TypeError):
        spmd.shard_map(body, mesh=mesh, in_specs=in_s, out_specs=out_s,
                       check_vma=False, check_rep=False)


def test_make_mesh_and_helpers():
    from jax.sharding import AxisType
    mesh = spmd.make_mesh((1, 1), ("data", "model"), axis_types="auto")
    assert spmd.mesh_size(mesh) == 1
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    proc = spmd.make_proc_mesh(1)
    assert proc.axis_names == ("proc",)
    assert spmd.ensure_mesh(proc) is proc
    assert spmd.ensure_mesh(None, axis_name="x").axis_names == ("x",)
    with pytest.raises(ValueError):
        spmd.make_proc_mesh(4096)
    explicit = spmd.make_mesh((1,), ("data",), axis_types="explicit")
    assert explicit.axis_types == (AxisType.Explicit,)


# --- device probes and the compile cache -------------------------------------

class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "fake", stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,expect", [
    ("tpu", {"bytes_limit": 16 << 30}, 16 << 30),
    ("tpu", None, RuntimeError),
    ("tpu", {"bytes_in_use": 1}, RuntimeError),
    ("cpu", None, spmd.HOST_DEVICE_MEMORY),
])
def test_device_memory_bytes_never_guesses_on_an_accelerator(
        monkeypatch, platform, stats, expect):
    """The probed budget feeds the pair capacity, which is part of the
    graph's identity: an accelerator without ``bytes_limit`` raises, and
    only a host device gets the fixed budget."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, stats)])
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            spmd.device_memory_bytes()
    else:
        assert spmd.device_memory_bytes() == expect


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert spmd.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = spmd.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert spmd.enable_compile_cache() == path  # fixed, not per call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dp_sync_rejects_wrong_leading_dim():
    from repro.train.compress import dp_sync
    with pytest.raises(ValueError):  # leading dim must equal device count
        dp_sync({"w": jnp.zeros((3, 4), jnp.float32)})


# --- Topology ---------------------------------------------------------------

def test_topology_constructors_and_derived():
    host = Topology.host()
    assert host.is_host and host.num_devices == 1 and host.ndim == 0
    assert host.spec_axes is None and host.psum_axes is None
    assert host.label == "host"

    flat = Topology.flat(8)
    assert flat.axis_names == ("proc",) and flat.axis_sizes == (8,)
    assert flat.num_devices == 8 and flat.spec_axes == "proc"
    assert flat.psum_axes == "proc" and flat.label == "flat_1x8"
    assert flat.lp(1000) == 125

    pods = Topology.pods(2, 4)
    assert pods.axis_names == ("pod", "proc")
    assert pods.num_devices == 8 and pods.label == "pods_2x4"
    assert pods.spec_axes == ("pod", "proc")
    assert pods.psum_axes == ("pod", "proc")
    assert pods.lp(16) == 2

    with pytest.raises(ValueError):  # P must divide over D
        pods.lp(10)
    with pytest.raises(ValueError):
        Topology.pods(0, 4)
    with pytest.raises(ValueError):  # duplicate axis names
        Topology(("proc", "proc"), (2, 2))
    with pytest.raises(ValueError):  # names/sizes length mismatch
        Topology(("a",), (2, 2))
    with pytest.raises(ValueError):  # host has no device mesh
        host.build_mesh()


def test_topology_mesh_roundtrip():
    flat = Topology.flat(1)
    mesh = flat.build_mesh()
    assert mesh.axis_names == ("proc",)
    assert Topology.from_mesh(mesh) == flat
    with pytest.raises(ValueError):  # more devices than exist
        Topology.pods(64, 64).build_mesh()


def test_topology_resolve_shared():
    """The one shared (topology, mesh) resolution rule (runtime.resolve)."""
    from repro.runtime import topology as topo_mod
    t, mesh = topo_mod.resolve(None, None)       # flat over all devices
    assert t == Topology.flat(len(jax.devices()))
    assert tuple(mesh.axis_names) == ("proc",)
    flat = Topology.flat(1)
    t2, m2 = topo_mod.resolve(flat)              # topology wins, mesh built
    assert t2 is flat and tuple(m2.axis_names) == ("proc",)
    t3, _ = topo_mod.resolve(None, m2)           # mesh implies topology
    assert t3 == flat
    with pytest.raises(ValueError):              # host has no device mesh
        topo_mod.resolve(Topology.host())
    with pytest.raises(ValueError):              # axes must agree
        topo_mod.resolve(Topology.pods(1, 1), m2)


def test_make_production_mesh_device_aware():
    from repro.launch.mesh import make_production_mesh
    # canonical pod shapes preserved when the devices exist
    assert make_production_mesh(num_devices=512, device_kind="cpu"
                                ).axis_sizes == (16, 16)
    assert make_production_mesh(multi_pod=True, num_devices=512,
                                device_kind="cpu").axis_sizes == (2, 16, 16)
    # device-count-aware adaptation below a pod
    t = make_production_mesh(num_devices=8, device_kind="cpu")
    assert t.axis_names == ("data", "model") and t.num_devices == 8
    mp = make_production_mesh(multi_pod=True, num_devices=8,
                              device_kind="cpu")
    assert mp.axis_sizes[0] == 2 and mp.num_devices == 8
    # device-kind-aware: TPU prefers a 16-wide model axis
    assert make_production_mesh(num_devices=64,
                                device_kind="TPU v4").axis_sizes[1] >= 8
    # clear failures when the count doesn't factor
    with pytest.raises(ValueError, match="prime"):
        make_production_mesh(num_devices=7, device_kind="cpu")
    with pytest.raises(ValueError, match="multi-pod"):
        make_production_mesh(multi_pod=True, num_devices=7,
                             device_kind="cpu")


def test_default_pair_capacity_memory_and_latency_aware():
    from repro.core.pba import default_pair_capacity
    # small scale: the load heuristic is unchanged by the new terms
    assert default_pair_capacity(600, 2) == 600
    assert default_pair_capacity(600, 2, num_procs=8) == 600
    # pod scale: the (P, C_r) buffer must fit 1/16 of device memory
    tight = default_pair_capacity(10**6, 1, num_procs=1000,
                                  memory_bytes=64 << 20)
    assert tight == (64 << 20) // 16 // (4 * 1000)
    # streamed runs recover clamped capacity via rounds: C scales with R
    r4 = default_pair_capacity(10**6, 1, num_procs=1000, exchange_rounds=4,
                               memory_bytes=64 << 20)
    assert r4 == 4 * tight
    # latency floor: never below 16 slots per round
    assert default_pair_capacity(10**6, 1, num_procs=10**6,
                                 exchange_rounds=2,
                                 memory_bytes=1 << 20) == 32


# --- blocking primitives, host path ----------------------------------------

HOST = Topology.host()


def test_transpose_host_matches_numpy():
    rng = np.random.default_rng(0)
    p, c = 6, 3
    counts = jnp.asarray(rng.integers(0, 50, (p, p)).astype(np.int32))
    buf = jnp.asarray(rng.integers(0, 50, (p, p, c)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(blocking.transpose_counts(counts, HOST)),
        np.asarray(counts).T)
    np.testing.assert_array_equal(
        np.asarray(blocking.transpose_payload(buf, HOST)),
        np.swapaxes(np.asarray(buf), 0, 1))


def test_transpose_shape_contracts():
    x = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError):  # host path needs the full (P, P) block
        blocking.transpose_counts(x, HOST)
    with pytest.raises(ValueError):  # blocked shape inconsistent with D
        blocking.transpose_counts(x, Topology.flat(3))
    with pytest.raises(ValueError):  # counts must be 2-D
        blocking.transpose_counts(jnp.zeros((2, 2, 2), jnp.int32), HOST)
    with pytest.raises(ValueError):  # payload needs a payload dim
        blocking.transpose_payload(jnp.zeros((2, 2), jnp.int32), HOST)
    with pytest.raises(NotImplementedError):  # >2-D topologies unsupported
        blocking.transpose_counts(
            jnp.zeros((1, 8), jnp.int32), Topology(("a", "b", "c"),
                                                   (2, 2, 2)))
    with pytest.raises(ValueError):
        blocking.split_logical(10, 4)
    assert blocking.split_logical(12, 4) == 3


def test_tail_mask_and_mask_tail():
    live = np.asarray(blocking.tail_mask(rank=2, chunk=4, total=10))
    np.testing.assert_array_equal(live, [True, True, False, False])
    u = jnp.arange(4, dtype=jnp.int32)
    (masked,) = blocking.mask_tail((u,), rank=2, chunk=4, total=10)
    np.testing.assert_array_equal(np.asarray(masked), [0, 1, -1, -1])


def test_map_logical_and_ranks_host():
    ranks = blocking.logical_ranks(4, HOST)
    np.testing.assert_array_equal(np.asarray(ranks), [0, 1, 2, 3])
    rows = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
    out = blocking.map_logical(lambda r, row: r + row.sum(), ranks, rows)
    np.testing.assert_array_equal(np.asarray(out), [1, 6, 11, 16])
    assert blocking.all_reduce_sum(jnp.int32(5), HOST) == 5
    assert int(blocking.device_index(HOST)) == 0


def test_pba_sharded_parity_one_device():
    """d=1 sharded run (lp == P) must equal the host path bit-for-bit."""
    from repro.core import FactionSpec, PBAConfig, make_factions
    from repro.core.pba import generate_pba
    table = make_factions(4, FactionSpec(2, 2, 3, seed=1))
    cfg = PBAConfig(vertices_per_proc=50, edges_per_vertex=3, seed=3)
    e_s, st_s = generate_pba(cfg, table, topology=Topology.flat(1))
    e_h, st_h = generate_pba(cfg, table, topology=HOST)
    np.testing.assert_array_equal(np.asarray(e_s.src).reshape(-1),
                                  np.asarray(e_h.src).reshape(-1))
    np.testing.assert_array_equal(np.asarray(e_s.dst).reshape(-1),
                                  np.asarray(e_h.dst).reshape(-1))
    assert st_s.dropped_edges == st_h.dropped_edges


# --- blocking primitives, real device axis ----------------------------------

@pytest.mark.parametrize("devices", [2, 8])
def test_transpose_distributed_matches_host(devices):
    run_with_devices(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.runtime import Topology, blocking, spmd
        d, lp, c = {devices}, 2, 3
        p = d * lp
        topo = Topology.flat(d)
        mesh = topo.build_mesh()
        rng = np.random.default_rng(0)
        counts = jnp.asarray(rng.integers(0, 100, (p, p)).astype(np.int32))
        buf = jnp.asarray(rng.integers(0, 100, (p, p, c)).astype(np.int32))
        def body(cb, bb):
            return (blocking.transpose_counts(cb, topo),
                    blocking.transpose_payload(bb, topo))
        ct, bt = jax.jit(spmd.shard_map(
            body, mesh=mesh, in_specs=(P("proc"), P("proc")),
            out_specs=(P("proc"), P("proc")), check_vma=False))(counts, buf)
        np.testing.assert_array_equal(np.asarray(ct), np.asarray(counts).T)
        np.testing.assert_array_equal(np.asarray(bt),
                                      np.swapaxes(np.asarray(buf), 0, 1))
        print("OK")
    """, devices)


@pytest.mark.parametrize("rows,cols", [(2, 4), (4, 2)])
def test_transpose_hierarchical_matches_host(rows, cols):
    """The 2-D two-hop transpose is the same permutation as a flat one."""
    run_with_devices(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.runtime import Topology, blocking, spmd
        topo = Topology.pods({rows}, {cols})
        d, lp, c = topo.num_devices, 3, 2
        p = d * lp
        mesh = topo.build_mesh()
        spec = topo.spec_axes
        rng = np.random.default_rng(1)
        counts = jnp.asarray(rng.integers(0, 100, (p, p)).astype(np.int32))
        buf = jnp.asarray(rng.integers(0, 100, (p, p, c)).astype(np.int32))
        def body(cb, bb):
            ranks = blocking.logical_ranks(lp, topo)
            return (blocking.transpose_counts(cb, topo),
                    blocking.transpose_payload(bb, topo), ranks)
        ct, bt, ranks = jax.jit(spmd.shard_map(
            body, mesh=mesh, in_specs=(P(spec), P(spec)),
            out_specs=(P(spec), P(spec), P(spec)), check_vma=False))(
            counts, buf)
        np.testing.assert_array_equal(np.asarray(ct), np.asarray(counts).T)
        np.testing.assert_array_equal(np.asarray(bt),
                                      np.swapaxes(np.asarray(buf), 0, 1))
        # pod-major linear device index => globally contiguous rank order
        np.testing.assert_array_equal(np.asarray(ranks), np.arange(p))
        print("OK")
    """, rows * cols)


def test_pba_parity_matrix_8dev():
    """flat 1x8, pods 2x4 / 4x2, flat 1x2 (lp=4) and host: all
    bit-identical (single-shot)."""
    run_with_devices("""
        import numpy as np
        from repro.core import FactionSpec, PBAConfig, make_factions
        from repro.core.pba import generate_pba
        from repro.runtime import Topology
        table = make_factions(8, FactionSpec(4, 2, 4, seed=2))
        cfg = PBAConfig(vertices_per_proc=100, edges_per_vertex=3, seed=5)
        e_h, st_h = generate_pba(cfg, table, topology=Topology.host())
        rs = np.asarray(e_h.src).reshape(-1)
        rd = np.asarray(e_h.dst).reshape(-1)
        for topo in (Topology.flat(8), Topology.pods(2, 4),
                     Topology.pods(4, 2), Topology.flat(2)):
            e, st = generate_pba(cfg, table, topology=topo)
            np.testing.assert_array_equal(
                np.asarray(e.src).reshape(-1), rs, err_msg=topo.label)
            np.testing.assert_array_equal(
                np.asarray(e.dst).reshape(-1), rd, err_msg=topo.label)
            assert st.dropped_edges == st_h.dropped_edges
            assert st.pair_capacity == st_h.pair_capacity > 0
        print("OK")
    """, 8)


def test_pba_sharded_parity_2dev():
    """lp=4 logical procs per device through map_logical + the transposes."""
    run_with_devices("""
        import numpy as np
        from repro.core import FactionSpec, PBAConfig, make_factions
        from repro.core.pba import generate_pba
        from repro.runtime import Topology
        table = make_factions(8, FactionSpec(4, 2, 4, seed=2))
        cfg = PBAConfig(vertices_per_proc=100, edges_per_vertex=3, seed=5)
        e_s, st_s = generate_pba(cfg, table, topology=Topology.flat(2))
        e_h, st_h = generate_pba(cfg, table, topology=Topology.host())
        np.testing.assert_array_equal(np.asarray(e_s.src).reshape(-1),
                                      np.asarray(e_h.src).reshape(-1))
        np.testing.assert_array_equal(np.asarray(e_s.dst).reshape(-1),
                                      np.asarray(e_h.dst).reshape(-1))
        assert st_s.dropped_edges == st_h.dropped_edges
        print("OK")
    """, 2)


def test_shim_runs_on_8dev():
    """The shim + blocking reductions on a real 8-way device axis."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.runtime import Topology, blocking, spmd
        mesh = spmd.make_proc_mesh(8)
        def body(x):
            return blocking.all_reduce_sum(x.sum(), Topology.flat(8))[None]
        out = jax.jit(spmd.shard_map(
            body, mesh=mesh, in_specs=(P("proc"),), out_specs=P("proc"),
            check_vma=False))(jnp.arange(16, dtype=jnp.int32))
        assert int(np.asarray(out)[0]) == 120
        print("OK")
    """, 8)
