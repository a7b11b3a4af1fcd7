"""Fig. 3 analogue on real (forced host) devices: constant local problem.

Wall-clock on a shared CPU is only indicative; the *structural* assertions
are the strong ones: PK's HLO contains zero collectives (embarrassingly
parallel — the paper's key claim for it), PBA's contains exactly the two
exchange collectives, and both produce the right edge counts at every P.
"""
import re

import pytest

from helpers import run_with_devices


@pytest.mark.parametrize("procs", [2, 8])
def test_pk_zero_collectives(procs):
    out = run_with_devices(f"""
        import re, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import star_clique_seed, PKConfig
        from repro.core.pk import decompose_base, expand_chunk
        seed = star_clique_seed(4)
        cfg = PKConfig(levels=5)
        e = seed.num_edges ** 5
        chunk = -(-e // {procs})
        from repro.runtime import spmd
        mesh = spmd.make_proc_mesh({procs})
        bases = np.stack([decompose_base(min(p * chunk, e), seed.num_edges, 5)
                          for p in range({procs})]).astype(np.int32)
        su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
        def body(base):
            t = jnp.arange(chunk, dtype=jnp.int32)
            u, v = expand_chunk(t, base[0], su, sv, seed.num_vertices,
                                seed.num_edges, 5, cfg, 0)
            return u[None], v[None]
        f = jax.jit(spmd.shard_map(body, mesh=mesh, in_specs=(P("proc", None),),
                                   out_specs=(P("proc", None), P("proc", None)),
                                   check_vma=False))
        hlo = f.lower(jnp.asarray(bases)).compile().as_text()
        colls = re.findall(r"(all-reduce|all-gather|reduce-scatter|"
                           r"all-to-all|collective-permute)", hlo)
        assert not colls, f"PK must be collective-free, found {{colls}}"
        u, v = f(jnp.asarray(bases))
        assert int((np.asarray(u).reshape(-1) >= 0).sum()) >= e
        print("OK")
    """, procs)
    assert "OK" in out


def test_pba_exactly_two_exchanges():
    out = run_with_devices("""
        import re, jax, numpy as np
        from repro.core import make_factions, FactionSpec, PBAConfig
        from repro.core.pba import _pba_inputs, _pba_program
        from repro.runtime import Topology
        procs = 8
        table = make_factions(procs, FactionSpec(4, 2, 4, seed=1))
        cfg = PBAConfig(vertices_per_proc=200, edges_per_vertex=3, seed=7,
                        pair_capacity=256)
        topo = Topology.flat(procs)
        f = _pba_program(cfg, procs, 256, topo)
        hlo = f.lower(*_pba_inputs(table, topo)).compile().as_text()
        n_a2a = len(re.findall(r" all-to-all\\(", hlo))
        assert n_a2a == 2, f"expected exactly 2 all_to_alls, got {n_a2a}"
        print("OK")
    """, 8)
    assert "OK" in out


def test_pba_hierarchical_exactly_four_exchanges():
    """2-D pods topology: each of the two exchanges is a two-hop transpose
    (intra-pod + cross-pod all_to_all) — exactly 4 all_to_alls, half with
    strided (cross-pod) replica groups."""
    out = run_with_devices("""
        import re, jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import make_factions, FactionSpec, PBAConfig
        from repro.core.pba import pba_logical_block
        from repro.launch.hlo_stats import all_to_all_span_bytes
        from repro.runtime import Topology, blocking, spmd
        procs = 8
        table = make_factions(procs, FactionSpec(4, 2, 4, seed=1))
        cfg = PBAConfig(vertices_per_proc=200, edges_per_vertex=3, seed=7,
                        pair_capacity=256)
        topo = Topology.pods(2, 4)
        mesh = topo.build_mesh()
        spec = topo.spec_axes
        def body(procs_blk, s_blk):
            ranks = blocking.logical_ranks(1, topo)
            u, v, dropped, _, rounds = pba_logical_block(
                ranks, procs_blk, s_blk, cfg, procs, 256, topo)
            return u, v
        f = jax.jit(spmd.shard_map(
            body, mesh=mesh,
            in_specs=(P(spec, None), P(spec)),
            out_specs=(P(spec, None), P(spec, None)), check_vma=False))
        hlo = f.lower(jnp.asarray(table.procs),
                      jnp.asarray(table.s)).compile().as_text()
        n_a2a = len(re.findall(r" all-to-all\\(", hlo))
        assert n_a2a == 4, f"expected exactly 4 all_to_alls, got {n_a2a}"
        span = all_to_all_span_bytes(hlo)
        assert span["n_local"] == 2 and span["n_cross"] == 2, span
        print("OK")
    """, 8)
    assert "OK" in out


def test_weak_scaling_times():
    """Generation completes at every P with constant local size; report times."""
    for procs in (1, 2, 4, 8):
        out = run_with_devices(f"""
            import time, jax, numpy as np
            from repro.core import (make_factions, FactionSpec, PBAConfig,
                                    generate_pba, star_clique_seed, PKConfig,
                                    generate_pk)
            from repro.runtime import Topology
            table = make_factions({procs}, FactionSpec(
                max({procs} // 2, 1), 1, max({procs} // 2, 1), seed=1))
            cfg = PBAConfig(vertices_per_proc=20000, edges_per_vertex=4,
                            seed=7)
            t0 = time.perf_counter()
            edges, stats = generate_pba(cfg, table, Topology.flat({procs}))
            jax.block_until_ready(edges.src)
            t = time.perf_counter() - t0
            assert stats.emitted_edges + stats.dropped_edges == \\
                {procs} * 20000 * 4
            print(f"pba_p{procs}", round(t, 3))
        """, procs)
        assert f"pba_p{procs}" in out
