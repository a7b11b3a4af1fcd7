#!/usr/bin/env bash
# Tier-1 verify + 8-host-device smoke + static analysis + collective gates.
#
# Catches environment drift mechanically: the probe prints which shard_map
# API the runtime layer resolved, spmdlint enforces the SPMD invariants
# statically (python -m repro.analysis), the test run covers the
# single-device suite, the smoke pass exercises the real distributed paths
# (shard_map collectives, blocked/streamed transposes, tail masking) on 8
# forced host devices, pallascheck statically certifies every registered
# pl.pallas_call (grid/BlockSpec partition + race, VMEM budget) and runs
# the interpret-vs-ref differential, the compiled-collective audit
# re-derives the all_to_all structure of every front-door program from its
# jaxpr/HLO, flowcheck proves the dataflow contracts (RNG lineage from the
# declared determinism roots, blocked-layout axis roles on every
# all_to_all, spec-digest soundness), and the collective gate fails on
# exchange-volume regressions, audit-count drift, kernel-inventory drift,
# and flow-inventory drift against the committed results/ baselines.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== environment probe =="
python - <<'PY'
import jax, numpy, pytest
from repro.runtime import spmd
print("jax", jax.__version__, "| numpy", numpy.__version__,
      "| pytest", pytest.__version__)
info = spmd.api_info()
print("shard_map ->", info["shard_map_impl"],
      f"({info['check_kwarg']}, {info['manual_axes_kwarg']})")
try:
    import hypothesis
    print("hypothesis", hypothesis.__version__)
except ImportError:
    print("hypothesis missing: property tests will be skipped")
PY

echo "== spmdlint =="
python -m repro.analysis

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== 8-host-device smoke =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import numpy as np
from repro.core import (FactionSpec, PBAConfig, PKConfig, make_factions,
                        generate_pba, generate_pk, star_clique_seed)
from repro.core.distributed_analysis import (degree_counts_sharded,
                                             edge_count_sharded)
from repro.runtime import Topology

table = make_factions(8, FactionSpec(4, 2, 4, seed=1))
cfg = PBAConfig(vertices_per_proc=200, edges_per_vertex=3, seed=7)
e_d, st_d = generate_pba(cfg, table, Topology.flat(8))
e_h, st_h = generate_pba(cfg, table, Topology.host())
np.testing.assert_array_equal(np.asarray(e_d.src).reshape(-1),
                              np.asarray(e_h.src).reshape(-1))
np.testing.assert_array_equal(np.asarray(e_d.dst).reshape(-1),
                              np.asarray(e_h.dst).reshape(-1))

# multi-round streaming exchange: same parity contract, zero drops
import dataclasses
cfg_s = dataclasses.replace(cfg, pair_capacity=8, exchange_rounds=4)
e_ds, st_ds = generate_pba(cfg_s, table, Topology.flat(8))
e_hs, st_hs = generate_pba(cfg_s, table, Topology.host())
np.testing.assert_array_equal(np.asarray(e_ds.src).reshape(-1),
                              np.asarray(e_hs.src).reshape(-1))
np.testing.assert_array_equal(np.asarray(e_ds.dst).reshape(-1),
                              np.asarray(e_hs.dst).reshape(-1))
assert st_ds.exchange_rounds == st_hs.exchange_rounds > 1, (st_ds, st_hs)

pk_edges, pk_st = generate_pk(star_clique_seed(4), PKConfig(levels=5))
assert pk_st.emitted_edges == pk_st.requested_edges, pk_st

assert edge_count_sharded(e_d) == st_d.emitted_edges
deg = degree_counts_sharded(e_d)
assert int(deg.sum()) == 2 * st_d.emitted_edges
print("8-device smoke OK")
PY

echo "== 2x4 hierarchical smoke =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import dataclasses
import numpy as np
from repro.core import PBAConfig, generate_pba, hub_factions
from repro.runtime import Topology

# Two-hop intra-pod/cross-pod exchange must be bit-identical to the host
# path, single-shot and streamed, on both pod factorizations.
table = hub_factions(8)
cfg = PBAConfig(vertices_per_proc=150, edges_per_vertex=3, seed=5,
                pair_capacity=16, total_capacity_factor=8)
for cfg_i in (cfg, dataclasses.replace(cfg, exchange_rounds=4)):
    e_h, st_h = generate_pba(cfg_i, table, Topology.host())
    for topo in (Topology.pods(2, 4), Topology.pods(4, 2)):
        e_s, st_s = generate_pba(cfg_i, table, topology=topo)
        np.testing.assert_array_equal(np.asarray(e_s.src).reshape(-1),
                                      np.asarray(e_h.src).reshape(-1))
        np.testing.assert_array_equal(np.asarray(e_s.dst).reshape(-1),
                                      np.asarray(e_h.dst).reshape(-1))
        assert st_s.dropped_edges == st_h.dropped_edges, (st_s, st_h)
        assert st_s.exchange_rounds == st_h.exchange_rounds, (st_s, st_h)
print("hierarchical smoke OK")
PY

echo "== sharded-streamed smoke =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import tempfile
import numpy as np
from repro import api
from repro.core.storage import read_shards
from repro.runtime import Topology

# Out-of-core generation over the real mesh: sink='shards' on 8 devices
# resolves to the device-sharded stream, its shards are bit-identical to
# the host-driven stream's on the flat and hierarchical topologies, and
# the hub-stress layout ships zero dropped edges.
with tempfile.TemporaryDirectory() as d:
    spec = api.preset("hub_stress", sink="shards", out_dir=d + "/flat")
    pl = api.plan(spec)
    assert pl.executor == "pba_stream_sharded", pl.executor
    assert pl.overlap_bytes > 0, pl
    res = api.generate(pl)
    assert res.stats.dropped_edges == 0, res.stats
    assert res.stats.exchange_rounds > 1, res.stats
    s_ref, d_ref, man = read_shards(d + "/flat")
    assert len(s_ref) == res.stats.emitted_edges
    for tag, topo in (("host", Topology.host()),
                      ("pods", Topology.pods(2, 4))):
        r = api.generate(spec.replace(topology=topo,
                                      out_dir=d + "/" + tag))
        s, dd, _ = read_shards(d + "/" + tag)
        np.testing.assert_array_equal(s, s_ref, err_msg=tag)
        np.testing.assert_array_equal(dd, d_ref, err_msg=tag)
print("sharded-streamed smoke OK")
PY

echo "== front door: preset dry-run + end-to-end =="
python examples/generate_massive.py --preset paper_smoke --dry-run
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import tempfile
from repro import api

# auto resolution lands on the sharded executor over the 8 forced devices
res = api.generate(api.preset("paper_smoke"))
assert res.plan.execution == "sharded", res.plan.executor
assert res.stats.emitted_edges + res.stats.dropped_edges \
    == res.stats.requested_edges

# streamed hub-stress preset into a resumable shard sink: zero drops
with tempfile.TemporaryDirectory() as d:
    shards = api.generate(api.preset("hub_stress", sink="shards",
                                     out_dir=d))
    assert shards.plan.execution == "streamed"
    assert shards.stats.dropped_edges == 0, shards.stats
    from repro.core.storage import read_shards
    src, dst, man = read_shards(d)
    assert len(src) == shards.stats.emitted_edges
    assert "spec_digest" in man["meta"]
print("front door OK")
PY

echo "== communication-free smoke =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import tempfile
import numpy as np
from repro import api
from repro.core.storage import read_shards
from repro.runtime import Topology

# Zero-exchange generators through the front door: host, sharded over the
# forced mesh, and streamed-to-shards all emit bit-identical edges with
# exchange_rounds == 0.
for model, kw in (("ba_cfree", dict(cfree_vertices=400, ba_degree=3)),
                  ("rmat", dict(cfree_vertices=256, cfree_edges=1024)),
                  ("er", dict(cfree_vertices=300, cfree_edges=900))):
    spec = api.GraphSpec(model=model, seed=7, **kw)
    hs, hd = api.generate(spec.replace(execution="host")).edges.to_numpy()
    res = api.generate(spec.replace(execution="sharded",
                                    topology=Topology.pods(2, 4)))
    assert res.stats.exchange_rounds == 0, res.stats
    ss, sd = res.edges.to_numpy()
    np.testing.assert_array_equal(ss, hs, err_msg=model)
    np.testing.assert_array_equal(sd, hd, err_msg=model)
    with tempfile.TemporaryDirectory() as d:
        api.generate(spec.replace(sink="shards", out_dir=d, slab_edges=97))
        s, dd, _ = read_shards(d)
        assert sorted(zip(s.tolist(), dd.tolist())) \
            == sorted(zip(hs.tolist(), hd.tolist())), model

# preset dry-run: the paper-scale cfree plan validates without compiling
pl = api.plan(api.preset("ba_cfree_1b"))
assert pl.exchange_rounds == 0 and pl.requested_edges == 1_000_000_000
print("communication-free smoke OK")
PY
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m benchmarks.cfree_expand --smoke

echo "== pallascheck: kernel registry (interpret differential) =="
REPRO_PALLAS=interpret python -m repro.analysis kernels

echo "== compiled-collective audit =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.analysis audit --out /tmp/collective_audit.json

echo "== flowcheck: jaxpr dataflow verifier =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.analysis flow --out /tmp/flow_audit.json

echo "== collective-bytes gate =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python scripts/collective_gate.py

echo "verify OK"
