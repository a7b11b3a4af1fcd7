"""Version-portable SPMD runtime layer — the single gateway for all
distributed execution in this repo.

Submodules:
  spmd     — the shard_map / make_mesh gateway; device_kind/count/memory
             probes; the axis_index gateway; the persistent compile
             cache an entry point turns on.
  topology — the Topology dataclass: mesh axes + sizes + the P = lp * D
             factorization (host / flat / pods constructors).
  blocking — logical-processors-over-devices primitives: map_logical,
             transpose_counts / transpose_payload (1-D: one (lp, d, lp)
             all_to_all; 2-D pods: hierarchical two-hop intra-pod ->
             cross-pod exchange), tail masking, all_reduce_sum over every
             topology axis.
  streaming — multi-round streamed exchange over the blocked-transpose
             contract: run_exchange loops (lp, P, C_r) rounds until the
             globally all-reduced residual hits zero (bounded memory,
             zero drops) — topology-agnostic by construction.

No module outside ``repro.runtime`` may reference ``jax.shard_map`` /
``jax.experimental.shard_map``, ``jax.lax.all_to_all``, or
``jax.lax.axis_index`` directly (enforced by tests/test_runtime.py).
"""
from repro.runtime import blocking, spmd, streaming, topology
from repro.runtime.blocking import (all_reduce_sum, device_index,
                                    logical_ranks, map_logical, mask_tail,
                                    split_logical, tail_mask,
                                    transpose_counts, transpose_payload)
from repro.runtime.spmd import (api_info, axis_index, cost_analysis,
                                device_count, device_kind,
                                device_memory_bytes, ensure_mesh, make_mesh,
                                make_proc_mesh, mesh_size, shard_map)
from repro.runtime.topology import Topology, resolve

__all__ = [
    "spmd", "blocking", "streaming", "topology", "Topology", "resolve",
    "shard_map", "make_mesh", "make_proc_mesh", "ensure_mesh", "mesh_size",
    "api_info", "cost_analysis", "axis_index", "device_count", "device_kind",
    "device_memory_bytes",
    "map_logical", "logical_ranks", "device_index", "split_logical",
    "transpose_counts", "transpose_payload", "tail_mask", "mask_tail",
    "all_reduce_sum",
]
