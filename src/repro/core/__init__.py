"""Core library: the paper's contribution — parallel scale-free graph generation.

Public API — the **one front door** is ``repro.api``:
  GraphSpec -> repro.api.plan() -> repro.api.generate()

The per-model entry points below (generate_pba, generate_pk*, PBAStream,
PKStream, stream_to_shards) are the internal executors that front door
dispatches to. They remain importable for compatibility but are
deprecated as public entry points — new callers should build a GraphSpec
(see README "One front door").

  PBA (parallel Barabási–Albert): PBAConfig, generate_pba
  PK (parallel Kronecker): PKConfig, SeedGraph, generate_pk, generate_pk_host
  Factions: FactionSpec, FactionTable, make_factions, block_factions
  Out-of-core streaming: PBAStream, PKStream, stream_to_shards
  Containers: EdgeList, GenStats
  Analysis: fit_power_law, sampled_path_stats, community_contrast, ...
"""
import warnings

from repro.core.graph import EdgeList, GenStats, degree_counts, to_csr
from repro.core.factions import (FactionSpec, FactionTable, make_factions,
                                 block_factions, hub_factions)
from repro.core import pba as _pba
from repro.core import pk as _pk
from repro.core import stream as _stream
from repro.core.pba import PBAConfig, serial_ba_reference
from repro.core.pk import (PKConfig, SeedGraph, star_clique_seed,
                           dense_power_seed, dense_kronecker_power,
                           pk_sizes, xor_randomize)
from repro.core.spec import GraphSpec, spec_digest
from repro.core.stream import EdgeBlock
from repro.core.analysis import (fit_power_law, sampled_path_stats,
                                 community_contrast, block_density,
                                 self_similarity_score,
                                 sampled_clustering_coefficient,
                                 degree_histogram)


# Deprecation shims (PEP 562): the legacy entry points resolve to the very
# same internal executors ``repro.api.generate`` dispatches to — type
# identity and signatures are preserved (isinstance/subclassing keep
# working) — but touching them through ``repro.core`` warns: new code
# should describe the graph with a GraphSpec and go through the front
# door (plan/generate) instead.
_DEPRECATED_ENTRY_POINTS = {
    "generate_pba": _pba.generate_pba,
    "generate_pk": _pk.generate_pk,
    "generate_pk_host": _pk.generate_pk_host,
    "PBAStream": _stream.PBAStream,
    "PKStream": _stream.PKStream,
    "stream_to_shards": _stream.stream_to_shards,
}


def __getattr__(name):
    obj = _DEPRECATED_ENTRY_POINTS.get(name)
    if obj is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    warnings.warn(
        f"repro.core.{name} is deprecated as a public entry point; "
        "build a repro.api.GraphSpec and call repro.api.generate "
        "(see README 'One front door')",
        DeprecationWarning, stacklevel=2)
    return obj

__all__ = [
    "EdgeList", "GenStats", "degree_counts", "to_csr",
    "FactionSpec", "FactionTable", "make_factions", "block_factions",
    "hub_factions",
    "GraphSpec", "spec_digest",
    "PBAConfig", "generate_pba", "serial_ba_reference",
    "PKConfig", "SeedGraph", "generate_pk", "generate_pk_host",
    "star_clique_seed", "dense_power_seed", "dense_kronecker_power",
    "pk_sizes", "xor_randomize",
    "EdgeBlock", "PBAStream", "PKStream", "stream_to_shards",
    "fit_power_law", "sampled_path_stats", "community_contrast",
    "block_density", "self_similarity_score",
    "sampled_clustering_coefficient", "degree_histogram",
]
