"""Plain reference of the PBA generator with a static urn budget.

The same graph as ``bench/references/pba.py`` describes, except for the
size every phase-2 urn is drawn at: with ``auto_capacity`` false in the
configuration's spec, every provider's urn holds E + B slots with
B = ``total_capacity_factor`` * E (the generator's default factor is 2),
the budget the repo's host and sharded executors draw at, where the
default streamed run takes the busiest provider's demand rounded up to a
power of two. A provider whose demand exceeds B would drop edges; the
harness counts those as ``edges_dropped``.

``control=True`` draws the inter-faction coin in bfloat16, as in
``pba.py``.
"""
from __future__ import annotations

import jax

from bench.edges import to_host
from bench.references import pba

ORDERED = pba.ORDERED


def budget(config: dict) -> int:
    """B, the urn slots beyond the E source slots, of ``config``."""
    spec = config["spec"]
    if spec.get("auto_capacity", True):
        raise ValueError("pba_static describes auto_capacity=false specs; "
                         "the demand-sized budget is pba.py's")
    _, _, _, edges = pba._sizes(config)
    return int(spec.get("total_capacity_factor", 2)) * edges


def reference(config: dict, seed: int, *, control: bool = False):
    """Fingerprint of the PBA graph ``config`` describes, for ``seed``."""
    procs, verts, degree, edges = pba._sizes(config)
    with jax.default_device(jax.devices("cpu")[0]):
        key, tags, counts = pba._tags(config, seed, control)
        return to_host(pba._phase2(key, tags, counts, procs=procs,
                                   edges=edges, verts=verts, degree=degree,
                                   budget=budget(config)))
