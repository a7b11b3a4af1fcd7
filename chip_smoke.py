#!/usr/bin/env python3
"""Smoke run of the generator's main path on TPU, through the front door.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip exchange phase only

One chip runs two phases, each through ``api.plan`` / ``api.generate``:

1. PBA at the paper's per-rank shape (64 logical ranks x 500k vertices x
   5 edges = 1.6e8 edges, 8 exchange rounds) streamed on ``flat(1)``:
   no drops, and a warm call equal to the first. At ``paper_smoke`` size
   its edges must equal, bit for bit, the host-driven stream of the same
   spec on the chip and the XLA reference on the CPU device.
2. The Mosaic expansion kernels: ``ba_cfree`` at 1e8 edges and PK at
   levels=8 (4.3e7 edges), each matched bit for bit against the XLA
   reference on the CPU device over a leading and a trailing slice.

``--chips 4`` runs the phase-1 spec on ``flat(4)`` and ``pods(2, 2)`` and
requires both to equal its ``flat(1)`` run on device 0.

Timings printed here are smoke timings, not benchmark numbers. The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``,
printed only when every check passed. Without a TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.api import GraphSpec, Topology  # noqa: E402
from repro.core import cfree as cfree_lib  # noqa: E402
from repro.core import degree_counts, fit_power_law  # noqa: E402
from repro.core import pk as pk_lib  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch.bench import (compile_sharded_cfree,  # noqa: E402
                                compile_sharded_stream_round)
from repro.runtime import spmd  # noqa: E402

#: The paper's per-rank shape (Table 1: 1M vertices x 5 edges per rank,
#: R=8) at half the vertices per rank and 64 ranks, so the whole run's
#: state fits one v5e chip (plan: ~4.6 GiB of device state).
PBA_SPEC = GraphSpec(model="pba", procs=64, vertices_per_proc=500_000,
                     edges_per_vertex=5, exchange_rounds=8, seed=7,
                     execution="streamed", topology=Topology.flat(1))
#: ``paper_smoke``'s scale for the CPU-reference check. The pair capacity
#: is pinned: its default depends on the probed device memory.
SMALL_SPEC = PBA_SPEC.replace(procs=8, vertices_per_proc=2000,
                              edges_per_vertex=4, pair_capacity=2048)
CFREE_SPEC = GraphSpec(model="ba_cfree", cfree_vertices=25_000_000,
                       ba_degree=4, seed=7, execution="sharded",
                       topology=Topology.flat(1))
PK_SPEC = GraphSpec(model="pk", levels=8, noise=0.0, seed=3,
                    execution="sharded", topology=Topology.flat(1))
SLICE = 1 << 20


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found platform {dev.platform!r} "
                           f"({dev.device_kind})")
    if os.environ.get("REPRO_PALLAS"):
        raise SmokeFailure(f"REPRO_PALLAS={os.environ['REPRO_PALLAS']!r} "
                           "is set; the smoke run needs compiled kernels")
    if dispatch.mode() != "tpu":
        raise SmokeFailure(f"dispatch mode is {dispatch.mode()!r}, not 'tpu'")
    if len(jax.devices()) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} devices, JAX "
                           f"found {len(jax.devices())}")
    return dev


def generate(pl):
    """(result, host (src, dst), wall seconds until the edges are in the
    sink)."""
    t0 = time.perf_counter()
    res = api.generate(pl)
    jax.block_until_ready((res.edges.src, res.edges.dst))
    wall = time.perf_counter() - t0
    host = (np.asarray(res.edges.src).reshape(-1),
            np.asarray(res.edges.dst).reshape(-1))
    return res, host, wall


def identical(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def custom_calls(fn, args) -> int:
    return fn.lower(*args).compile().as_text().count("tpu_custom_call")


def phase_pba() -> None:
    pl = api.plan(PBA_SPEC)
    say(f"[pba] executor={pl.executor} P={pl.num_procs} lp={pl.lp} "
        f"topology={pl.topology.label} "
        f"plan_device_bytes={pl.device_bytes}")
    check(pl.executor == "pba_stream_sharded",
          f"executor {pl.executor}, expected pba_stream_sharded")

    res, first, t_first = generate(pl)
    st = res.stats
    deg = np.asarray(degree_counts(res.edges))
    del res
    res, warm, t_warm = generate(pl)
    del res
    say(f"[pba] emitted={st.emitted_edges} requested={st.requested_edges} "
        f"drops={st.dropped_edges} rounds={st.exchange_rounds} "
        f"pair_capacity={st.pair_capacity}")
    say(f"[pba] smoke timing (not a benchmark): first call {t_first} s "
        f"(compile included), warm call {t_warm} s")
    check(st.dropped_edges == 0, f"{st.dropped_edges} edges dropped")
    check(st.emitted_edges == st.requested_edges == first[0].size,
          "emitted edge count disagrees with the edge list")
    check(identical(first, warm), "warm call differs from the first call")
    stats = jax.devices()[0].memory_stats()
    say(f"[pba] peak_bytes_in_use={stats['peak_bytes_in_use']} "
        f"bytes_limit={stats['bytes_limit']}")
    fit = fit_power_law(deg, kmin=5)
    say(f"[pba] gamma_mle={fit.gamma_mle} max_degree={int(deg.max())}")

    n_custom = custom_calls(*compile_sharded_stream_round(pl))
    say(f"[pba] round program tpu_custom_call={n_custom}")
    check(n_custom >= 1, "no Mosaic kernel in the compiled round program")

    # The references run at paper_smoke scale: the full-size host-driven
    # stream takes over two minutes on one chip.
    _, chip_small, _ = generate(api.plan(SMALL_SPEC))
    # (a) the host-driven stream of the same spec, on the chip
    _, host, t_host = generate(api.plan(SMALL_SPEC.replace(
        topology=Topology.host())))
    same = identical(chip_small, host)
    say(f"[pba] paper_smoke scale: host stream (pba_stream) {t_host} s, "
        f"identical={same}")
    check(same, "flat(1) stream differs from the host-driven stream")

    # (b) the chip against XLA on the CPU device
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), dispatch.forced_mode("off"):
        ref, cpu_small, _ = generate(api.plan(SMALL_SPEC.replace(
            topology=Topology.host())))
    on = sorted(str(d) for d in ref.edges.src.devices())
    same = identical(chip_small, cpu_small)
    say(f"[pba] paper_smoke scale: {chip_small[0].size} edges, CPU "
        f"reference on {on}, identical={same}")
    check(same, "chip stream differs from the CPU reference")


def _slices(e: int):
    return ((0, SLICE), (e - SLICE, e))


def phase_kernels() -> None:
    cpu = jax.devices("cpu")[0]

    pl = api.plan(CFREE_SPEC)
    res, (u, v), wall = generate(pl)
    del res
    n_custom = custom_calls(*compile_sharded_cfree(pl))
    cfg = pl.config
    say(f"[cfree] {cfg.model} executor={pl.executor} edges={u.size} "
        f"tpu_custom_call={n_custom}; smoke timing {wall} s")
    check(u.size == pl.requested_edges, "cfree edge count")
    check(n_custom >= 1, "no Mosaic kernel in the cfree program")
    with jax.default_device(cpu), dispatch.forced_mode("off"):
        ref = jax.jit(lambda t: cfree_lib.cfree_endpoints(
            cfg, t, cfree_lib.cfree_words(cfg)))
        for lo, hi in _slices(u.size):
            ru, rv = ref(jnp.arange(lo, hi, dtype=jnp.int32))
            same = identical((u[lo:hi], v[lo:hi]),
                             (np.asarray(ru), np.asarray(rv)))
            say(f"[cfree] slice [{lo}, {hi}) identical={same}")
            check(same, f"cfree slice [{lo}, {hi}) differs from XLA")

    pl = api.plan(PK_SPEC)
    res, (u, v), wall = generate(pl)
    del res
    say(f"[pk] levels={pl.config.levels} executor={pl.executor} "
        f"edges={u.size}; smoke timing {wall} s")
    check(u.size == pl.requested_edges, "pk edge count")
    seed = pl.seed_graph
    with jax.default_device(cpu), dispatch.forced_mode("off"):
        ref = jax.jit(functools.partial(
            pk_lib.expand_chunk, n0=seed.num_vertices, e0=seed.num_edges,
            levels=pl.config.levels, cfg=pl.config, rank=0))
        su, sv = jnp.asarray(seed.u), jnp.asarray(seed.v)
        for lo, hi in _slices(u.size):
            base = jnp.asarray(pk_lib.decompose_base(
                lo, seed.num_edges, pl.config.levels))
            ru, rv = ref(jnp.arange(hi - lo, dtype=jnp.int32), base, su, sv)
            same = identical((u[lo:hi], v[lo:hi]),
                             (np.asarray(ru), np.asarray(rv)))
            say(f"[pk] slice [{lo}, {hi}) identical={same}")
            check(same, f"pk slice [{lo}, {hi}) differs from XLA")


def phase_four_chips() -> None:
    runs = {}
    for topo in (Topology.flat(4), Topology.pods(2, 2), Topology.flat(1)):
        pl = api.plan(PBA_SPEC.replace(topology=topo))
        res, edges, wall = generate(pl)
        st = res.stats
        placed = sorted(str(d) for d in res.edges.src.sharding.device_set)
        del res
        peaks = [d.memory_stats()["peak_bytes_in_use"]
                 for d in jax.devices()[:4]]
        say(f"[4chip] {topo.label}: executor={pl.executor} lp={pl.lp} "
            f"drops={st.dropped_edges} rounds={st.exchange_rounds} "
            f"smoke timing {wall} s (compile included); output "
            f"device_set={placed}; peak_bytes_in_use per device={peaks}")
        check(st.dropped_edges == 0, f"{topo.label}: edges dropped")
        check(pl.executor == "pba_stream_sharded", pl.executor)
        runs[topo.label] = edges
    ref = runs.pop("flat_1x1")
    for label, edges in runs.items():
        same = identical(edges, ref)
        say(f"[4chip] {label} identical to flat_1x1: {same}")
        check(same, f"{label} differs from flat(1)")


def main() -> int:
    spmd.enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip exchange phase")
    args = ap.parse_args()
    t0 = time.perf_counter()
    try:
        dev = require_tpu(args.chips)
        say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
            f"; jax {jax.__version__}")
        phases = ((phase_four_chips,) if args.chips == 4
                  else (phase_pba, phase_kernels))
        for phase in phases:
            t = time.perf_counter()
            phase()
            say(f"{phase.__name__} done in {time.perf_counter() - t} s")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"total {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
