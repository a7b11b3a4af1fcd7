#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed: one graph through the timed path (``harness.one_graph``),
the plain reference, and the reference's control (the same computation
one precision lower, see ``bench/references``). Prints one JSON line per
seed: whether the program's graph and the control's graph each differ
from the reference (``graphs_wrong`` as the harness counts it), and the
program's dropped and missing edges. A sound program reads 0 and the
control 1. The harness's own runs never run the control.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seeds, require_chip: bool = True):
    import jax
    from bench import harness
    from bench.edges import to_host
    if require_chip and jax.devices()[0].platform != "tpu":
        raise harness.NoChip("the control readings need the chip")
    sys.path.insert(0, os.path.join(cell.root, "src"))
    from repro import api
    from repro.runtime import spmd
    spmd.enable_compile_cache()
    ref = cell.reference()
    for seed in seeds:
        g, _ = harness.one_graph(api, harness.graph_spec(cell, seed),
                                 ref.ORDERED)
        got = to_host(g.fingerprint)
        want = tuple(ref.reference(cell.config, seed))
        control = tuple(ref.reference(cell.config, seed, control=True))
        yield {"seed": seed,
               "program_graphs_wrong": int(got != want),
               "control_graphs_wrong": int(control != want),
               "edges_dropped": g.dropped,
               "edges_missing": g.requested - g.emitted,
               "graph_s": g.seconds}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.pin_compile_cache(ROOT)
    try:
        cell = harness.resolve(ROOT, args.workload)
        for line in readings(cell, args.seeds):
            print(json.dumps(line), flush=True)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
