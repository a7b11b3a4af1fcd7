#!/usr/bin/env python3
"""Benchmark entry point: run one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See bench/harness.py for what a run does and prints.
"""
import time

T0 = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    # libtpu writes its logs under /tmp unless told otherwise; a run
    # writes only inside its checkout and its own TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.pin_compile_cache(ROOT)   # before anything imports JAX
    sys.exit(harness.main(sys.argv[1:], t0=T0, root=ROOT))
