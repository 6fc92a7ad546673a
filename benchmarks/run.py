#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, sets the system up (that is ``setup_s``), warms every
shape, measures for ``--seconds``, checks the outputs against the plain
reference, and prints the result as the last line of standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the last seconds of the window are traced and the metrics are
its per-layer metrics, with the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. The compile cache is where
``paddle_tpu.core.config.compile_cache_dir`` says: ``JAX_COMPILATION_CACHE_DIR``
if set, else ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # benchmarks and paddle_tpu of this checkout


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmarks import harness

    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
