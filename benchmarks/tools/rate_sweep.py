#!/usr/bin/env python3
"""Find, once, the highest rate an open-loop serve cell sustains.

    python3 benchmarks/tools/rate_sweep.py --workload gpt2m-serve-steady \\
        --rates 8,12,16,20 --seconds 20

Sets the cell's server up once, then offers the cell's traffic at each rate
in turn for ``--seconds`` (the server drains between rates; the arrivals
are the cell's fixed draw at that rate, each at another phase). A rate is
sustained when the backlog does not grow: requests still open when the
arrivals end are no more than a batch's worth, and the last third's median
latency is not above the first third's by more than a half. The cell's
traffic file then gets 0.8 of the highest sustained rate, as a number; no
run of the benchmark searches for a rate.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import numpy as np
    from benchmarks import harness
    from benchmarks.drivers import serve_open, serving

    run = harness.start_run(args.workload, args.seed, args.seconds, False,
                            T_START)
    cell = run.cell
    rows = []
    with serving.served(run) as (server, prompts):
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            due = serve_open.schedule(cell.traffic["schedule_seed"], rate,
                                      args.seconds, args.seed + k)
            t0 = time.perf_counter() + 0.05
            reqs = serve_open.offer(server, prompts, due, t0,
                                    cell.traffic["drain_timeout_s"])
            end = t0 + args.seconds
            lat = serve_open.latencies_ms(reqs, args.seconds)
            third = max(1, len(reqs) // 3)
            row = {"rate_per_s": rate, "requests": len(reqs),
                   "failed": sum(1 for r in reqs if not r.ok),
                   "open_at_end": sum(1 for r in reqs
                                      if r.done is None or r.done > end),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "p95_judged_ms": serve_open.block_percentile(
                       lat, 95, cell.traffic["latency_blocks"]),
                   "p99_ms": float(np.percentile(lat, 99)),
                   "p50_first_third_ms": float(np.median(lat[:third])),
                   "p50_last_third_ms": float(np.median(lat[-third:])),
                   "drain_s": max(r.done for r in reqs if r.done) - end,
                   "late_p99_ms": float(np.percentile(
                       [(r.submitted - r.due) * 1e3 for r in reqs], 99))}
            rows.append(row)
            print(json.dumps(row), flush=True)
        report = server.report()
    print(json.dumps({"sweep": rows, "errors": report["errors"],
                      "coalesced_batches": report["coalesced_batches"],
                      "coalesced_requests": report["coalesced_requests"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
