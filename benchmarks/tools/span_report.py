#!/usr/bin/env python3
"""Where a cell's set-up and its window went, by the program's own spans.

    python3 benchmarks/tools/span_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--trace 1] [--out chiprun_out/span_report.json]

Runs the cell once through the harness, as ``run.py`` does, prints its
result line, and then reads ``paddle_tpu.core.profiler``'s ring:

- before the window opened (spans that ended by then): the top-level
  spans by name (those no other span encloses on their thread), the
  compile phases (``jax.trace`` /
  ``jax.lower`` / ``jax.compile`` / ``jax.cache_read``) by the span they
  ran under, and ``setup_s`` less the union of the top-level spans: what
  lies outside the program (imports, the benchmark's own data,
  ``warm_slices``, the time between the program's calls);
- inside the window: per span name its count, total and self seconds
  and the longest one, and every ``jax.compile`` with the span it ran under (expected: none);
- with ``--trace 1``, where the cell has the metric: the device's idle
  seconds by program span.

Set-up parts cannot be entries of ``BENCHMARK.json`` (no per-layer metric
may name ``setup_s`` as what it moves); this report is their reader. The
last line of output is the report as one JSON object.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COMPILE_SPANS = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_read")


def under(spans, parents, i):
    """The name of the nearest enclosing span of ``spans[i]`` that is not
    itself a compile phase."""
    p = parents[i]
    while p is not None and spans[p][0] in COMPILE_SPANS:
        p = parents[p]
    return spans[p][0] if p is not None else "(no span)"


def report(run, obs) -> dict:
    from benchmarks import harness, program_spans as ps
    from benchmarks.trace_reduce import clip, total, union

    spans = ps.ring()
    win = ps.window(run, obs)
    start = ps.to_ring_clock(run.t_start)
    out = {"workload": run.cell.name, "seed": run.seed,
           "setup_s": obs.values["setup_s"], "window_s": obs.values["window_s"],
           "ring_spans": len(spans), "ring_full": ps.ring_is_full(spans),
           # what the driver saw on the host's clock, traced or not: a
           # traced run against an untraced one is what tracing costs
           "observed": {k: v for k, v in obs.values.items()
                        if isinstance(v, (int, float))}}
    if obs.series.get("latency_ms"):
        out["observed"]["latency_p50_ms"] = statistics.median(
            obs.series["latency_ms"])
    if obs.series.get("late_ms"):       # a starved generator is not a server
        out["observed"]["generator_late_max_ms"] = max(obs.series["late_ms"])

    # a worker's first turn begins with the server and ends with its first
    # reply, inside the window: set-up is what ended before the window
    before = [s for s in spans if start <= s[1] and s[1] + s[2] <= win[0]]
    par = ps.parents(before)
    top = [s for s, p in zip(before, par) if p is None]
    by_name = {}
    for s in top:
        row = by_name.setdefault(s[0], {"count": 0, "total_s": 0.0})
        row["count"] += 1
        row["total_s"] += s[2] / 1e9
    covered = total(union(clip(((s[1], s[1] + s[2]) for s in top),
                               (start, win[0])))) / 1e9
    compiles = {}
    for i, s in enumerate(before):
        if s[0] in COMPILE_SPANS:
            row = compiles.setdefault(under(before, par, i), {})
            row[s[0]] = row.get(s[0], 0.0) + s[2] / 1e9
            row["count." + s[0]] = row.get("count." + s[0], 0) + 1
    out["setup"] = {"top_level": by_name, "covered_s": covered,
                    "outside_program_s": obs.values["setup_s"] - covered,
                    "compile_by_parent": compiles,
                    "all_spans": ps.totals(before)}

    inside = ps.started_in(spans, win)
    par_in = ps.parents(inside)
    out["window"] = {
        "spans": ps.totals(inside),
        "compiles": [{"under": under(inside, par_in, i), "s": s[2] / 1e9,
                      "fun": s[4].get("fun")}
                     for i, s in enumerate(inside) if s[0] == "jax.compile"]}

    for spec in run.cell.per_layer if run.trace else []:
        if spec["reader"] == "idle_unattributed_share":
            reader = harness.load_module("readers", spec["reader"])
            out["idle_by_program_span_s"] = reader.attribution(obs, spec["args"])
    return out


def show(rep: dict) -> None:
    print(f"== {rep['workload']} seed {rep['seed']}: setup_s "
          f"{rep['setup_s']:.3f}, {rep['ring_spans']} spans in the ring"
          + (" (FULL: the oldest are gone)" if rep["ring_full"] else ""))
    s = rep["setup"]
    print("-- before the window: top-level spans")
    for name, row in sorted(s["top_level"].items(),
                            key=lambda kv: -kv[1]["total_s"]):
        print(f"  {name:<28}{row['count']:>6}{row['total_s']:>10.3f} s")
    print(f"  {'(outside the program)':<28}{'':>6}{s['outside_program_s']:>10.3f} s")
    print("-- before the window: compile phases by the span they ran under")
    for name, row in sorted(s["compile_by_parent"].items()):
        print(f"  {name:<28}" + "  ".join(
            f"{k[4:]} {row.get(k, 0.0):.2f} s x{row.get('count.' + k, 0)}"
            for k in COMPILE_SPANS))
    print("-- inside the window: count, total s, self s, longest s")
    for name, row in sorted(rep["window"]["spans"].items(),
                            key=lambda kv: -kv[1]["total_s"]):
        print(f"  {name:<28}{row['count']:>6}{row['total_s']:>10.3f}"
              f"{row['self_s']:>10.3f}{row['max_s']:>10.3f}")
    print(f"-- compiles inside the window: {rep['window']['compiles']}")
    if rep.get("idle_by_program_span_s") is not None:
        print(f"-- idle seconds by program span: {rep['idle_by_program_span_s']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", help="also write the report to this file")
    args = ap.parse_args()

    from benchmarks import harness

    run = harness.start_run(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)
    obs = run.cell.driver.run(run)
    print(json.dumps(harness.result_line(run, obs)), flush=True)
    rep = report(run, obs)
    show(rep)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=1)
    print(json.dumps({"span_report": rep}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
