#!/usr/bin/env python3
"""Where a cell's device time went, by the program's own scopes.

    python3 benchmarks/tools/scope_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--json] [--out chiprun_out/scope_report.json]

Runs the cell once, traced, through the harness as ``run.py --trace 1``
does, prints its result line, and then joins the trace's device operations
inside the main module's whole executions to the table the program keeps for
that executable (``paddle_tpu.core.profiler.device_scopes``, the join the
``scope_time_share`` reader makes): one row a scope path with its
milliseconds a step or request, its share of the module's device time, of
it remat (a checkpoint's second forward), backward, inherited (operations
with no name stack of their own, placed with the instruction they feed:
the compiler's copies, slices and kernels), Mosaic kernels and collectives
by mesh axes, and under each path its five largest operations
(milliseconds and calls an execution) with the ``op_name`` the compiler
recorded, behind ``~`` where it is the donor's. This is how a ``fusion.N``
of a trace or of the ledger's ``breakdown.device_ops`` gets its name.

Beside the table: what reading the program's text and making the join
cost (the ``profiler.program_table`` and ``profiler.device_scopes`` spans:
seconds, bytes, rows). ``--json`` prints the numbers as one JSON object on
the last line.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import resource
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOP_OPS = 5


def collectives(ops, table) -> dict:
    """Milliseconds of a path's collectives by ``opcode axes``."""
    out = {}
    for k, (ns, _) in ops:
        if k in table and table[k].axes is not None:
            key = f"{table[k].opcode} {table[k].axes or '-'}"
            out[key] = out.get(key, 0.0) + ns / 1e6
    return out


def peak_rss_kb() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss


def report(run, obs) -> dict:
    from benchmarks.readers import scope_time_share
    from benchmarks.trace_reduce import total
    from paddle_tpu.core import profiler

    got = scope_time_share.joined(obs)
    out = {"workload": run.cell.name, "seed": run.seed,
           "host_peak_rss_kb": peak_rss_kb(),
           "table_spans": [s[4] | {"span": s[0], "s": s[2] / 1e9}
                           for s in profiler.spans()
                           if s[0] in ("profiler.program_table",
                                       "profiler.device_scopes")]}
    if got is None:
        return out
    joined, module_ns, runs, left_out = got
    execs = len(runs)
    tr = obs.trace
    name = tr.main_module(0)
    tables = profiler.program_tables(name)
    table = tables[joined["table"]]
    kernels = {k.split(" ", 1)[0] for k in tr.kernels}
    out.update(module=name, module_ms=module_ns / 1e6, executions=execs,
               execution_ms=[(e - s) / 1e6 for s, e in runs],
               executions_left_out=left_out,
               busy_ms=total(tr.busy(0)) / 1e6, events_ms=joined["total_ns"] / 1e6,
               tables=len(tables), rows=len(table))
    paths = {}
    for label, at in sorted(joined["paths"].items(), key=lambda kv: -kv[1]["ns"]):
        ops = sorted(at["ops"].items(), key=lambda kv: -kv[1][0])
        paths[label] = {
            "calls": at["calls"], "ms": at["ns"] / 1e6,
            "ms_per_execution": at["ns"] / 1e6 / execs,
            "share": 100.0 * at["ns"] / module_ns,
            "remat_ms": at["remat_ns"] / 1e6,
            "backward_ms": at["backward_ns"] / 1e6,
            "inherited_ms": at["inherited_ns"] / 1e6,
            "kernel_ms": sum(ns for k, (ns, _) in ops if k in kernels) / 1e6,
            "axes_ms": {a: ns / 1e6 for a, ns in at["axes"].items()},
            "collectives_ms": collectives(ops, table),
            "top_ops": [{"name": k, "ms": ns / 1e6, "calls": calls,
                         "opcode": table[k].opcode if k in table else None,
                         "op_name": table[k].op_name if k in table else None,
                         "inherited": k in table and table[k].inherited}
                        for k, (ns, calls) in ops[:TOP_OPS]]}
    out["paths"] = paths
    out["sum_share"] = sum(p["share"] for p in paths.values())
    out["inherited_share"] = 100.0 * profiler.scope_ns(
        joined, inherited=True) / module_ns
    return out


def show(rep: dict) -> None:
    if "paths" not in rep:
        print("== no device trace, or no table of the main module")
        return
    print(f"== {rep['workload']} seed {rep['seed']}: module {rep['module']}, "
          f"{rep['module_ms']:.1f} ms in the traced part over "
          f"{rep['executions']} whole executions of "
          f"{min(rep['execution_ms']):.2f} to {max(rep['execution_ms']):.2f}"
          f" ms ({rep['executions_left_out']} more held in part and left "
          f"out); {rep['rows']} rows in the table ({rep['tables']} under the "
          f"name); shares sum to {rep['sum_share']:.2f}%, of it inherited "
          f"{rep['inherited_share']:.2f}%")
    print(f"{'path':<34}{'ms/exec':>10}{'share%':>8}{'remat':>9}{'bwd':>9}"
          f"{'inherit':>9}{'kernel':>9}  axes")
    for label, p in rep["paths"].items():
        per = p["ms_per_execution"] / max(p["ms"], 1e-12)
        print(f"{label:<34}{p['ms_per_execution']:>10.3f}{p['share']:>8.2f}"
              f"{p['remat_ms'] * per:>9.3f}{p['backward_ms'] * per:>9.3f}"
              f"{p['inherited_ms'] * per:>9.3f}{p['kernel_ms'] * per:>9.3f}  "
              + " ".join(f"{a or '-'}:{ms * per:.3f}"
                         for a, ms in p["axes_ms"].items()))
        for op in p["top_ops"]:
            print(f"    {op['name']:<38}{op['ms'] * per:>9.3f}"
                  f"{op['calls'] / rep['executions']:>8.1f}x  "
                  f"{op['opcode']}  {'~' if op['inherited'] else ''}"
                  f"{op['op_name']}")
    for s in rep["table_spans"]:
        print(f"-- {s['span']} {s['s']:.2f} s: "
              + ", ".join(f"{k} {v}" for k, v in s.items()
                          if k not in ("span", "s")))
    print(f"-- host peak RSS {rep.get('host_peak_rss_kb_before', 0) / 1e6:.2f}"
          f" GB when the run ended, {rep['host_peak_rss_kb'] / 1e6:.2f} GB "
          f"after the table and the join")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--json", action="store_true",
                    help="print the numbers as one JSON object, last line")
    ap.add_argument("--out", help="also write the report to this file")
    args = ap.parse_args()

    from benchmarks import harness

    run = harness.start_run(args.workload, args.seed, args.seconds, True,
                            T_START)
    obs = run.cell.driver.run(run)
    rss = peak_rss_kb()             # before any text is read or joined
    print(json.dumps(harness.result_line(run, obs)), flush=True)
    rep = dict(report(run, obs), host_peak_rss_kb_before=rss)
    show(rep)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=1)
    if args.json:
        print(json.dumps({"scope_report": rep}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
