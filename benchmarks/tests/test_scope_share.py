"""The ``scope_time_share`` reader on a recorded trace with a recorded
table (``data/tiny-scopes.json``, summed by hand), the report's rows from
the same join, and the seventeen entries through ``harness.load_cell``."""

import json
import os

import pytest

from benchmarks import harness, trace_reduce
from benchmarks.readers import scope_time_share
from benchmarks.tools import scope_report

from conftest import BENCH, ROOT

DATA = json.load(open(os.path.join(BENCH, "tests", "data", "tiny-scopes.json")))

ENTRIES = {
    "remat_time_share": ["gpt2m-train-s1024", "gpt2l-train-dp2tp2"],
    "attn_time_share": ["gpt2m-train-s1024", "gpt2l-train-dp2tp2"],
    "ce_time_share": ["gpt2m-train-s1024", "gpt2l-train-dp2tp2"],
    "optimizer_time_share": ["gpt2m-train-s1024", "gpt2l-train-dp2tp2"],
    "unscoped_time_share.train": ["gpt2m-train-s1024", "gpt2l-train-dp2tp2"],
    "dp_exchange_time_share": ["gpt2l-train-dp2tp2"],
    "prefill_time_share.batch": ["gpt2m-serve-batch"],
    "unscoped_time_share.batch": ["gpt2m-serve-batch"],
    "mla_time_share.k25": ["k25-serve-batch"],
    "moe_time_share.k25": ["k25-serve-batch"],
    "unscoped_time_share.k25": ["k25-serve-batch"],
    "scorer_time_share.sala": ["sala-serve-long"],
    "unscoped_time_share.sala": ["sala-serve-long"],
    "inherited_time_share.train": ["gpt2m-train-s1024", "gpt2l-train-dp2tp2"],
    "inherited_time_share.batch": ["gpt2m-serve-batch"],
    "inherited_time_share.k25": ["k25-serve-batch"],
    "inherited_time_share.sala": ["sala-serve-long"],
}


@pytest.fixture
def program():
    """The recorded table, registered as the program's for its module."""
    from paddle_tpu.core import profiler
    from paddle_tpu.profiling.fusion import ScopeRow

    table = {k: ScopeRow(tuple(v[0]), *v[1:]) for k, v in DATA["table"].items()}
    profiler._programs.clear()
    profiler._programs.append([DATA["module"], None, None, (), table])
    yield table
    profiler._programs.clear()


def observed():
    return harness.Observed(True, 2, 0, {}, trace=trace_reduce.Trace.from_json(
        DATA["trace"]))


def share(args, obs=None):
    return scope_time_share.read(None, obs or observed(), {"args": args})


@pytest.mark.parametrize("args, expected", [
    ({"scopes": ["attn"]}, "scopes_attn"),
    ({"scopes": ["ce", "head"]}, "scopes_ce_head"),
    ({"scopes": ["gpt"]}, "scopes_gpt"),
    ({"remat": True}, "remat"),
    ({"scopes": ["attn"], "remat": True}, "attn_remat"),
    ({"axes": "dp"}, "axes_dp"),
    ({"axes": "tp"}, "axes_tp"),
    ({"unscoped": True}, "unscoped"),
    ({"inherited": True}, "inherited"),
    ({"scopes": ["attn"], "inherited": True}, "attn_inherited"),
])
def test_each_args_form_against_the_hand_sum(program, args, expected):
    assert share(args) == pytest.approx(DATA["expected"]["share"][expected])


def test_the_denominator_is_the_main_modules_whole_executions(program):
    obs = observed()
    joined, module_ns, runs, left_out = scope_time_share.joined(obs)
    want = DATA["expected"]
    assert (module_ns, len(runs)) == (want["module_ns"], want["executions"])
    assert left_out == 1            # the first, begun before the traced part
    assert joined["total_ns"] == want["events_ns"]
    assert {k: v["ns"] for k, v in joined["paths"].items()} == want["self_ns"]
    # neither the other module's fusion.1 nor the cut execution's
    assert joined["paths"]["gpt/attn"]["ops"]["fusion.1"] == (2000, 1)
    # the paths partition the module's operations; remat cuts across
    assert sum(share({"scopes": [s]}, obs) for s in ("gpt", "optimizer")) \
        + share({"unscoped": True}, obs) == pytest.approx(
            100.0 * want["events_ns"] / want["module_ns"])
    # trace_reduce's own self times, an independent sum, agree
    lo, hi = obs.trace.window
    own = trace_reduce.self_times(
        [e for e in obs.trace.ops[0] if 12000 <= e[1] < 20000])
    assert sum(own.values()) == want["events_ns"]
    # the join is made once a run
    assert scope_time_share.joined(obs) is obs._scope_join


def test_a_trace_without_a_whole_execution_is_read_over_its_parts(program):
    want = DATA["expected_parts"]
    obs = observed()
    obs.trace.window = tuple(want["window"])
    joined, module_ns, runs, left_out = scope_time_share.joined(obs)
    assert (module_ns, len(runs), left_out) == (
        want["module_ns"], want["executions"], 0)
    assert joined["total_ns"] == want["events_ns"]
    assert share({"scopes": ["attn"]}, obs) == pytest.approx(
        want["share"]["scopes_attn"])
    assert share({"unscoped": True}, obs) == pytest.approx(
        want["share"]["unscoped"])


def test_a_shorter_whole_execution_counts_like_a_longer_one(program):
    """Fewer new tokens or a smaller batch make an execution shorter, not
    less whole: every whole one is in both sums, weighed by its time."""
    short = observed()
    short.trace.modules[0].append(("jit_train_step(1)", 20100, 800))
    short.trace.ops[0].append(("fusion.1 [fusion]", 20100, 800))
    joined, module_ns, runs, left_out = scope_time_share.joined(short)
    assert (module_ns, runs, left_out) == (
        8800, [(12000, 20000), (20100, 20900)], 1)
    assert share({"scopes": ["attn"]}, short) == pytest.approx(
        100 * (3050 + 800) / 8800)


def test_the_execution_that_the_records_end_cut_is_left_out(program):
    """A long trace's device record can end inside the traced part: the
    execution in flight then shows whole, its event ending where the
    record does (PERF.md §7). That one is told by it and left out."""
    cut = observed()
    cut.trace.modules[0][-1:] = [("jit_train_step(1)", 20100, 800)]
    cut.trace.ops[0][-1:] = [("fusion.1 [fusion]", 20100, 800)]
    joined, module_ns, runs, left_out = scope_time_share.joined(cut)
    assert (module_ns, runs, left_out) == (8000, [(12000, 20000)], 2)
    assert share({"scopes": ["attn"]}, cut) == pytest.approx(
        DATA["expected"]["share"]["scopes_attn"])


def test_nothing_without_a_table_a_trace_or_a_device_plane(program):
    from paddle_tpu.core import profiler

    no_ops = observed()
    no_ops.trace.ops = {}
    assert share({"unscoped": True}, no_ops) is None
    assert share({"unscoped": True}, harness.Observed(True, 0, 0, {})) is None
    profiler._programs.clear()
    assert share({"unscoped": True}) is None
    # a table of another module is not this one's
    profiler._programs.append(["jit_call", None, None, (), program])
    assert share({"scopes": ["attn"]}) is None


def test_the_report_prints_the_same_join(program, capsys):
    cell = harness.load_cell("gpt2m-train-s1024")
    run = harness.Run(cell=cell, seed=3, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None)
    rep = scope_report.report(run, observed())
    assert rep["module"] == "jit_train_step(1)" and rep["rows"] == len(program)
    assert rep["sum_share"] == pytest.approx(100 * 7950 / 8000)
    assert rep["inherited_share"] == pytest.approx(100 * 50 / 8000)
    assert (rep["execution_ms"], rep["executions_left_out"]) == ([8e-3], 1)
    attn = rep["paths"]["gpt/attn"]
    assert attn["share"] == pytest.approx(100 * 3050 / 8000)
    assert attn["inherited_ms"] == pytest.approx(50 / 1e6)
    assert [o["inherited"] for o in attn["top_ops"]] == [False, False, True]
    assert attn["kernel_ms"] == pytest.approx(1000 / 1e6)
    assert attn["remat_ms"] == pytest.approx(1000 / 1e6)
    assert attn["top_ops"][0]["name"] == "fusion.1"
    assert attn["top_ops"][0]["calls"] == 1
    assert attn["top_ops"][0]["op_name"].endswith("attn/dot_general")
    assert rep["paths"]["gpt"]["collectives_ms"] == {
        "all-reduce dp": pytest.approx(500 / 1e6)}
    assert rep["paths"]["gpt/ffn"]["collectives_ms"] == {
        "collective-permute-done tp": pytest.approx(300 / 1e6)}
    assert rep["paths"]["(not in table)"]["top_ops"][0]["op_name"] is None
    assert rep["executions"] == 1 and rep["module_ms"] == 8000 / 1e6
    scope_report.show(rep)
    out = capsys.readouterr().out
    assert "gpt/attn" in out and "(not in table)" in out
    assert "copy  ~jit(train_step)" in out
    json.dumps(rep)


def test_the_seventeen_entries_resolve_and_equal_their_files():
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    tail = doc["per_layer"][-len(ENTRIES):]
    assert [m["name"] for m in tail] == list(ENTRIES)      # appended, in order
    for m in tail:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        assert spec["reader"] == "scope_time_share" and spec["doc"]
        assert {k: spec[k] for k in m if k != "name"} == \
            {k: v for k, v in m.items() if k != "name"}
        assert m["workloads"] == ENTRIES[m["name"]]
        assert (m["unit"], m["better"], m["source"]) == ("%", "lower",
                                                         "device_trace")
        assert set(spec["args"]) <= {"scopes", "remat", "axes", "unscoped",
                                     "inherited"}
    for cell_name in {c for cells in ENTRIES.values() for c in cells}:
        cell = harness.load_cell(cell_name)
        mine = [s["name"] for s in cell.per_layer
                if s["reader"] == "scope_time_share"]
        assert mine == [n for n, cells in ENTRIES.items() if cell_name in cells]
        for s in cell.per_layer:
            if s["reader"] == "scope_time_share":
                assert s["moves"] in cell.end_to_end
