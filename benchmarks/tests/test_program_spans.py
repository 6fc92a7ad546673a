"""The readers of the program's span ring: the alignment with a trace on
synthetic spans, each reader on a hand-made ring, the report's nesting, the
new entries through ``harness.load_cell``, and one traced toy run."""

import json
import os
import time

import pytest

from benchmarks import harness, program_spans as ps, trace_reduce
from benchmarks.readers import (idle_unattributed_share, server_turn_ms,
                                span_stat)

from conftest import BENCH, ROOT

MS = 1_000_000
NEW = ["server_turn_ms", "server_reply_ms", "server_feed_ms",
       "queue_wait_p95_ms", "idle_unattributed_share.steady",
       "server_block_ms.batch"]


# ---------------------------------------------------------------------------
# the alignment


def calls(shift=0, extra_late=0, jitter=0):
    """``outer``: the trace's spans round the last 20 of 60 calls at
    exponential gaps (14 a second); ``inner``: the ring's span of every
    call, on a clock ``shift`` behind the trace's."""
    import numpy as np

    gaps = np.random.RandomState(7).exponential(71.0, 64 + extra_late)
    outer, inner, t = [], [], 5_000 * MS
    for k in range(60 + extra_late):
        t += int(gaps[k] * MS) + 1000
        if 40 <= k < 60:
            outer.append((t, 400_000))
        wobble = (k % 5 - 2) * jitter
        inner.append((t + 20_000 - shift + wobble, 300_000))
    return outer, inner


def test_align_finds_the_shift_between_the_clocks():
    outer, inner = calls(shift=1_790_000_000 * MS)
    assert ps.align(outer, inner) == 1_790_000_000 * MS - 20_000


def test_align_is_right_when_calls_began_after_the_trace_stopped():
    # the ring's tail is then off by one (or three) against the trace
    for late in (1, 3):
        outer, inner = calls(shift=123 * MS, extra_late=late)
        assert ps.align(outer, inner) == 123 * MS - 20_000


def test_a_pairing_off_by_one_is_refused_not_guessed():
    outer, inner = calls(shift=123 * MS)
    # the ring lost the last traced call: every pair is off by one
    assert ps.align(outer, inner[:-1]) is None
    assert ps.align(outer[:-1], inner[:-1]) == 123 * MS - 20_000
    assert ps.align(outer, inner[:10]) is None          # fewer than traced
    assert ps.align([], inner) is None


def test_align_refuses_spans_that_do_not_lie_inside_their_callers():
    outer, inner = calls(shift=0, jitter=300_000)       # 0.6 ms of wobble
    assert ps.align(outer, inner) is None
    outer, inner = calls(shift=0, jitter=20_000)
    assert ps.align(outer, inner) is not None


# ---------------------------------------------------------------------------
# a hand-made ring


class FakeRun:
    def __init__(self, cell="gpt2m-serve-steady"):
        self.cell = harness.load_cell(cell)
        self.t_start = time.perf_counter() - 40.0
        self.trace = True


def spec_of(name):
    return json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))


def turn(t0, dispatch, worker, dequeue, coalesce, merge, run, block, reply,
         between=0):
    """The spans of one dispatch from ``t0`` (ns); returns them and its end."""
    ids = {"dispatch": dispatch, "worker": worker}
    out, t = [], t0
    for name, dur in (("serving.dequeue", dequeue), ("serving.coalesce", coalesce),
                      ("serving.merge", merge), ("serving.run", run),
                      ("serving.block", block), ("serving.reply", reply)):
        out.append((name, t, dur, 100 + worker, dict(ids)))
        t += dur + between
    out.append(("serving.turn", t0, t - t0, 100 + worker,
                dict(ids, rows=13, bucket=16, requests=13)))
    return out, t


@pytest.fixture
def ring(monkeypatch):
    """Four dispatches of one worker that began inside a 30 s window which
    opened 10 s after the start, one that began before it and ended in
    it, and the requests' waits."""
    run = FakeRun()
    obs = harness.Observed(True, 1, 0, {"setup_s": 10.0, "window_s": 30.0})
    lo, _ = ps.window(run, obs)
    spans, t = [], lo - 900 * MS
    #            dequeue   coalesce merge    run      block      reply
    shapes = [(0.2 * MS, 1 * MS, 2 * MS, 3 * MS, 900 * MS, 10 * MS),   # set-up
              (0.1 * MS, 1 * MS, 2 * MS, 3 * MS, 916 * MS, 10 * MS),
              (0.1 * MS, 5 * MS, 2 * MS, 4 * MS, 916 * MS, 12 * MS),
              (40 * MS, 5 * MS, 2 * MS, 5 * MS, 916 * MS, 14 * MS),    # queue empty
              (0.1 * MS, 1 * MS, 4 * MS, 3 * MS, 918 * MS, 30 * MS)]
    for d, shape in enumerate(shapes):
        got, t = turn(t, d, 0, *(int(x) for x in shape))
        spans += got
    waits = [100, 200, 300, 400, 500, 600, 700, 800, 900, 1000, 1100, 1200,
             1300, 1400, 1500, 1600, 1700, 1800, 1900, 2000]
    for k, w in enumerate(waits):
        spans.append(("serving.queued", lo + (k + 1) * 50 * MS, w * MS, 7,
                      {"req": f"r{k}", "dispatch": 1 + k % 4}))
    spans.append(("serving.queued", lo - 500 * MS, 5_000 * MS, 7,
                  {"req": "before", "dispatch": 0}))
    monkeypatch.setattr(ps, "ring", lambda since_ns=0: sorted(
        spans, key=lambda s: s[1]))
    return run, obs


def test_span_stat_sums_per_dispatch_or_takes_spans_singly(ring):
    run, obs = ring
    # the five replies that began in the window: 10, 10, 12, 14, 30
    assert span_stat.read(run, obs, spec_of("server_reply_ms")) \
        == pytest.approx(12.0)
    assert span_stat.read(run, obs, spec_of("server_feed_ms")) \
        == pytest.approx(6.5)                   # four began inside: 5, 6, 7, 7
    # 95th percentile of the twenty waits that began in the window
    assert span_stat.read(run, obs, spec_of("queue_wait_p95_ms")) \
        == pytest.approx(1905.0)
    assert span_stat.read(FakeRun("gpt2m-serve-batch"), obs,
                          spec_of("server_block_ms.batch")) \
        == pytest.approx(916.0)


def test_server_turn_is_block_to_block_over_turns_that_found_a_backlog(ring):
    run, obs = ring
    spec = spec_of("server_turn_ms")
    # reply of the one before + dequeue, coalesce, merge, run of this one;
    # the turn that waited 40 ms for a request is left out
    # and the one whose first block began before the window
    by_hand = [10 + 0.1 + 5 + 2 + 4, 14 + 0.1 + 1 + 4 + 3]
    spans = ps.started_in(ps.ring(), ps.window(run, obs))
    assert sorted(server_turn_ms.turns_ms(spans, 1.0)) \
        == pytest.approx(sorted(by_hand))
    assert server_turn_ms.read(run, obs, spec) == pytest.approx(21.6)
    # with the limit off, the empty queue's wait would count
    assert max(server_turn_ms.turns_ms(spans, 1e9)) \
        == pytest.approx(12 + 40 + 5 + 2 + 5)
    # another server of the process numbers its dispatches from 1 too:
    # what began outside the window is not read
    assert len(server_turn_ms.turns_ms(ps.ring(), 1.0)) == 3


def test_readers_return_nothing_without_a_ring_or_a_window(monkeypatch):
    run = FakeRun()
    obs = harness.Observed(True, 1, 0, {"setup_s": 10.0, "window_s": 30.0})
    monkeypatch.setattr(ps, "ring", lambda since_ns=0: [])
    for name in NEW:
        spec = spec_of(name)
        reader = harness.load_module("readers", spec["reader"])
        assert reader.read(run, obs, spec) is None
        assert reader.read(run, harness.Observed(True, 1, 0, {}), spec) is None


def test_a_program_without_the_ring_reads_as_empty(monkeypatch):
    from paddle_tpu.core import profiler

    monkeypatch.delattr(profiler, "spans")      # the parent of the PR
    assert ps.ring() == []


def test_idle_time_goes_under_the_deepest_program_span(ring, monkeypatch):
    run, obs = ring
    spans = ps.ring()
    shift = -spans[0][1] + 1_000               # the trace's clock starts near 0
    at = {(s[0], s[4].get("dispatch")): s[1] + shift for s in spans}
    b2, b3 = at["serving.block", 2], at["serving.block", 3]
    # busy: dispatch 2's block but its last 2 ms, then from dispatch 3's
    # block on; idle in between: the end of a block, a reply, and the next
    # turn's dequeue (a 40 ms wait for a request), coalesce, merge and run.
    # One more gap of 3 ms far from any span.
    far = b3 + 5_000 * MS
    ops = [("a", b2, 914 * MS), ("b", b3, far - b3)]
    window = (b2, far + 3 * MS)
    trace = trace_reduce.Trace({0: ops}, {}, [], [], window)
    idle = ps.idle_by_program_span(trace, spans, shift, skip=["serving.queued"])
    assert {k: round(v * 1e3, 3) for k, v in idle.items()} == {
        "serving.block": 2.0, "serving.reply": 12.0, "serving.dequeue": 40.0,
        "serving.coalesce": 5.0, "serving.merge": 2.0, "serving.run": 5.0,
        "(no span)": 3.0}
    # a wait is no one's work: were it not skipped, a request that waited
    # 10 ms inside that dequeue would take its time
    wait = ("serving.queued", at["serving.dequeue", 3] - shift + 20 * MS,
            10 * MS, 7, {"req": "short", "dispatch": 3})
    assert ps.idle_by_program_span(trace, spans + [wait], shift)[
        "serving.queued"] == pytest.approx(0.010)
    assert ps.idle_by_program_span(trace, spans + [wait], shift,
                                   skip=["serving.queued"]) == idle

    # the reader: aligned through bench.submit / serving.submit
    submits = [("serving.submit", spans[0][1] + k * 37 * MS + 500_000, 200_000,
                7, {"req": f"s{k}"}) for k in range(30)]
    host = [("submit", s[1] + shift - 30_000, 300_000) for s in submits[-12:]]
    monkeypatch.setattr(ps, "ring", lambda since_ns=0: sorted(
        spans + submits, key=lambda s: s[1]))
    obs.trace = trace_reduce.Trace({0: ops}, {}, [], host, window)
    spec = spec_of("idle_unattributed_share.steady")
    assert idle_unattributed_share.read(run, obs, spec) \
        == pytest.approx(100 * 3.0 / 69.0, rel=1e-3)
    # time under the umbrella alone counts as unattributed too
    spec["args"]["umbrella"] = ["serving.turn", "serving.dequeue"]
    assert idle_unattributed_share.read(run, obs, spec) \
        == pytest.approx(100 * 43.0 / 69.0, rel=1e-3)
    # no alignment, no number
    obs.trace = trace_reduce.Trace({0: ops}, {}, [], host[:3] + [
        ("submit", host[5][1] + 9 * MS, 300_000)], window)
    assert idle_unattributed_share.read(run, obs, spec) is None
    # no device operations (a CPU trace), no number
    obs.trace = trace_reduce.Trace({}, {}, [], host, window)
    assert idle_unattributed_share.read(run, obs, spec) is None


def test_deepest_cover_cuts_at_span_boundaries():
    cover = ps.deepest_cover([("turn", 0, 100), ("merge", 10, 20),
                              ("run", 20, 50), ("submit", 30, 35),
                              ("late", 120, 130)])
    assert cover == [(0, 10, "turn"), (10, 20, "merge"), (20, 30, "run"),
                     (30, 35, "submit"), (35, 50, "run"), (50, 100, "turn"),
                     (120, 130, "late")]


def test_nesting_and_self_time():
    spans = [("outer", 0, 100 * MS, 1, {}), ("a", 10 * MS, 30 * MS, 1, {}),
             ("a.1", 12 * MS, 5 * MS, 1, {}), ("b", 50 * MS, 50 * MS + 20_000, 1, {}),
             ("other", 20 * MS, 10 * MS, 2, {}),          # another thread
             ("straddles", 90 * MS, 30 * MS, 1, {})]      # overlaps, not inside
    par = ps.parents(spans)
    assert [None if p is None else spans[p][0] for p in par] \
        == [None, "outer", "a", "outer", None, None]
    rows = ps.totals(spans)
    assert rows["outer"]["self_s"] == pytest.approx(0.02 - 20e-6)
    assert rows["a"]["self_s"] == pytest.approx(0.025)
    assert rows["other"] == {"count": 1, "total_s": 0.01, "self_s": 0.01,
                             "max_s": 0.01}


# ---------------------------------------------------------------------------
# the entries, and a toy run


def test_the_new_entries_resolve_and_say_where_they_read():
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [m["name"] for m in doc["per_layer"]][-6:] == NEW
    steady = harness.load_cell("gpt2m-serve-steady")
    batch = harness.load_cell("gpt2m-serve-batch")
    by = {m["name"]: m for m in steady.per_layer + batch.per_layer}
    assert set(NEW) <= set(by)
    assert [n for n in NEW if n in {m["name"] for m in batch.per_layer}] \
        == ["server_block_ms.batch"]
    for name in NEW:
        m = by[name]
        assert m["source"] in ("program_span", "device_trace")
        assert m["moves"] in (steady if name != NEW[-1] else batch).end_to_end
        assert hasattr(harness.load_module("readers", m["reader"]), "read")
    for cell in ("gpt2m-train-s1024", "gpt2l-train-dp2tp2"):
        assert not set(NEW) & {m["name"] for m in harness.load_cell(cell).per_layer}


def test_a_traced_toy_run_reports_the_span_metrics(tiny_root):
    from benchmarks.tools import span_report

    run = harness.start_run("tiny-serve-open", 5, 3.0, True, time.perf_counter(),
                            root=tiny_root, allow_cpu=True)
    obs = run.cell.driver.run(run)
    line = harness.result_line(run, obs)
    assert line["correct"] is True, line["notes"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"server_reply_ms", "server_feed_ms", "queue_wait_p95_ms"} \
        <= set(metrics)
    assert all(metrics[k] > 0 for k in ("server_reply_ms", "server_feed_ms"))
    # no device plane in a CPU trace: nothing to attribute
    assert "idle_unattributed_share.steady" not in metrics
    # the waits the ring holds are the ones the server counted
    waits = [s for s in ps.started_in(ps.ring(), ps.window(run, obs))
             if s[0] == "serving.queued"]
    assert len(waits) == line["attempted"]

    rep = span_report.report(run, obs)
    json.dumps(rep)
    top = rep["setup"]["top_level"]
    assert {"io.save_inference_model", "io.load_inference_model",
            "serving.warmup"} <= set(top)
    assert 0 <= rep["setup"]["outside_program_s"] < rep["setup_s"]
    assert rep["setup"]["covered_s"] + rep["setup"]["outside_program_s"] \
        == pytest.approx(rep["setup_s"])
    assert any("jax.compile" in row
               for row in rep["setup"]["compile_by_parent"].values())
    assert "io.aot_compile" in rep["setup"]["compile_by_parent"]
    assert {"serving.turn", "serving.block", "serving.submit"} \
        <= set(rep["window"]["spans"])
    assert rep["window"]["compiles"] == []
    span_report.show(rep)
