"""Unified-telemetry suite (paddle_tpu.telemetry + the instrumentation
wired through executor/fit, data/feeder, serving, async_ps, resilience).

The acceptance contracts, all CPU + deterministic:

  * the process registry walks clean under the
    ``paddle_tpu_<subsystem>_<name>{labels}`` naming convention after a
    short train + serve run (the tier-1 CI contract);
  * ``GET /metrics`` on a live PredictorServer under load returns
    valid Prometheus text whose queue/latency/reject series agree with
    ``ServingMetrics.report()``;
  * one serving request's span id appears in journal events from
    submit through worker dispatch to completion; one training chunk's
    span is shared by its feeder fill and its dispatch;
  * a SIGTERM preemption's flight dump contains the last guard
    incident and checkpoint event; a watchdog kill-drill dumps with
    the hang's span id and ``tools/flight_dump.py`` renders it;
  * journal + registry accounting stays under 2% of a K=16 fused
    dispatch (direct-cost pin, like the PR-6 StepTimer contract).
"""

import gc
import io as _stdio
import json
import os
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import layers as L
from paddle_tpu import optimizer as opt
from paddle_tpu import resilience, serving, telemetry
from paddle_tpu.telemetry import (FlightRecorder, MetricsRegistry,
                                  RunJournal, counter_deltas)
from paddle_tpu.telemetry.registry import counter_family, gauge_family
from paddle_tpu.testing import faults

DIM, CLASSES, BS, N_BATCHES = 6, 4, 4, 8


def _net(x, label):
    h = L.fc(x, 16, name="fc1")
    logits = L.fc(h, CLASSES, name="fc2")
    return {"loss": L.mean(L.softmax_with_cross_entropy(logits, label))}


_PROG = pt.build(_net)
_FEED = {"x": np.zeros((BS, DIM), np.float32),
         "label": np.zeros((BS, 1), np.int64)}


def _trainer(guard=None):
    tr = pt.Trainer(_PROG, opt.SGD(0.1), loss_name="loss", guard=guard)
    tr.startup(sample_feed=_FEED)
    return tr


def _reader(n_batches=N_BATCHES, seed=7):
    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(n_batches):
            x = rng.randn(BS, DIM).astype(np.float32)
            y = rng.randint(0, CLASSES, (BS,)).astype(np.int64)
            yield [(x[j], y[j:j + 1]) for j in range(BS)]
    return reader


def _fit(tr, cfg=None, epochs=1, handler=None, **kw):
    return pt.fit(tr, _reader(), num_epochs=epochs,
                  feed_names=["x", "label"], dtypes=["float32", "int64"],
                  checkpoint_config=cfg, event_handler=handler, **kw)


@pytest.fixture()
def fresh_telemetry(tmp_path):
    """A fresh process journal + a flight root under tmp_path, so span
    assertions see only this test's events and dumps land where the
    test can find them. The (shared) registry is left alone — its
    naming contract must hold cumulatively anyway."""
    old = telemetry.set_journal(RunJournal())
    rec = telemetry.get_recorder()
    old_root = rec.root
    rec.set_root(str(tmp_path / "flight"))
    try:
        yield telemetry.get_journal()
    finally:
        rec.set_root(old_root)
        j = telemetry.set_journal(old)
        if j is not None:
            j.close()


def _flight_dirs(tmp_path):
    root = tmp_path / "flight"
    if not root.is_dir():
        return []
    return sorted(p for p in root.iterdir()
                  if p.name.startswith("flight_") and ".tmp." not in p.name)


def _parse_prometheus(text):
    """Minimal exposition-format parser: {series_with_labels: value},
    plus per-family TYPE/HELP — raises on malformed lines, which IS
    the 'valid Prometheus text' assertion."""
    series, types, helps = {}, {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            _, _, name, h = line.split(" ", 3)
            helps[name] = h
        elif line.startswith("# TYPE "):
            _, _, name, t = line.split(" ", 3)
            assert t in ("counter", "gauge", "histogram"), line
            types[name] = t
        else:
            assert not line.startswith("#"), line
            key, val = line.rsplit(" ", 1)
            assert key not in series, f"duplicate series {key}"
            series[key] = float(val)
    for name in types:
        assert name in helps and helps[name].strip(), name
    return series, types, helps


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------


def test_metric_naming_convention_enforced():
    r = MetricsRegistry()
    with pytest.raises(ValueError, match="convention"):
        r.counter("requests_total", "h")
    with pytest.raises(ValueError, match="convention"):
        r.counter("paddle_tpu_BadCase_total", "h")
    with pytest.raises(ValueError, match="_total"):
        r.counter("paddle_tpu_serving_requests", "h")
    with pytest.raises(ValueError, match="_total"):
        r.gauge("paddle_tpu_serving_depth_total", "h")
    with pytest.raises(ValueError, match="help"):
        r.counter("paddle_tpu_x_y_total", "  ")
    with pytest.raises(ValueError, match="label"):
        r.counter("paddle_tpu_x_y_total", "h", ("Bad-Label",))
    # re-registration with a different labelset is a hard error
    r.counter("paddle_tpu_x_a_total", "h", ("k",))
    with pytest.raises(ValueError, match="re-registered"):
        r.counter("paddle_tpu_x_a_total", "h", ("other",))


def test_counter_gauge_histogram_render_and_values():
    r = MetricsRegistry()
    c = r.counter("paddle_tpu_t_reqs_total", "requests", ("kind",))
    c.inc(kind="a")
    c.inc(2, kind="b")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, kind="a")
    g = r.gauge("paddle_tpu_t_depth", "depth")
    g.set(3)
    h = r.histogram("paddle_tpu_t_lat_seconds", "latency",
                    bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    series, types, _ = _parse_prometheus(r.render_prometheus())
    assert series['paddle_tpu_t_reqs_total{kind="a"}'] == 1
    assert series['paddle_tpu_t_reqs_total{kind="b"}'] == 2
    assert series["paddle_tpu_t_depth"] == 3
    # histogram: cumulative _bucket series + _sum + _count
    assert series['paddle_tpu_t_lat_seconds_bucket{le="0.1"}'] == 1
    assert series['paddle_tpu_t_lat_seconds_bucket{le="1"}'] == 2
    assert series['paddle_tpu_t_lat_seconds_bucket{le="+Inf"}'] == 3
    assert series["paddle_tpu_t_lat_seconds_count"] == 3
    assert abs(series["paddle_tpu_t_lat_seconds_sum"] - 5.55) < 1e-9
    assert types["paddle_tpu_t_lat_seconds"] == "histogram"
    assert r.validate() == []
    # JSON exporter carries the same snapshot
    snap = json.loads(r.render_json())
    assert snap["paddle_tpu_t_depth"]["samples"][0]["value"] == 3


def test_collector_merge_instance_labels_and_weakref_cleanup():
    r = MetricsRegistry()

    class Owner:
        pass

    owners = [Owner(), Owner()]
    for i, o in enumerate(owners):
        # with an owner, the registry hands the LIVE owner back as the
        # callback's argument — no hand-rolled weakref dance needed
        r.add_collector(
            (lambda owner, i=i: [counter_family(
                "paddle_tpu_t_work_total", "work",
                [({"inst": str(i)}, 10 * (i + 1))])]), owner=o)
    del o  # the loop variable must not keep the last owner alive
    series, _, _ = _parse_prometheus(r.render_prometheus())
    assert series['paddle_tpu_t_work_total{inst="0"}'] == 10
    assert series['paddle_tpu_t_work_total{inst="1"}'] == 20
    assert r.validate() == []
    # a collected owner's series drop out of the next scrape
    owners.pop()
    gc.collect()
    series, _, _ = _parse_prometheus(r.render_prometheus())
    assert 'paddle_tpu_t_work_total{inst="1"}' not in series
    assert 'paddle_tpu_t_work_total{inst="0"}' in series


def test_validate_flags_collector_violations():
    r = MetricsRegistry()

    class Keep:
        pass

    keep = Keep()
    r.add_collector(lambda owner: [
        counter_family("bad_name_total", "h", [({}, 1)]),
        counter_family("paddle_tpu_x_nototal", "h", [({}, 1)]),
        gauge_family("paddle_tpu_x_dup", "h", [({}, 1)]),
        gauge_family("paddle_tpu_x_dup", "h", [({}, 2)]),  # dup series
        counter_family("paddle_tpu_x_nohelp_total", "", [({}, 1)]),
    ], owner=keep)
    v = "\n".join(r.validate())
    assert "bad_name_total" in v and "convention" in v
    assert "paddle_tpu_x_nototal" in v
    assert "duplicate series paddle_tpu_x_dup" in v
    assert "missing help" in v


def test_validate_flags_cross_publisher_type_conflict():
    """Two publishers declaring the same family with different
    types/help: the merged TYPE line is wrong for one of them —
    validate() must say so instead of shipping the conflict."""
    r = MetricsRegistry()
    r.add_collector(lambda: [gauge_family("paddle_tpu_x_thing", "a",
                                          [({"inst": "0"}, 1)])])
    r.add_collector(lambda: [counter_family("paddle_tpu_x_thing", "b",
                                            [({"inst": "1"}, 2)])])
    v = "\n".join(r.validate())
    assert "paddle_tpu_x_thing" in v and "declared as" in v


def test_server_close_removes_collector(fresh_telemetry, pred):
    """A closed-but-referenced PredictorServer must stop exporting
    live-looking queue/worker gauges."""
    srv = serving.PredictorServer(pred, workers=1, queue_size=4)
    inst = srv.telemetry_inst
    series, _, _ = _parse_prometheus(
        telemetry.get_registry().render_prometheus())
    assert f'paddle_tpu_serving_queue_depth{{inst="{inst}"}}' in series
    srv.close()
    series, _, _ = _parse_prometheus(
        telemetry.get_registry().render_prometheus())
    assert f'paddle_tpu_serving_queue_depth{{inst="{inst}"}}' not in series


def test_broken_collector_isolated_not_scrape_poison():
    """One broken collector must not take down the process-wide
    scrape: its failure becomes a validate() violation and every
    other family still exports."""
    r = MetricsRegistry()
    r.counter("paddle_tpu_t_ok_total", "fine").inc()

    def boom():
        raise RuntimeError("half-constructed owner")

    r.add_collector(boom)
    series, _, _ = _parse_prometheus(r.render_prometheus())
    assert series["paddle_tpu_t_ok_total"] == 1
    v = "\n".join(r.validate())
    assert "half-constructed owner" in v and "RuntimeError" in v


def test_counter_deltas_shape():
    before = {"a": 1.0}
    after = {"a": 5.0, "b": 2.0, "c": 0.0}
    assert counter_deltas(before, after, per=2) == {"a": 2.0, "b": 1.0}


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------


def test_journal_run_id_monotonic_seq_and_span_filter():
    j = RunJournal(ring_size=100)
    s1, s2 = j.new_span(), j.new_span()
    assert s1 != s2 and len(s1) == 16
    j.emit("a.one", span=s1, x=1)
    j.emit("a.two", span=s2)
    j.emit("b.one", span=s1)
    events = j.recent()
    assert [e["seq"] for e in events] == [1, 2, 3]
    assert all(e["run"] == j.run_id for e in events)
    assert [e["kind"] for e in j.recent(span=s1)] == ["a.one", "b.one"]
    assert [e["kind"] for e in j.recent(kind="a.")] == ["a.one", "a.two"]
    assert [e["kind"] for e in j.recent(n=1)] == ["b.one"]


def test_journal_ring_bounded_and_file_sink(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    j = RunJournal(ring_size=8)
    j.open(path)
    for i in range(20):
        j.emit("tick", i=i)
    j.close()
    assert len(j.recent()) == 8               # ring holds the tail
    assert j.recent()[0]["i"] == 12
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert len(lines) == 20                   # the sink got everything
    assert [e["seq"] for e in lines] == list(range(1, 21))
    # unserializable payloads degrade per-event, never raise
    j2 = RunJournal()
    j2.open(str(tmp_path / "j2.jsonl"))
    j2.emit("weird", obj=object())
    j2.close()
    assert json.loads(open(str(tmp_path / "j2.jsonl")).read())


def test_journal_sink_safe_under_concurrent_emitters(tmp_path):
    """Serving workers, the watchdog, the fill thread, and the
    training loop all emit concurrently: the JSONL sink must hold
    intact lines in strict seq order (the write happens under the
    journal lock), never interleaved bytes."""
    path = str(tmp_path / "concurrent.jsonl")
    j = RunJournal(ring_size=16)
    j.open(path)
    n_threads, per = 4, 200

    def worker(t):
        for i in range(per):
            j.emit("tick", thread=t, i=i, pad="x" * 64)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    j.close()
    lines = [json.loads(line) for line in open(path)]  # every line parses
    seqs = [e["seq"] for e in lines]
    assert seqs == list(range(1, n_threads * per + 1))


def test_journal_sampling_deterministic_and_span_consistent():
    """The high-QPS pressure valve: per-kind sampling is keyed on the
    span's hash, so one request's submit→dispatch→complete events share
    a fate (a sampled-in submit KEEPS its lifecycle), the same traffic
    journals the same events every run (no random), unconfigured kinds
    always keep, and dropped events still consume a seq."""
    j = RunJournal(sample={"serving": 0.5})
    spans = [j.new_span() for _ in range(40)]
    for s in spans:
        j.emit("serving.submit", span=s)
        j.emit("serving.dispatch", span=s)
        j.emit("serving.complete", span=s)
        j.emit("trainer.dispatch", span=s)     # unconfigured: always kept
    events = j.recent()
    per_span = {}
    for e in events:
        per_span.setdefault(e["span"], []).append(e["kind"])
    kept = {s for s, ks in per_span.items()
            if any(k.startswith("serving.") for k in ks)}
    assert 0 < len(kept) < 40                  # some sampled out
    for s in kept:                             # span-consistent: all 3
        assert [k for k in per_span[s] if k.startswith("serving.")] == \
            ["serving.submit", "serving.dispatch", "serving.complete"]
    assert all("trainer.dispatch" in ks for ks in per_span.values())
    assert j.dropped_sampled == 3 * (40 - len(kept))
    # dropped events consume seqs: the last seq counts every emit
    assert j.seq == 4 * 40
    # deterministic: a fresh journal with the same spans keeps the same
    j2 = RunJournal(sample={"serving": 0.5})
    for s in spans:
        j2.emit("serving.submit", span=s)
    assert {e["span"] for e in j2.recent()} == kept
    # rate 0/1 edges + longest-prefix matching + the catch-all
    assert j.sample_rate("serving.submit") == 0.5
    j.set_sample({"serving": 0.0, "serving.hang": 1.0, "*": 0.25})
    assert j.sample_rate("serving.hang") == 1.0     # exact beats prefix
    assert j.sample_rate("serving.submit") == 0.0
    assert j.sample_rate("ps.push") == 0.25         # catch-all
    before = len(j.recent())
    j.emit("serving.submit", span=j.new_span())
    assert len(j.recent()) == before                # rate 0 drops
    j.emit("serving.hang", span=j.new_span())
    assert j.recent()[-1]["kind"] == "serving.hang"  # rate 1 keeps


def test_journal_sampling_env_knob(monkeypatch):
    from paddle_tpu.telemetry.journal import parse_sample

    assert parse_sample("serving=0.01, ps=0.5") == \
        {"serving": 0.01, "ps": 0.5}
    # malformed entries are skipped, rates clamp to [0, 1]
    assert parse_sample("bad, x=zz, y=3.0, z=-1") == {"y": 1.0, "z": 0.0}
    assert parse_sample(None) == {} and parse_sample("") == {}
    # the process journal honors PDTPU_JOURNAL_SAMPLE at creation
    monkeypatch.setenv("PDTPU_JOURNAL_SAMPLE", "serving=0.0")
    old = telemetry.set_journal(None)
    try:
        j = telemetry.get_journal()
        j.emit("serving.submit", span=j.new_span())
        j.emit("other.kind")
        assert [e["kind"] for e in j.recent()] == ["other.kind"]
        assert j.dropped_sampled == 1
    finally:
        telemetry.set_journal(old)


# ---------------------------------------------------------------------------
# flight recorder + dump tool
# ---------------------------------------------------------------------------


def test_flight_dump_committed_validated_and_rotated(tmp_path):
    j = RunJournal(ring_size=64)
    span = j.new_span()
    j.emit("x.boom", span=span, detail="d")
    rec = FlightRecorder(journal=j, root=str(tmp_path), max_dumps=2)
    p1 = rec.dump("unit", detail={"k": 1}, span=span)
    assert os.path.isdir(p1) and ".tmp." not in p1
    resilience.validate_checkpoint(p1)        # CRC-manifested like a ckpt
    meta = json.load(open(os.path.join(p1, "flight.json")))
    assert meta["trigger"] == "unit" and meta["span"] == span
    assert meta["run"] == j.run_id and meta["num_events"] == 1
    assert "metrics" in meta                  # registry snapshot rides along
    events = [json.loads(line)
              for line in open(os.path.join(p1, "events.jsonl"))]
    assert events[0]["kind"] == "x.boom" and events[0]["span"] == span
    # rotation: oldest dump beyond max_dumps is removed
    for i in range(3):
        j.emit("more", i=i)
        rec.dump(f"t{i}")
    dumps = [d for d in os.listdir(tmp_path) if d.startswith("flight_")]
    assert len(dumps) == 2
    assert not any(p1.endswith(d) for d in dumps)


def test_flight_dump_tool_renders_filters_and_validates(tmp_path):
    import importlib
    flight_dump_tool = importlib.import_module("tools.flight_dump")

    j = RunJournal()
    span = j.new_span()
    j.emit("serving.submit", span=span, n=4)
    j.emit("serving.hang", span=span, worker=0)
    j.emit("other.noise", span=j.new_span())
    rec = FlightRecorder(journal=j, root=str(tmp_path))
    p = rec.dump("worker_hung", span=span, detail={"worker": 0})

    meta, events = flight_dump_tool.load_dump(p)
    assert meta["trigger"] == "worker_hung"
    assert len(events) == 3
    only = flight_dump_tool.filter_events(events, span=span)
    assert [e["kind"] for e in only] == ["serving.submit", "serving.hang"]
    out = _stdio.StringIO()
    flight_dump_tool.render(meta, only, out=out)
    text = out.getvalue()
    assert "worker_hung" in text and span in text and "serving.hang" in text
    # CLI contract: 0 on success (with or without the CRC pass), 2 on
    # a corrupt dump — the manifest catches the silent bit flip
    assert flight_dump_tool.main([str(p), "--span", span]) == 0
    assert flight_dump_tool.main([str(p), "--no-validate"]) == 0
    faults.flip_byte(str(p), name="events.jsonl")
    assert flight_dump_tool.main([str(p)]) == 2


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------


def test_http_metrics_healthz_and_404():
    r = MetricsRegistry()
    r.counter("paddle_tpu_t_hits_total", "hits").inc(3)
    live = {"live": True, "state": "ready"}
    with telemetry.serve_metrics(registry=r, health_fn=lambda: dict(live)) \
            as srv:
        body = urllib.request.urlopen(srv.url + "/metrics")
        assert body.headers["Content-Type"].startswith("text/plain")
        series, _, _ = _parse_prometheus(body.read().decode())
        assert series["paddle_tpu_t_hits_total"] == 3
        health = json.loads(
            urllib.request.urlopen(srv.url + "/healthz").read())
        assert health == live
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope")
        assert ei.value.code == 404
        # not-live flips /healthz to 503 (the LB probe contract)
        live["live"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/healthz")
        assert ei.value.code == 503


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------


def test_trainer_dispatch_journal_and_registry_series(fresh_telemetry):
    j = fresh_telemetry
    tr = _trainer()
    tr.step(_FEED)
    tr.step(_FEED)
    disp = j.recent(kind="trainer.dispatch")
    assert len(disp) == 2
    assert disp[0]["span"] and disp[0]["num_steps"] == 1
    assert disp[0]["base_step"] == 0 and disp[1]["base_step"] == 1
    assert disp[0]["dur_s"] > 0
    reg = telemetry.get_registry()
    series, _, _ = _parse_prometheus(reg.render_prometheus())
    inst = tr.telemetry_inst
    assert series[f'paddle_tpu_trainer_steps_total{{inst="{inst}"}}'] == 2
    assert series[
        f'paddle_tpu_trainer_dispatches_total{{inst="{inst}",kind="step"}}'
    ] == 2
    assert series[f'paddle_tpu_trainer_global_step{{inst="{inst}"}}'] == 2
    assert reg.validate() == []


def test_counter_snapshot_delta_over_a_window_of_steps(fresh_telemetry):
    """Two ``counter_values()`` snapshots round a window of steps,
    reduced by ``counter_deltas(per=steps)``: the trainer's step counter
    reads 1.0 a step, only counters appear (the global_step gauge does
    not), and a series that did not move in the window is left out."""
    tr = _trainer()
    tr.step(_FEED)
    reg = telemetry.get_registry()
    before = reg.counter_values()
    for _ in range(3):
        tr.step(_FEED)
    per_step = counter_deltas(before, reg.counter_values(), per=3)
    inst = tr.telemetry_inst
    assert per_step[f'paddle_tpu_trainer_steps_total{{inst="{inst}"}}'] == 1.0
    assert per_step[
        f'paddle_tpu_trainer_dispatches_total{{inst="{inst}",kind="step"}}'
    ] == 1.0
    assert not any(k.startswith("paddle_tpu_trainer_global_step")
                   for k in per_step)
    assert counter_deltas(reg.counter_values(), reg.counter_values()) == {}


def test_fit_fill_span_shared_with_dispatch(fresh_telemetry):
    j = fresh_telemetry
    tr = _trainer()
    _fit(tr, steps_per_dispatch=4)
    fills = j.recent(kind="feeder.fill")
    disp = j.recent(kind="trainer.dispatch")
    assert fills and disp
    fill_spans = [e["span"] for e in fills]
    disp_spans = [e["span"] for e in disp]
    # every dispatch rides the span its fill minted, 1:1 in order
    assert fill_spans == disp_spans
    assert {e["num_steps"] for e in disp} == {4}


def test_fit_profile_interval_events(fresh_telemetry):
    events = []
    tr = _trainer()
    _fit(tr, handler=events.append, profile_interval_steps=3)
    profs = [e for e in events if e.kind == "profile"]
    # 8 steps, boundary-crossings of 3 at steps 3 and 6
    assert [e.step for e in profs] == [3, 6]
    end_epoch = [e for e in events if e.kind == "end_epoch"][0]
    # same report path as end_epoch: same schema, pipeline aliased in
    assert set(profs[0].profile.keys()) == set(end_epoch.profile.keys())
    assert profs[0].pipeline is profs[0].profile["pipeline"]
    assert profs[0].profile["steps"] == 3
    with pytest.raises(Exception, match="profile_interval_steps"):
        _fit(_trainer(), profile_interval_steps=-1)


def test_sigterm_flight_dump_has_guard_incident_and_ckpt(fresh_telemetry,
                                                         tmp_path):
    """The training black-box contract: a SIGTERM preemption dump
    contains the last guard incident and the boundary checkpoint
    event."""
    ckdir = tmp_path / "ck"
    cfg = pt.CheckpointConfig(str(ckdir), epoch_interval=0,
                              step_interval=0, max_num_checkpoints=3)
    reader = faults.nan_batch_reader(_reader(), at_batch=2)

    def handler(e):
        if e.kind == "end_step" and e.step == 5:
            os.kill(os.getpid(), signal.SIGTERM)

    tr = _trainer(guard=pt.GuardPolicy(max_incidents=8, window=100))
    pt.fit(tr, reader, num_epochs=2, feed_names=["x", "label"],
           dtypes=["float32", "int64"], checkpoint_config=cfg,
           event_handler=handler)
    assert tr.guard_incident_total == 1
    # with a checkpoint_config, dumps land next to the checkpoints
    root = ckdir / "flight"
    dumps = [p for p in root.iterdir() if p.name.startswith("flight_")]
    assert len(dumps) == 1
    meta = json.load(open(dumps[0] / "flight.json"))
    assert meta["trigger"] == "preempted"
    assert meta["detail"]["signum"] == signal.SIGTERM
    kinds = [json.loads(line)["kind"]
             for line in open(dumps[0] / "events.jsonl")]
    assert "guard.incident" in kinds and "ckpt.save" in kinds
    inc = [json.loads(line) for line in open(dumps[0] / "events.jsonl")
           if json.loads(line)["kind"] == "guard.incident"]
    assert inc[-1]["step"] == 2 and inc[-1]["outputs"]
    # the registry counted it too
    series, _, _ = _parse_prometheus(
        telemetry.get_registry().render_prometheus())
    assert series[
        f'paddle_tpu_trainer_guard_incidents_total{{inst="{tr.telemetry_inst}"}}'
    ] == 1


def test_fit_unhandled_exception_flight_dump(fresh_telemetry, tmp_path):
    def bad_reader():
        def r():
            yield from _reader(2)()
            raise RuntimeError("disk on fire")
        return r

    tr = _trainer()
    with pytest.raises(RuntimeError, match="disk on fire"):
        pt.fit(tr, bad_reader(), num_epochs=1, feed_names=["x", "label"],
               dtypes=["float32", "int64"])
    dumps = _flight_dirs(tmp_path)
    assert len(dumps) == 1
    meta = json.load(open(dumps[0] / "flight.json"))
    assert meta["trigger"] == "fit_exception"
    assert "disk on fire" in meta["detail"]["error"]


def test_guard_escalation_flight_dump(fresh_telemetry, tmp_path):
    reader = faults.nan_batch_reader(_reader(), at_batch=1)
    tr = _trainer(guard=pt.GuardPolicy(max_incidents=0, window=10,
                                       defer_readback=False))
    with pytest.raises(FloatingPointError):
        pt.fit(tr, reader, num_epochs=1, feed_names=["x", "label"],
               dtypes=["float32", "int64"])
    dumps = _flight_dirs(tmp_path)
    # exactly ONE dump: the escalation site's (fit's wrapper skips
    # FloatingPointError so the same crash is not dumped twice)
    assert len(dumps) == 1
    meta = json.load(open(dumps[0] / "flight.json"))
    assert meta["trigger"] == "guard_escalation"
    kinds = [json.loads(line)["kind"]
             for line in open(dumps[0] / "events.jsonl")]
    assert "guard.incident" in kinds


def test_trainer_serve_metrics_endpoint(fresh_telemetry):
    tr = _trainer()
    tr.step(_FEED)
    srv = tr.serve_metrics()
    try:
        # idempotent: a repeat call returns the SAME running server,
        # never a second port/daemon thread
        assert tr.serve_metrics() is srv
        health = json.loads(
            urllib.request.urlopen(srv.url + "/healthz").read())
        assert health["role"] == "trainer" and health["global_step"] == 1
        series, _, _ = _parse_prometheus(
            urllib.request.urlopen(srv.url + "/metrics").read().decode())
        assert series[
            f'paddle_tpu_trainer_steps_total{{inst="{tr.telemetry_inst}"}}'
        ] == 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------


def _serving_feed(n, seed=0):
    rng = np.random.RandomState(seed)
    return {"image": rng.randn(n, 784).astype(np.float32),
            "label": rng.randint(0, 10, (n, 1)).astype(np.int64)}


@pytest.fixture(scope="module")
def pred(tmp_path_factory):
    from paddle_tpu.models import mnist

    d = str(tmp_path_factory.mktemp("telemetry_serving") / "model")
    prog = pt.build(mnist.mlp)
    feed8 = _serving_feed(8)
    params, state = prog.init(jax.random.PRNGKey(0), **feed8)
    pio.save_inference_model(d, prog, params, state, feed8,
                             batch_buckets=[4, 8])
    return pio.load_inference_model(d)


def test_request_span_correlates_submit_queue_dispatch_complete(
        fresh_telemetry, pred):
    j = fresh_telemetry
    with serving.PredictorServer(pred, workers=1, queue_size=4) as srv:
        h = srv.submit(_serving_feed(8))
        h.result(timeout=60)
        assert h.span
        ev = j.recent(span=h.span)
        kinds = [e["kind"] for e in ev]
        assert kinds == ["serving.submit", "serving.dispatch",
                         "serving.complete"]
        submit, dispatch, complete = ev
        assert "queue_depth" in submit            # queued state at submit
        assert dispatch["worker"] == 0 and dispatch["queued_s"] >= 0
        assert dispatch["bucket"] == 8 and submit["n"] == 8
        assert complete["latency_s"] > 0
        # a reject carries the same span discipline
        bad = _serving_feed(8)
        bad["image"][0, 0] = np.nan
        with pytest.raises(serving.InvalidRequest):
            srv.submit(bad)
        rej = j.recent(kind="serving.reject")
        assert rej[-1]["reason"] == "invalid" and rej[-1]["span"]


def test_metrics_endpoint_on_live_server_under_load(fresh_telemetry, pred):
    """The acceptance criterion: GET /metrics on a LIVE PredictorServer
    under load parses as Prometheus text whose queue/latency/reject
    series agree with ServingMetrics.report()."""
    with serving.PredictorServer(pred, workers=2, queue_size=8) as srv:
        ep = srv.serve_metrics()
        pending = [srv.submit(_serving_feed(8, seed=i)) for i in range(6)]
        # scrape WHILE requests are in flight: must parse regardless
        _parse_prometheus(
            urllib.request.urlopen(ep.url + "/metrics").read().decode())
        for p in pending:
            p.result(timeout=60)
        with pytest.raises(serving.InvalidRequest):
            srv.submit({"image": np.zeros((8, 3), np.float32),
                        "label": np.zeros((8, 1), np.int64)})
        series, types, _ = _parse_prometheus(
            urllib.request.urlopen(ep.url + "/metrics").read().decode())
        rep = srv.report()
        inst = srv.telemetry_inst
        assert series[
            f'paddle_tpu_serving_submitted_total{{inst="{inst}"}}'
        ] == rep["submitted"] == 6
        assert series[
            f'paddle_tpu_serving_completed_total{{inst="{inst}"}}'
        ] == rep["completed"] == 6
        assert series[
            f'paddle_tpu_serving_rejected_total{{inst="{inst}",reason="invalid"}}'
        ] == rep["rejected_invalid"] == 1
        assert series[
            f'paddle_tpu_serving_queue_depth{{inst="{inst}"}}'
        ] == rep["health"]["queue_depth"]
        assert series[
            f'paddle_tpu_serving_queue_capacity{{inst="{inst}"}}'
        ] == rep["health"]["queue_capacity"] == 8
        # the latency histogram's _count equals the report's count and
        # the +Inf bucket (series agree, not re-derived)
        hist = rep["latency_hist"]
        assert series[
            f'paddle_tpu_serving_latency_seconds_count{{inst="{inst}"}}'
        ] == hist["count"] == 6
        assert series[
            f'paddle_tpu_serving_latency_seconds_bucket{{inst="{inst}",le="+Inf"}}'
        ] == 6
        assert types["paddle_tpu_serving_latency_seconds"] == "histogram"
        # healthz agrees with health()
        health = json.loads(
            urllib.request.urlopen(ep.url + "/healthz").read())
        assert health["ready"] is True and health["state"] == "ready"
        assert telemetry.get_registry().validate() == []


def test_latency_hist_buckets_consistent_with_percentiles(fresh_telemetry,
                                                          pred):
    with serving.PredictorServer(pred, workers=1, queue_size=4) as srv:
        for i in range(4):
            srv.run(_serving_feed(8, seed=i), timeout=60)
        rep = srv.report()
        h = rep["latency_hist"]
        assert len(h["counts"]) == len(h["bounds_s"]) + 1
        assert sum(h["counts"]) == h["count"] == 4
        assert h["bounds_s"] == sorted(h["bounds_s"])
        assert h["sum_s"] > 0
        # the p50 the report derives lives inside the populated range
        p50_s = rep["latency_ms"]["p50"] / 1e3
        lo = min(b for b, c in zip(h["bounds_s"], h["counts"]) if c) \
            if any(h["counts"][:-1]) else 0.0
        assert p50_s >= lo * 0.99


def test_watchdog_kill_drill_dumps_with_hang_span(fresh_telemetry,
                                                  tmp_path, pred):
    """The kill-drill acceptance: hanging predictor → watchdog →
    flight dump on disk that tools/flight_dump.py renders with the
    hang's span id."""
    import importlib
    flight_dump_tool = importlib.import_module("tools.flight_dump")

    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = serving.PredictorServer(
        hang, workers=1, queue_size=4, warmup=False, watchdog_timeout=0.2,
        breaker=serving.BreakerPolicy(failure_threshold=5, cooldown=0.2))
    try:
        hung = srv.submit(_serving_feed(8))
        with pytest.raises(serving.WorkerHung):
            hung.result(timeout=60)
        deadline = time.monotonic() + 5
        while not _flight_dirs(tmp_path) and time.monotonic() < deadline:
            time.sleep(0.02)
        dumps = _flight_dirs(tmp_path)
        assert dumps, "watchdog produced no flight dump"
        meta = json.load(open(dumps[0] / "flight.json"))
        assert meta["trigger"] == "worker_hung"
        assert meta["span"] == hung.span
        out = _stdio.StringIO()
        m, events = flight_dump_tool.load_dump(str(dumps[0]))
        flight_dump_tool.render(
            m, flight_dump_tool.filter_events(events, span=hung.span),
            out=out)
        text = out.getvalue()
        assert hung.span in text and "serving.hang" in text
        # hang + submit of the same request share the span
        kinds = [e["kind"] for e in events if e.get("span") == hung.span]
        assert "serving.submit" in kinds and "serving.hang" in kinds
        m2 = srv.metrics.snapshot()
        assert m2["hangs"] == 1
    finally:
        release.set()
        srv.close(drain=False, timeout=5)


def test_breaker_threshold_trip_journals_and_dumps(fresh_telemetry,
                                                   tmp_path, pred):
    j = fresh_telemetry
    failing = faults.failing_predictor(pred, fail_calls=10)
    srv = serving.PredictorServer(
        failing, workers=1, queue_size=8, warmup=False,
        breaker=serving.BreakerPolicy(failure_threshold=2, cooldown=30.0))
    try:
        for i in range(2):
            with pytest.raises(Exception):
                srv.run(_serving_feed(8), timeout=60)
        assert srv.breaker.state == "open"
        trips = j.recent(kind="serving.breaker_open")
        assert trips and trips[-1]["reason"] == "failures"
        # the trip's dump is written on the WORKER thread (the request
        # completes before breaker.record runs) — wait for the commit
        deadline = time.monotonic() + 5

        def trip_dumps():
            return [d for d in _flight_dirs(tmp_path)
                    if json.load(open(d / "flight.json"))["trigger"]
                    == "breaker_trip"]

        while not trip_dumps() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert trip_dumps()
    finally:
        srv.close(drain=False, timeout=5)


# ---------------------------------------------------------------------------
# async-PS telemetry
# ---------------------------------------------------------------------------


def test_ps_client_report_and_wire_trace_echo(fresh_telemetry):
    from paddle_tpu.parallel.async_ps import PServerProcess, PSClient

    with PServerProcess(lr=0.1) as srv:
        c = PSClient(srv.addr)
        c.init_param("w", np.ones(4, np.float32))
        span = telemetry.get_journal().new_span()
        c.push("w", np.ones(4, np.float32), span=span)
        # the optional trace field rode the framed header and the NEW
        # server echoed it; positional reply fields are unchanged, so
        # an old client (int(resp.split()[1])) never notices
        assert c.last_reply.startswith("OK 1")
        assert f"trace={span}" in c.last_reply
        c.pull("w", (4,), span=span)
        assert f"trace={span}" in c.last_reply
        # without a span the header is byte-identical to the old wire
        c.push("w", np.ones(4, np.float32))
        assert "trace=" not in c.last_reply
        rep = c.report()
        assert rep["pushes"] == 2 and rep["pulls"] == 1
        assert rep["reconnects"] == 0 and rep["pushes_undelivered"] == 0
        with pytest.raises(Exception, match="whitespace"):
            c.push("w", np.ones(4, np.float32), span="bad span")


def test_ps_shard_group_totals_monotonic_across_retirement():
    """resize()/rebind() close transports to departed servers; their
    traffic folds into the retired aggregate so the exported
    paddle_tpu_ps_* counters never go backwards (a Prometheus counter
    reset would fake a huge rate)."""
    from paddle_tpu.parallel.async_ps import PSShardGroup

    g = PSShardGroup.__new__(PSShardGroup)  # no network: counters only
    g._clients, g._retired_counts, g.addrs = {}, {}, []

    class FakeClient:
        def __init__(self, n):
            self.rep = {"addr": f"h:{n}", "requests": 5 * n,
                        "pushes": 3 * n, "pulls": n, "reconnects": 2,
                        "retries": 4, "pushes_undelivered": 1}

        def report(self):
            return dict(self.rep)

        def close(self):
            self.closed = True

    g._clients[("h", 1)] = FakeClient(1)
    g._clients[("h", 2)] = FakeClient(2)
    before = g.report()
    departed = g._clients.pop(("h", 2))
    g._retire_client(departed)
    after = g.report()
    assert departed.closed
    assert "h:2" not in after["servers"] and "h:2" in before["servers"]
    for k in ("requests", "pushes", "pulls", "reconnects", "retries",
              "pushes_undelivered"):
        assert after[k] == before[k], k  # totals unchanged, not reversed


def test_async_ps_trainer_report_and_registry(fresh_telemetry):
    from paddle_tpu.parallel.async_ps import AsyncPSTrainer, PServerProcess

    j = fresh_telemetry
    with PServerProcess(lr=0.1) as srv:
        t = AsyncPSTrainer(_PROG, srv.addr, trainer_id=0)
        t.startup(sample_feed=_FEED)
        t.step(_FEED)
        rep = t.report()
        assert rep["global_step"] == 1 and rep["pushes_lost"] == 0
        assert rep["client"]["pushes"] == 4      # fc1/fc2 w+b
        assert rep["client"]["pulls"] >= 4
        steps = j.recent(kind="ps.step")
        assert len(steps) == 1 and steps[0]["span"]
        series, _, _ = _parse_prometheus(
            telemetry.get_registry().render_prometheus())
        inst = t.telemetry_inst
        assert series[
            f'paddle_tpu_ps_pushes_total{{inst="{inst}"}}'] == 4
        assert series[
            f'paddle_tpu_ps_pushes_lost_total{{inst="{inst}"}}'] == 0


# ---------------------------------------------------------------------------
# the tier-1 CI contracts: naming convention + overhead
# ---------------------------------------------------------------------------


def test_registry_naming_contract_after_train_and_serve(fresh_telemetry,
                                                        pred):
    """The CI naming-convention gate: after a short train + serve run,
    every family the process registry exports obeys
    paddle_tpu_<subsystem>_<name>{labels} with help text — and the
    full exposition parses. This walks EVERYTHING registered by the
    whole test process (trainers, servers, PS clients), so any
    instrumentation added later that violates the convention fails
    here."""
    tr = _trainer()
    _fit(tr, steps_per_dispatch=2)
    with serving.PredictorServer(pred, workers=1, queue_size=4) as srv:
        srv.run(_serving_feed(8), timeout=60)
        reg = telemetry.get_registry()
        assert reg.validate() == []
        series, types, helps = _parse_prometheus(reg.render_prometheus())
        from paddle_tpu.telemetry.registry import METRIC_NAME_RE
        for fam in reg.collect():
            assert METRIC_NAME_RE.match(fam.name), fam.name
            assert fam.help.strip(), fam.name
        # both halves of the fleet story are present in one scrape
        assert any(k.startswith("paddle_tpu_trainer_") for k in series)
        assert any(k.startswith("paddle_tpu_serving_") for k in series)
        assert any(k.startswith("paddle_tpu_feeder_") for k in series)


def test_feeder_cache_and_overlap_counter_families(fresh_telemetry):
    """The device-resident data path's counters (PR 15) export through
    the same scrape-time collector as every feeder stage: cache hit
    bytes/chunks and ring-hidden transfer seconds, naming-contract
    clean, and numerically equal to the PipelineMetrics accumulators
    they render (the can-never-disagree property)."""
    from paddle_tpu.data.feeder import PipelineMetrics
    from paddle_tpu.telemetry.registry import METRIC_NAME_RE

    m = PipelineMetrics()
    m.record_h2d(1_000, 0.25, exposed_s=0.1)   # 0.15 s hidden
    m.record_cache_hit(4_096)
    m.record_cache_hit(4_096)
    fams = {f.name: f for f in m.telemetry_families(inst="7")}
    for name, want in [
            ("paddle_tpu_feeder_overlap_hidden_seconds_total", 0.15),
            ("paddle_tpu_feeder_cache_hit_bytes_total", 8_192),
            ("paddle_tpu_feeder_cache_hits_total", 2)]:
        assert name in fams, sorted(fams)
        assert METRIC_NAME_RE.match(name), name
        fam = fams[name]
        assert fam.help.strip()
        (labels, value), = fam.samples
        assert labels == {"inst": "7"}
        assert value == pytest.approx(want)


def test_telemetry_overhead_under_2pct_at_k16(fresh_telemetry):
    """The hot-path budget (same direct-cost method as the PR-6
    StepTimer pin): the per-dispatch cost of the telemetry-bearing
    record_dispatch — ring append + journal emit with a span — stays
    under 2% of a measured K=16 fused dispatch. No device interaction
    happens anywhere in that path (zero added host syncs)."""
    from paddle_tpu.data.feeder import stack_batches
    from paddle_tpu.profiling.steptime import StepTimer

    k, n = 16, 6
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(BS, DIM).astype(np.float32),
              "label": rng.randint(0, CLASSES, (BS, 1)).astype(np.int64)}
             for _ in range(4)]
    tr = _trainer()
    stacked = tr._put_feed(
        stack_batches([feeds[i % len(feeds)] for i in range(k)]),
        stacked=True)
    out = tr.run_steps(stacked, k=k)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = tr.run_steps(stacked, k=k)
    jax.block_until_ready(out)
    dispatch_s = (time.perf_counter() - t0) / n

    j = RunJournal()            # ring-only: the default hot-path config
    st = StepTimer(journal=j, inst="0")
    reps = 5_000
    t0 = time.perf_counter()
    for i in range(reps):
        st.record_dispatch(time.perf_counter(), time.perf_counter(), k,
                           "run_steps", span=None, base_step=i * k)
    per_record = (time.perf_counter() - t0) / reps
    assert per_record < 0.02 * dispatch_s, (per_record, dispatch_s)
