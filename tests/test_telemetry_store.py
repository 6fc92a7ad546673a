"""Durable-telemetry acceptance suite: on-disk series store, collector
restart recovery, HA failover, and alert-rule hot-reload.

The contracts (all CPU; real sockets, explicit clocks where possible):

  * resilience segment primitives: CRC-framed records survive a torn
    tail and a flipped byte as SKIPPED records (never a crash), sealed
    segments commit an atomic CRC sidecar that `check_segment` holds
    them to;
  * SegmentStore rotates at the byte bound, enforces retention by time
    AND bytes (oldest-segment deletion, active never deleted), and
    serves deterministic downsampled range reads;
  * collector restart with a populated store reproduces pre-restart
    /metrics (every fleet series + ingest counters; the store's own
    per-life I/O meta-series are the documented exception), /alerts
    (firing state with its original clock — no re-fire, no resolve
    flap), /query range reads, the fleet journal, and the EVENTS
    dedupe high-water marks — bit-identically;
  * torn/bit-flipped segments are detected by CRC, skipped, and
    counted (`paddle_tpu_collector_segments_corrupt_total`) while
    ingestion keeps working;
  * shipper failover: the comma-separated PDTPU_TELEMETRY_ADDR shape,
    a dead primary rotating to the standby WITHIN one flush, zero
    shipped-event loss across the cutover (dedupe high-water marks on
    the promoted standby), and the failover recorded in
    `paddle_tpu_shipper_flushes_total{outcome="failover"}`;
  * standby promotion replays the shared segment log: a pre-kill
    firing alert is firing on the standby with its original `since`
    and ZERO alert transitions journaled for it;
  * alert rules hot-reload through `lint_rules` with reject-on-
    findings (engine untouched), a journaled `alert.rules_reloaded`,
    state carried for persisting rule names, `POST /rules` and SIGHUP
    drive the same path;
  * `GET /query` serves range reads over HTTP (store-backed and the
    in-memory fallback);
  * tools/series_dump.py holds the 0/2/3 exit contract;
  * the ingest hot path WITH persistence stays under 2% of a measured
    K=16 fused dispatch (the established telemetry overhead pin).
"""

import json
import os
import signal
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu import resilience
from paddle_tpu import telemetry
from paddle_tpu.telemetry import alerts
from paddle_tpu.telemetry import shipper as tshipper
from paddle_tpu.telemetry.collector import TelemetryCollector
from paddle_tpu.telemetry.journal import RunJournal
from paddle_tpu.telemetry.registry import (MetricsRegistry,
                                           render_families_prometheus)
from paddle_tpu.telemetry.store import SegmentStore, downsample
from paddle_tpu.testing import faults


@pytest.fixture()
def fresh(tmp_path):
    old = telemetry.set_journal(RunJournal())
    try:
        yield telemetry.get_journal()
    finally:
        tshipper.stop_shipping()
        j = telemetry.set_journal(old)
        if j is not None:
            j.close()


def _snap(name, value, labels=None, type_="gauge", help_="h"):
    return {name: {"type": type_, "help": help_,
                   "samples": [{"labels": dict(labels or {}),
                                "value": value}]}}


def _gauge_snap_record(origin, t, value, name="paddle_tpu_serving_queue_depth"):
    return {"k": "snap", "o": origin, "t": t,
            "f": _snap(name, value, labels={"inst": "0"})}


# ---------------------------------------------------------------------------
# resilience segment primitives
# ---------------------------------------------------------------------------


def test_frame_and_iter_records_roundtrip_and_corruption(tmp_path):
    p = str(tmp_path / "seg.log")
    payloads = [json.dumps({"i": i}).encode() for i in range(5)]
    with open(p, "wb") as f:
        for b in payloads:
            f.write(resilience.frame_record(b))
    got = list(resilience.iter_records(p))
    assert [ok for ok, _ in got] == [True] * 5
    assert [b for _, b in got] == payloads

    # a newline-carrying payload is rejected at frame time (framing is
    # line-based)
    with pytest.raises(ValueError):
        resilience.frame_record(b"a\nb")

    # torn tail (kill -9 mid-append): last record unreadable, earlier
    # ones intact, no exception
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) - 3)
    got = list(resilience.iter_records(p))
    assert [ok for ok, _ in got] == [True] * 4 + [False]
    assert "torn tail" in got[-1][1]

    # a flipped byte fails exactly its record's CRC
    p2 = str(tmp_path / "seg2.log")
    with open(p2, "wb") as f:
        for b in payloads:
            f.write(resilience.frame_record(b))
    faults.flip_byte(str(tmp_path), "seg2.log",
                     offset=os.path.getsize(p2) // 2)
    got = list(resilience.iter_records(p2))
    assert got.count((True, payloads[0])) == 1
    assert sum(1 for ok, _ in got if not ok) == 1


def test_seal_and_check_segment(tmp_path):
    p = str(tmp_path / "segment-00000001.log")
    with open(p, "wb") as f:
        f.write(resilience.frame_record(b'{"k":"x"}'))
    meta = resilience.seal_segment(p, meta={"records": 1})
    assert meta["records"] == 1 and meta["size"] == os.path.getsize(p)
    ok, reason = resilience.check_segment(p)
    assert ok, reason
    # sidecar-less file is a finding
    p2 = str(tmp_path / "segment-00000002.log")
    open(p2, "wb").close()
    ok, reason = resilience.check_segment(p2)
    assert not ok and "sidecar" in reason
    # bit flip after sealing is caught by the whole-file CRC
    faults.flip_byte(str(tmp_path), os.path.basename(p))
    ok, reason = resilience.check_segment(p)
    assert not ok and "checksum mismatch" in reason


# ---------------------------------------------------------------------------
# SegmentStore: rotation, retention, range reads
# ---------------------------------------------------------------------------


def test_downsample_last_sample_per_bucket():
    pts = [(100.0, 1.0), (101.0, 2.0), (104.9, 3.0), (105.0, 4.0),
           (109.0, 5.0)]
    assert downsample(pts, 100.0, 0.0) == pts
    assert downsample(pts, 100.0, 5.0) == [(100.0, 3.0), (105.0, 5.0)]
    assert downsample([], 0.0, 5.0) == []


def test_segment_store_rotation_retention_and_query(tmp_path):
    seg = SegmentStore(str(tmp_path / "s"), segment_max_bytes=256,
                       retention_s=3600.0, retention_bytes=1 << 30,
                       state_fn=lambda: {"marker": True})
    seg.open()
    for i in range(20):
        assert seg.append(_gauge_snap_record("r0", 1000.0 + i, i))
    names = [os.path.basename(p) for p in seg.segment_paths()]
    assert sum(1 for n in names if n.endswith(".log")) >= 3
    assert sum(1 for n in names if n.endswith(".open")) == 1
    # sealed segments carry atomic CRC sidecars and validate clean
    assert seg.validate() == []
    # every segment BEGINS with a state record (the recovery baseline)
    first = next(seg._iter_payloads([seg.segment_paths()[0]]))
    assert first["k"] == "state" and first["marker"] is True

    # raw + downsampled range reads, label matching
    q = seg.query("paddle_tpu_serving_queue_depth", {"origin": "r0"},
                  start=1000.0, end=1019.0)
    assert len(q["series"]) == 1
    assert [p[1] for p in q["series"][0]["points"]] == \
        [float(i) for i in range(20)]
    q = seg.query("paddle_tpu_serving_queue_depth", {}, start=1000.0,
                  end=1019.0, step=10.0)
    assert q["series"][0]["points"] == [[1000.0, 9.0], [1010.0, 19.0]]
    assert seg.query("paddle_tpu_serving_queue_depth",
                     {"origin": "nope"}, 0, 2000.0)["series"] == []

    # retention by BYTES: oldest sealed segments deleted, active kept
    seg.retention_bytes = 600
    deleted = seg.enforce_retention(now=2000.0)
    assert deleted and all(n.endswith(".log") for n in deleted)
    assert seg.total_bytes() <= 600 + seg.segment_max_bytes
    remaining = [os.path.basename(p) for p in seg.segment_paths()]
    assert any(n.endswith(".open") for n in remaining)
    # the deleted prefix is GONE from range reads (the trade is
    # explicit: segment-granularity forgetting)
    q = seg.query("paddle_tpu_serving_queue_depth", {}, 1000.0, 1019.0)
    pts = q["series"][0]["points"] if q["series"] else []
    assert len(pts) < 20

    # retention by TIME: everything sealed is older than 1s at t+1h
    seg.retention_s = 1.0
    seg.rotate()   # seal the active tail so it is eligible
    deleted = seg.enforce_retention(now=1019.0 + 3600.0)
    assert deleted
    assert all(os.path.basename(p).endswith(".open")
               for p in seg.segment_paths())
    seg.close()


def test_segment_store_recovers_from_leftover_open_segment(tmp_path):
    """A killed writer leaves an .open segment (optionally torn):
    recovery reads it record-by-record, and the next open() seals it."""
    root = str(tmp_path / "s")
    seg = SegmentStore(root)
    seg.open()
    for i in range(5):
        seg.append(_gauge_snap_record("r0", 100.0 + i, i))
    seg.close()   # flushed but NOT sealed: simulates kill -9
    active = [p for p in seg.segment_paths() if p.endswith(".open")]
    assert len(active) == 1
    with open(active[0], "r+b") as f:   # torn tail
        f.truncate(os.path.getsize(f.name) - 2)

    seg2 = SegmentStore(root)
    got = []
    seg2.recover(lambda k, doc: got.append(doc))
    assert [d["f"]["paddle_tpu_serving_queue_depth"]["samples"][0]["value"]
            for d in got if d["k"] == "snap"] == [0, 1, 2, 3]
    assert seg2.counters["corrupt_records"] == 1
    seg2.open()
    assert not any(p.endswith(".open") and "00000001" in p
                   for p in seg2.segment_paths())
    # the sealed leftover + the new active
    assert len(seg2.segment_paths()) == 2
    seg2.close()


# ---------------------------------------------------------------------------
# collector restart: bit-identical recovery
# ---------------------------------------------------------------------------


_STORE_SELF_SERIES = "paddle_tpu_collector_store_"


def _strip_store_self_series(text):
    """The store's own I/O meta-series (appends/bytes/seconds/segment
    gauge) describe THIS process's disk work and are per-life by
    design — the one documented exception to restart bit-identity."""
    return "\n".join(l for l in text.splitlines()
                     if _STORE_SELF_SERIES not in l) + "\n"


def test_collector_restart_reproduces_state_bit_identically(fresh, tmp_path):
    store_dir = str(tmp_path / "tstore")
    rules = [alerts.parse_rule(
        "hot", "paddle_tpu_serving_queue_depth > 5 for 0s",
        severity="warn")]
    kw = dict(eval_interval=3600, rules=rules, store_dir=store_dir,
              flight_root=str(tmp_path / "flight"))
    col = TelemetryCollector(**kw)
    cli = tshipper.ShipperClient(col.addr)
    now = time.time()
    for i, v in enumerate([2, 7, 9]):
        cli.ship_snapshot("r0", _snap("paddle_tpu_serving_queue_depth", v,
                                      labels={"inst": "0"}))
    cli.ship_snapshot("r1", _snap("paddle_tpu_serving_errors_total", 4,
                                  labels={"inst": "0"}, type_="counter"))
    cli.ship_events("r0", "run1", [
        {"run": "run1", "seq": i, "sseq": i, "t": now + i, "kind": "x.y",
         "span": "s1"} for i in range(1, 6)])
    trans = col.evaluate_once()
    assert [t["state"] for t in trans] == ["firing"]
    cli.close()

    fixed = time.time()
    fam1 = _strip_store_self_series(
        render_families_prometheus(col.families(now=fixed)))
    al1 = col.engine.snapshot(now=fixed)
    q1 = col.query("paddle_tpu_serving_queue_depth", {}, 0.0,
                   fixed + 10, 0.0)
    qd1 = col.query("paddle_tpu_serving_queue_depth", {}, 0.0,
                    fixed + 10, 0.5)
    tl1 = col.timeline("s1")
    j1 = col.journal.recent(kind="x.")
    assert len(j1) == 5
    col.close()

    col2 = TelemetryCollector(**kw)
    try:
        # /metrics (modulo the per-life store I/O meta-series),
        # /alerts incl. in-flight firing state, /query raw AND
        # downsampled, /timeline, and the journal: all bit-identical
        assert _strip_store_self_series(
            render_families_prometheus(col2.families(now=fixed))) == fam1
        assert col2.engine.snapshot(now=fixed) == al1
        assert col2.query("paddle_tpu_serving_queue_depth", {}, 0.0,
                          fixed + 10, 0.0) == q1
        assert col2.query("paddle_tpu_serving_queue_depth", {}, 0.0,
                          fixed + 10, 0.5) == qd1
        assert col2.timeline("s1") == tl1
        assert col2.journal.recent(kind="x.") == j1
        # no spurious transitions on the next tick: the firing
        # instance carried its clock, the condition still holds
        assert col2.evaluate_once() == []
        assert [e for e in col2.journal.recent(kind="alert.")] == []
        # dedupe high-water marks survived: a shipper retrying the
        # pre-restart batch is still deduped to zero
        cli2 = tshipper.ShipperClient(col2.addr)
        assert cli2.ship_events("r0", "run1", [
            {"run": "run1", "seq": i, "sseq": i, "t": now + i,
             "kind": "x.y", "span": "s1"} for i in range(1, 6)]) == 0
        # ...and fresh pushes keep working
        assert cli2.ship_events("r0", "run1", [
            {"run": "run1", "seq": 6, "sseq": 6, "t": now + 6,
             "kind": "x.z"}]) == 1
        cli2.close()
    finally:
        col2.close()


def test_collector_recovery_skips_corrupt_segments_counts_and_ingests(
        fresh, tmp_path):
    store_dir = str(tmp_path / "cstore")
    kw = dict(eval_interval=3600, rules=[], store_dir=store_dir)
    col = TelemetryCollector(**kw)
    cli = tshipper.ShipperClient(col.addr)
    for i in range(4):
        cli.ship_snapshot("r0", _snap("paddle_tpu_serving_queue_depth", i,
                                      labels={"inst": "0"}))
    cli.close()
    col._seg.rotate()
    col.close()

    # flip a byte mid-segment AND truncate the newest one: both are
    # detected by CRC, skipped, counted — never a crash
    segs = sorted(p for p in os.listdir(store_dir) if p.endswith(".log"))
    faults.flip_byte(store_dir, segs[0])
    faults.truncate_file(store_dir, segs[-1],
                         keep_bytes=os.path.getsize(
                             os.path.join(store_dir, segs[-1])) - 4)
    col2 = TelemetryCollector(**kw)
    try:
        corrupt = [f for f in col2.families(now=time.time())
                   if f.name == "paddle_tpu_collector_segments_corrupt_total"]
        assert corrupt and corrupt[0].samples[0][1] >= 2
        # the surviving records are there, and ingestion still works
        assert col2.store.origins().keys() == {"r0"}
        cli2 = tshipper.ShipperClient(col2.addr)
        assert cli2.ship_snapshot(
            "r1", _snap("paddle_tpu_serving_queue_depth", 1,
                        labels={"inst": "0"})) == 1
        cli2.close()
        assert set(col2.store.origins()) == {"r0", "r1"}
    finally:
        col2.close()


# ---------------------------------------------------------------------------
# /query endpoint
# ---------------------------------------------------------------------------


def test_query_endpoint_http_and_memory_fallback(fresh, tmp_path):
    for store_dir in (str(tmp_path / "qstore"), None):
        col = TelemetryCollector(eval_interval=3600, rules=[],
                                 store_dir=store_dir)
        cli = tshipper.ShipperClient(col.addr)
        for i in range(6):
            cli.ship_snapshot("r0", _snap("paddle_tpu_serving_queue_depth",
                                          i, labels={"inst": "0"}))
            cli.ship_snapshot("r1", _snap("paddle_tpu_serving_queue_depth",
                                          10 + i, labels={"inst": "0"}))
        cli.close()
        srv = col.serve_http()
        try:
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/query?metric=paddle_tpu_serving_queue_depth"
                          "&labels=origin=r1").read())
            assert len(doc["series"]) == 1
            assert 'origin="r1"' in doc["series"][0]["key"]
            assert [p[1] for p in doc["series"][0]["points"]] == \
                [float(v) for v in range(10, 16)]
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/query?metric=paddle_tpu_serving_queue_depth"
                          "&step=3600").read())
            assert {len(s["points"]) for s in doc["series"]} == {1}
            assert doc["step"] == 3600.0
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/query")
            assert ei.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    srv.url + "/query?metric=m&from=notanumber")
            assert ei.value.code == 400
        finally:
            col.close()


# ---------------------------------------------------------------------------
# shipper failover + standby promotion (the HA pair)
# ---------------------------------------------------------------------------


def _crash_collector(col):
    """Stop a collector WITHOUT the clean-close path (no final state
    record, active segment left .open, sockets refused) — the
    in-process stand-in for kill -9; the drill does the real SIGKILL."""
    col._stop.set()
    try:
        col._ls.close()
    except OSError:
        pass
    col._eval_thread.join(timeout=5)
    col._seg.close()


def test_shipper_failover_zero_loss_and_standby_promotion(fresh, tmp_path):
    store_dir = str(tmp_path / "ha")
    rule = alerts.parse_rule(
        "hot", "paddle_tpu_serving_breaker_open > 0 for 0s",
        severity="page")
    primary = TelemetryCollector(eval_interval=3600, rules=[rule],
                                 store_dir=store_dir,
                                 flight_root=str(tmp_path / "flight"))
    standby = TelemetryCollector(eval_interval=3600, rules=[rule],
                                 store_dir=store_dir, standby=True,
                                 takeover_s=30.0)
    assert standby.is_standby
    # a standby without a store is a loud misconfiguration
    with pytest.raises(ValueError):
        TelemetryCollector(eval_interval=3600, standby=True)

    j = RunJournal()
    reg = MetricsRegistry()
    reg.gauge("paddle_tpu_serving_breaker_open", "h").set(1)
    # the env-var shape: comma-separated failover list
    addr_list = (f"{primary.host}:{primary.port},"
                 f"{standby.host}:{standby.port}")
    assert tshipper.parse_addrs(addr_list) == (primary.addr, standby.addr)
    sh = tshipper.Shipper(addr_list, origin="o1", journal=j, registry=reg,
                          flush_interval=3600, client_timeout=1.0)
    try:
        for i in range(6):
            j.emit("tick.n", i=i)
        sh.flush()
        trans = primary.evaluate_once()
        assert [t["state"] for t in trans] == ["firing"]
        fired_since = primary.engine.firing()[0]["since"]
        assert sh.counters()["failovers"] == 0

        # primary dies mid-stream (no clean close, heartbeat left
        # FRESH). The first failed-over push hits the split-brain
        # fence: the standby refuses to promote while the writer's
        # stamp is fresher than takeover_s — a transiently stalled
        # primary must not lose its log to an eager standby. The
        # shipper re-buffers; nothing is lost.
        _crash_collector(primary)
        for i in range(6, 12):
            j.emit("tick.n", i=i)
        sh.flush()   # fails on primary, rotates, REJECTED by the fence
        assert standby.is_standby
        c = sh.counters()
        assert c["failovers"] == 1 and c["flush_failures"] == 1

        # the writer's heartbeat goes silent past takeover_s: now the
        # failed-over push promotes. The tail the shipper never got
        # acked for is RESENT — the replayed high-water marks dedupe
        # the overlap.
        hb = primary._seg._heartbeat_path
        os.utime(hb, (time.time() - 60, time.time() - 60))
        sh.flush()

        c = sh.counters()
        assert c["flush_failures"] == 1   # the retried flush SUCCEEDED
        fams = {f.name: f for f in sh._families()}
        outcomes = {labels["outcome"]: v for labels, v in
                    fams["paddle_tpu_shipper_flushes_total"].samples}
        assert outcomes["failover"] >= 1 and outcomes["ok"] == 2

        # the standby auto-promoted on the failed-over push
        assert not standby.is_standby
        # zero shipped-event loss, exactly once, in order
        ticks = [e["i"] for e in standby.journal.recent(kind="tick.")
                 if e.get("origin") == "o1"]
        assert ticks == list(range(12))
        # the pre-kill firing alert is FIRING on the standby with its
        # original clock, and NO transition was journaled for it
        firing = standby.engine.firing()
        assert [a["rule"] for a in firing] == ["hot"]
        assert firing[0]["since"] == fired_since
        assert standby.journal.recent(kind="alert.") == []
        # the promoted standby keeps evaluating without a flap
        standby.evaluate_once()
        assert standby.journal.recent(kind="alert.") == []
        # and appends to the shared log: a THIRD collector recovering
        # from it sees the full merged history
        standby.evaluate_once()
    finally:
        sh.close(timeout=5)
        standby.close()
        primary.close()

    col3 = TelemetryCollector(eval_interval=3600, rules=[rule],
                              store_dir=store_dir)
    try:
        ticks = [e["i"] for e in col3.journal.recent(kind="tick.")
                 if e.get("origin") == "o1"]
        assert ticks == list(range(12))
        assert [a["rule"] for a in col3.engine.firing()] == ["hot"]
    finally:
        col3.close()


# ---------------------------------------------------------------------------
# alert-rule hot-reload
# ---------------------------------------------------------------------------


def test_reload_rules_lint_reject_and_state_carry(fresh, tmp_path):
    rules = [alerts.parse_rule(
        "hot", "paddle_tpu_serving_queue_depth > 5 for 0s"),
        alerts.parse_rule(
            "doomed", "paddle_tpu_serving_workers_busy > 0 for 0s")]
    col = TelemetryCollector(eval_interval=3600, rules=rules)
    cli = tshipper.ShipperClient(col.addr)
    try:
        cli.ship_snapshot("r0", _snap("paddle_tpu_serving_queue_depth", 9,
                                      labels={"inst": "0"}))
        cli.ship_snapshot("r0", _snap("paddle_tpu_serving_workers_busy", 2,
                                      labels={"inst": "0"}))
        trans = col.evaluate_once()
        assert sorted(t["rule"] for t in trans) == ["doomed", "hot"]

        # findings REJECT the reload: the running rules stay in force
        findings = col.reload_rules(specs=[
            {"name": "bad", "expr": "paddle_tpu_nope > 1 for 5s"}])
        assert findings and findings[0].startswith("alert:unknown-metric")
        assert {r.name for r in col.engine.rules} == {"hot", "doomed"}
        assert [e["kind"] for e in col.journal.recent(kind="alert.rules")] \
            == ["alert.rules_rejected"]

        # a clean pack swaps in: 'hot' keeps its FIRING instance (new
        # threshold applies next tick), 'doomed' resolves exactly once
        out = col.reload_rules(specs=[
            {"name": "hot",
             "expr": "paddle_tpu_serving_queue_depth > 100 for 0s"},
            {"name": "fresh",
             "expr": "rate(paddle_tpu_serving_errors_total[30s]) > 1 "
                     "for 30s"}])
        assert out == []
        kinds = [e["kind"] for e in col.journal.recent(kind="alert.")]
        assert kinds.count("alert.rules_reloaded") == 1
        assert kinds.count("alert.resolved") == 1   # doomed, on removal
        assert [a["rule"] for a in col.engine.firing()] == ["hot"]
        # next tick: the EDITED threshold takes effect -> hot resolves
        trans = col.evaluate_once()
        assert [(t["rule"], t["state"]) for t in trans] == \
            [("hot", "resolved")]
    finally:
        cli.close()
        col.close()


def test_post_rules_endpoint(fresh):
    col = TelemetryCollector(eval_interval=3600, rules=[])
    srv = col.serve_http()
    try:
        body = json.dumps([
            {"name": "shed",
             "expr": "rate(paddle_tpu_serving_rejected_total[30s]) > 1 "
                     "for 30s"}]).encode()
        req = urllib.request.Request(srv.url + "/rules", data=body,
                                     method="POST")
        doc = json.loads(urllib.request.urlopen(req).read())
        assert doc["accepted"] is True
        assert [r["name"] for r in doc["rules"]] == ["shed"]
        assert {r.name for r in col.engine.rules} == {"shed"}

        # findings: 422, engine untouched
        bad = json.dumps([{"name": "x", "expr": "nope("}]).encode()
        req = urllib.request.Request(srv.url + "/rules", data=bad,
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 422
        doc = json.loads(ei.value.read())
        assert doc["accepted"] is False and doc["findings"]
        assert {r.name for r in col.engine.rules} == {"shed"}

        # not-JSON body: 400, never a traceback
        req = urllib.request.Request(srv.url + "/rules", data=b"not json",
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
    finally:
        col.close()


def test_sighup_reloads_rules_in_daemon(fresh, tmp_path):
    """The daemon contract: SIGHUP re-lints the --rules file and
    hot-swaps the pack; a file with findings is rejected and the
    running rules stay."""
    from paddle_tpu.telemetry.collector import CollectorProcess

    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps([
        {"name": "first",
         "expr": "paddle_tpu_serving_queue_depth > 5 for 5s"}]))
    with CollectorProcess(rules_path=str(rules)) as cp:
        def rule_names():
            # a transient RST from the child's threaded HTTP daemon is
            # a retry, not a verdict (cross-process poll)
            for _ in range(10):
                try:
                    doc = json.loads(urllib.request.urlopen(
                        cp.http_url + "/alerts", timeout=10).read())
                    return [r["name"] for r in doc["rules"]]
                except (ConnectionError, urllib.error.URLError) as e:
                    last = e
                    time.sleep(0.3)
            raise AssertionError(
                f"collector /alerts unreachable (child rc="
                f"{cp._proc.poll()}, last={last!r})")

        assert rule_names() == ["first"]
        rules.write_text(json.dumps([
            {"name": "second",
             "expr": "paddle_tpu_serving_breaker_open > 0 for 10s"}]))
        os.kill(cp.pid, signal.SIGHUP)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and rule_names() != ["second"]:
            time.sleep(0.2)
        assert rule_names() == ["second"]

        # a broken file is REJECTED on SIGHUP: rules unchanged
        rules.write_text(json.dumps([{"name": "broken", "expr": "x >"}]))
        os.kill(cp.pid, signal.SIGHUP)
        time.sleep(1.0)
        assert rule_names() == ["second"]


# ---------------------------------------------------------------------------
# tools/series_dump.py contract
# ---------------------------------------------------------------------------


def test_series_dump_tool_contract(fresh, tmp_path, capsys):
    import importlib
    tool = importlib.import_module("tools.series_dump")

    store_dir = str(tmp_path / "dstore")
    col = TelemetryCollector(eval_interval=3600, rules=[],
                             store_dir=store_dir)
    cli = tshipper.ShipperClient(col.addr)
    for i in range(5):
        cli.ship_snapshot("r0", _snap("paddle_tpu_serving_queue_depth", i,
                                      labels={"inst": "0"}))
    cli.close()
    col._seg.rotate()
    col.close()

    assert tool.main([store_dir, "--list"]) == 0
    out = capsys.readouterr().out
    assert 'paddle_tpu_serving_queue_depth{inst="0",origin="r0"}' in out

    assert tool.main([store_dir, "--metric",
                      "paddle_tpu_serving_queue_depth",
                      "--labels", "origin=r0"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert [p[1] for p in doc["series"][0]["points"]] == \
        [0.0, 1.0, 2.0, 3.0, 4.0]

    assert tool.main([store_dir, "--metric",
                      "paddle_tpu_serving_queue_depth",
                      "--format", "csv"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("key,t,value") and out.count("\n") == 6

    assert tool.main([store_dir, "--validate"]) == 0
    # findings: a flipped byte in a sealed segment -> exit 2, named
    segs = sorted(p for p in os.listdir(store_dir) if p.endswith(".log"))
    faults.flip_byte(store_dir, segs[0])
    assert tool.main([store_dir, "--validate"]) == 2
    out = capsys.readouterr().out
    assert "checksum mismatch" in out or "CRC" in out
    # nothing to dump -> 2; not a store dir -> 2
    assert tool.main([store_dir, "--metric", "paddle_tpu_nope"]) == 2
    assert tool.main([str(tmp_path / "empty"), "--list"]) == 2


# ---------------------------------------------------------------------------
# the overhead pin: ingest hot path WITH persistence
# ---------------------------------------------------------------------------


DIM, CLASSES, BS = 6, 4, 4


def _net(x, label):
    from paddle_tpu import layers as L
    h = L.fc(x, 16, name="fc1")
    logits = L.fc(h, CLASSES, name="fc2")
    return {"loss": L.mean(L.softmax_with_cross_entropy(logits, label))}


def test_persisted_ingest_under_2pct_of_k16_dispatch(fresh, tmp_path):
    """The established telemetry pin extended to persistence: one
    EVENTS-batch ingest (dedupe + journal + CRC-framed write-through
    append) must cost under 2% of a measured K=16 fused dispatch."""
    from paddle_tpu.data.feeder import stack_batches

    prog = pt.build(_net)
    feed = {"x": np.zeros((BS, DIM), np.float32),
            "label": np.zeros((BS, 1), np.int64)}
    k, n = 16, 6
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(BS, DIM).astype(np.float32),
              "label": rng.randint(0, CLASSES, (BS, 1)).astype(np.int64)}
             for _ in range(4)]
    tr = pt.Trainer(prog, opt.SGD(0.1), loss_name="loss")
    tr.startup(sample_feed=feed)
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

    col = TelemetryCollector(eval_interval=3600, rules=[],
                             store_dir=str(tmp_path / "perf"))
    try:
        # realistic shape: the shipper flushes BATCHES (one journal
        # event per dispatch, many dispatches per 0.25s flush), so the
        # pin is per EVENT — dedupe + journal + CRC-framed append
        # amortized over a 16-event batch, vs one dispatch each
        reps, per_batch = 400, 16
        batches = [
            {"run": "r", "events": [
                {"run": "r", "seq": b * per_batch + i,
                 "sseq": b * per_batch + i, "t": 1.0,
                 "kind": "trainer.dispatch", "span": "s", "k": k}
                for i in range(1, per_batch + 1)]}
            for b in range(reps)]
        t0 = time.perf_counter()
        for body in batches:
            col._ingest_events("o-bench", body)
        per_event = (time.perf_counter() - t0) / (reps * per_batch)
        assert per_event < 0.02 * dispatch_s, (per_event, dispatch_s)
    finally:
        col.close()


def test_stats_verb_prices_store_ingest_over_the_wire(fresh, tmp_path):
    """``Shipper.collector_stats`` (the ``STATS`` verb) reads the
    attached collector's counters over the shipper's own socket: with
    persistence on, the store's ingest-writes (appends, bytes, append
    seconds) grow with what was shipped; a collector without a store
    reports ``persistence: False`` and no ``store``; an unreachable one
    gives None, never an exception."""
    with TelemetryCollector(eval_interval=3600,
                            store_dir=str(tmp_path / "log")) as col:
        sh = tshipper.ship_to(col.addr, origin="o-stats",
                              flush_interval=3600)
        before = sh.collector_stats()
        assert before["persistence"] is True
        for i in range(8):
            fresh.emit("a.b", span="s", n=i)
        sh.flush()
        after = sh.collector_stats()
        for key in ("appends", "bytes", "append_seconds"):
            assert after["store"][key] > before["store"][key], key
        assert after["store"] == col.stats()["store"]
        tshipper.stop_shipping()
    with TelemetryCollector(eval_interval=3600) as col:
        sh = tshipper.ship_to(col.addr, origin="o-stats",
                              flush_interval=3600)
        stats = sh.collector_stats()
        assert stats["persistence"] is False and "store" not in stats
    assert sh.collector_stats() is None  # collector gone
    tshipper.stop_shipping()


# ---------------------------------------------------------------------------
# the HA drill end to end (real SIGKILL)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_drill_collector_failover_contract(fresh):
    import importlib
    import tempfile

    fleet_drill = importlib.import_module("tools.fleet_drill")
    with tempfile.TemporaryDirectory(prefix="fd_colfail_") as root:
        violations = fleet_drill.drill_collector_failover(root, 2, 45)
    assert violations == []
