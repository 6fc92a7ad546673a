"""Serving-runtime fault-injection suite (paddle_tpu.serving +
io.Predictor validation/bucketing + atomic inference artifacts).

The acceptance contracts, all CPU + deterministic:

  * malformed requests raise typed InvalidRequest naming the field;
  * a saturated bounded queue rejects with ServerOverloaded (no
    deadlock, bounded memory);
  * after warmup, off-bucket request shapes cause ZERO new compiles
    (the AOT compile count is pinned) and in-bucket results are
    bit-identical to bare Predictor.run;
  * a hung dispatch trips the watchdog + circuit breaker, fails fast,
    and a half-open probe recovers the pool;
  * hot reload of a corrupt/canary-failing artifact rolls back with
    zero dropped in-flight requests;
  * save_inference_model commits atomically (crash points leave the
    previous artifact intact) and load_inference_model rejects
    torn/bit-flipped artifacts with CheckpointCorrupt.
"""

import os
import shutil
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import serving
from paddle_tpu.resilience import CheckpointCorrupt
from paddle_tpu.serving import (BreakerPolicy, CircuitOpen, DeadlineExceeded,
                                InvalidRequest, PredictorServer, ReloadFailed,
                                ServerClosed, ServerOverloaded, WorkerHung)
from paddle_tpu.testing import faults


def _feed(n, seed=0):
    rng = np.random.RandomState(seed)
    return {"image": rng.randn(n, 784).astype(np.float32),
            "label": rng.randint(0, 10, (n, 1)).astype(np.int64)}


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """One exported classifier with bucket set {4, 8}; everything else
    clones/copies it."""
    from paddle_tpu.models import mnist

    d = str(tmp_path_factory.mktemp("serving") / "model")
    prog = pt.build(mnist.mlp)
    feed8 = _feed(8)
    params, state = prog.init(jax.random.PRNGKey(0), **feed8)
    pio.save_inference_model(d, prog, params, state, feed8,
                             batch_buckets=[4, 8])
    return {"dir": d, "prog": prog, "params": params, "state": state,
            "feed8": feed8}


@pytest.fixture(scope="module")
def pred(artifact):
    return pio.load_inference_model(artifact["dir"])


# -- request validation ------------------------------------------------------


def test_predictor_run_validates_standalone(pred):
    feed8 = _feed(8)
    out = pred.run(feed8)
    assert np.asarray(out["logits"]).shape == (8, 10)
    # bucket 4 dispatches to its own precompiled executable
    assert np.asarray(pred.run(_feed(4))["logits"]).shape == (4, 10)

    with pytest.raises(InvalidRequest, match="label.*missing") as ei:
        pred.run({"image": feed8["image"]})
    assert ei.value.field == "label"
    with pytest.raises(InvalidRequest, match="extra_key.*not a feed"):
        pred.run({**feed8, "extra_key": np.zeros(3)})
    with pytest.raises(InvalidRequest, match="image.*shape"):
        pred.run({**feed8, "image": feed8["image"][:, :700]})
    with pytest.raises(InvalidRequest, match="label.*dtype") as ei:
        pred.run({**feed8, "label": feed8["label"].astype(np.float32)})
    assert ei.value.field == "label"
    # off-bucket batch: run() is strict (padding is the server's job)
    with pytest.raises(InvalidRequest, match="not a precompiled bucket"):
        pred.run(_feed(5))
    with pytest.raises(InvalidRequest, match="batch dim.*disagrees"):
        pred.run({"image": feed8["image"], "label": _feed(4)["label"]})


def test_server_rejects_nonfinite_payload(pred):
    with PredictorServer(pred, workers=1, queue_size=4) as srv:
        bad = _feed(8)
        bad["image"][3, 17] = np.nan
        with pytest.raises(InvalidRequest, match="image.*non-finite") as ei:
            srv.submit(bad)
        assert ei.value.field == "image"
        assert srv.metrics.snapshot()["rejected_invalid"] == 1
        # int feeds are never finite-scanned
        srv.run(_feed(8), timeout=60)


# -- bucketing + compile pin -------------------------------------------------


def test_off_bucket_rejected_compiles_pinned_inbucket_bitexact(pred):
    """The acceptance pin: warmed up, mixed traffic (in-bucket, padded,
    off-bucket-rejected) causes zero new compiles, and in-bucket answers
    are bit-identical to bare Predictor.run."""
    feed8 = _feed(8, seed=3)
    golden = np.asarray(pred.run(feed8)["logits"])
    with PredictorServer(pred, workers=2, queue_size=16,
                         golden_feed=feed8) as srv:
        before = pio.aot_compile_count()
        for _ in range(3):
            got = np.asarray(srv.run(feed8, timeout=60)["logits"])
            assert got.tobytes() == golden.tobytes()  # bit-identical
            out5 = srv.run(_feed(5, seed=4), timeout=60)  # padded to 8
            assert np.asarray(out5["logits"]).shape == (5, 10)
            with pytest.raises(InvalidRequest,
                               match="exceeds the largest precompiled"):
                srv.submit(_feed(16))
            with pytest.raises(InvalidRequest):
                srv.submit(_feed(0))
        rep = srv.report()
        assert pio.aot_compile_count() == before
        assert rep["compiles_since_warmup"] == 0
        assert rep["batch_buckets"] == [4, 8]


def test_padded_rows_match_unpadded(pred):
    """Padding up to a bucket must not perturb the real rows (rows are
    independent through the MLP)."""
    f3 = _feed(3, seed=5)
    with PredictorServer(pred, workers=1, queue_size=4) as srv:
        served = np.asarray(srv.run(f3, timeout=60)["logits"])
    f4 = {k: np.concatenate([v, np.zeros((1,) + v.shape[1:], v.dtype)])
          for k, v in f3.items()}
    direct = np.asarray(pred.run(f4)["logits"])[:3]
    np.testing.assert_allclose(served, direct, rtol=1e-6, atol=1e-6)


# -- bounded queue + deadlines -----------------------------------------------


def test_saturated_queue_rejects_no_deadlock(pred):
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=1, queue_size=2, warmup=False,
                          watchdog_timeout=30.0)
    try:
        f = _feed(8)
        first = srv.submit(f)          # occupies the lone worker
        for _ in range(40):            # wait for it to be dequeued
            if srv._queue.empty():
                break
            time.sleep(0.02)
        queued = [srv.submit(f), srv.submit(f)]   # fills the queue
        with pytest.raises(ServerOverloaded) as ei:
            srv.submit(f)
        assert ei.value.capacity == 2
        assert srv.health()["state"] == "overloaded"
        assert srv.metrics.snapshot()["rejected_overload"] == 1
        release.set()                  # unwedge: everything drains
        assert np.asarray(first.result(timeout=60)["logits"]).shape == (8, 10)
        for p in queued:
            p.result(timeout=60)
    finally:
        release.set()
        srv.close(drain=False, timeout=5)


def test_deadline_expired_in_queue_is_dropped(pred):
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=1, queue_size=4, warmup=False,
                          watchdog_timeout=30.0)
    try:
        f = _feed(8)
        blocker = srv.submit(f)
        expiring = srv.submit(f, deadline=0.05)
        time.sleep(0.2)                # deadline passes while queued
        release.set()
        blocker.result(timeout=60)
        with pytest.raises(DeadlineExceeded):
            expiring.result(timeout=60)
        assert srv.metrics.snapshot()["timeouts"] == 1
    finally:
        release.set()
        srv.close(drain=False, timeout=5)


# -- circuit breaker + watchdog ----------------------------------------------


def test_breaker_trips_fails_fast_and_half_open_recovers(pred):
    flaky = faults.failing_predictor(pred, fail_calls=3)
    srv = PredictorServer(flaky, workers=1, queue_size=8, warmup=False,
                          breaker=BreakerPolicy(failure_threshold=3,
                                                cooldown=0.2))
    try:
        f = _feed(8)
        for _ in range(3):
            with pytest.raises(RuntimeError, match="injected executable"):
                srv.run(f, timeout=60)
        assert srv.breaker.state == "open"
        assert srv.health()["state"] == "breaker_open"
        assert not srv.health()["ready"]
        with pytest.raises(CircuitOpen):        # fail fast, no queueing
            srv.submit(f)
        time.sleep(0.25)                        # cooldown elapses
        out = srv.run(f, timeout=60)            # the half-open probe
        assert np.asarray(out["logits"]).shape == (8, 10)
        assert srv.breaker.state == "closed"
        assert srv.health()["ready"]
        rep = srv.report()
        assert rep["breaker"]["trips"] == 1
        assert rep["errors"] == 3 and rep["rejected_breaker"] == 1
    finally:
        srv.close(drain=False, timeout=5)


def test_probe_failure_reopens(pred):
    flaky = faults.failing_predictor(pred, fail_calls=5)
    srv = PredictorServer(flaky, workers=1, queue_size=8, warmup=False,
                          breaker=BreakerPolicy(failure_threshold=2,
                                                cooldown=0.15))
    try:
        f = _feed(8)
        for _ in range(2):
            with pytest.raises(RuntimeError):
                srv.run(f, timeout=60)
        assert srv.breaker.state == "open"
        time.sleep(0.2)
        with pytest.raises(RuntimeError):       # probe fails (call #3)
            srv.run(f, timeout=60)
        assert srv.breaker.state == "open"      # re-opened
        with pytest.raises(CircuitOpen):
            srv.submit(f)
    finally:
        srv.close(drain=False, timeout=5)


def test_watchdog_hung_worker_trips_breaker_and_replaces(pred):
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=1, queue_size=4, warmup=False,
                          watchdog_timeout=0.2,
                          breaker=BreakerPolicy(failure_threshold=5,
                                                cooldown=0.2))
    try:
        f = _feed(8)
        hung = srv.submit(f)
        with pytest.raises(WorkerHung, match="watchdog"):
            hung.result(timeout=60)             # failed FAST, not at join
        assert srv.breaker.state == "open"      # one hang is conclusive
        m = srv.metrics.snapshot()
        assert m["hangs"] == 1 and m["workers_replaced"] == 1
        release.set()                           # executable recovers
        time.sleep(0.25)                        # cooldown
        out = srv.run(f, timeout=60)            # probe on the REPLACEMENT
        assert np.asarray(out["logits"]).shape == (8, 10)
        assert srv.breaker.state == "closed"
        assert srv.health()["ready"] and srv.health()["live"]
    finally:
        release.set()
        srv.close(drain=False, timeout=5)


def test_breaker_stale_probe_success_cannot_bypass_fresh_trip():
    """A half-open probe that HANGS, gets abandoned, and finally returns
    success after the watchdog tripped the breaker again must not close
    it — the fresh trip's cooldown holds."""
    from paddle_tpu.serving import CircuitBreaker

    b = CircuitBreaker(BreakerPolicy(failure_threshold=1, cooldown=0.05))
    b.record("pass", success=False)          # trip
    assert b.state == "open"
    time.sleep(0.06)
    tok = b.acquire()
    assert tok == "probe"
    b.trip()                                 # watchdog fires mid-probe
    b.record(tok, success=True)              # the stale probe success
    assert b.state == "open"                 # cooldown NOT bypassed
    # and a stale "pass" success can't either
    b.record("pass", success=True)
    assert b.state == "open"


def test_expired_probe_returns_slot_breaker_recovers(pred):
    """A half-open PROBE whose deadline expires while queued must return
    its slot — otherwise the breaker wedges in half_open and rejects
    every request forever."""
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=1, queue_size=4, warmup=False,
                          watchdog_timeout=30.0,
                          breaker=BreakerPolicy(failure_threshold=5,
                                                cooldown=0.05))
    try:
        f = _feed(8)
        blocker = srv.submit(f)              # worker busy (hangs)
        for _ in range(200):                 # wait until it is DEQUEUED
            if any(w.busy_since is not None for w in srv._workers):
                break
            time.sleep(0.01)
        srv.breaker.trip()                   # breaker opens meanwhile
        time.sleep(0.06)                     # cooldown elapses
        probe = srv.submit(f, deadline=0.01)  # THE half-open probe
        time.sleep(0.05)                     # its deadline passes queued
        release.set()                        # worker frees, dequeues probe
        with pytest.raises(DeadlineExceeded):
            probe.result(timeout=60)
        # slot returned: the NEXT request becomes the probe and recovers
        out = srv.run(f, timeout=60)
        assert np.asarray(out["logits"]).shape == (8, 10)
        assert srv.breaker.state == "closed"
        blocker.result(timeout=60)
    finally:
        release.set()
        srv.close(drain=False, timeout=5)


def test_raw_validation_error_returns_probe_slot(pred):
    """Validation can raise RAW numpy errors (ragged nested list) — the
    half-open probe slot must come back or the breaker wedges."""
    srv = PredictorServer(pred, workers=1, queue_size=4, warmup=False,
                          breaker=BreakerPolicy(failure_threshold=5,
                                                cooldown=0.05))
    try:
        srv.breaker.trip()
        time.sleep(0.06)                     # cooldown: next token = probe
        bad = dict(_feed(8))
        bad["image"] = [[1.0, 2.0], [3.0]]   # ragged: np.asarray raises
        with pytest.raises(Exception) as ei:
            srv.submit(bad)
        assert not isinstance(ei.value, (CircuitOpen, InvalidRequest))
        # the slot was returned: this request becomes the probe
        out = srv.run(_feed(8), timeout=60)
        assert np.asarray(out["logits"]).shape == (8, 10)
        assert srv.breaker.state == "closed"
    finally:
        srv.close(drain=False, timeout=5)


def test_drain_timeout_fails_stranded_queue(pred):
    """A drain that hits its timeout must fail still-queued requests
    with ServerClosed rather than stranding their clients forever."""
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=1, queue_size=8, warmup=False,
                          watchdog_timeout=30.0)
    try:
        f = _feed(8)
        blocker = srv.submit(f)
        queued = [srv.submit(f) for _ in range(3)]
        srv.close(drain=True, timeout=0.2)   # worker still hung: timeout
        for p in queued:
            assert p.done()
            with pytest.raises(ServerClosed):
                p.result(timeout=0)
        blocker  # in-flight on the hung worker; typed outcome either way
    finally:
        release.set()


def test_failed_reload_does_not_poison_compile_pin(artifact, pred, tmp_path):
    """A rolled-back reload AOT-compiled its candidate off the request
    path; the compiles_since_warmup contract signal must re-pin, not
    read as a permanent (false) request-path recompile."""
    d_nan = _export_variant(
        artifact, tmp_path, "vnan_pin",
        lambda p: jax.tree.map(lambda v: np.full_like(v, np.nan), p))
    srv = PredictorServer(pred, workers=1, queue_size=8,
                          golden_feed=artifact["feed8"])
    try:
        with pytest.raises(ReloadFailed):
            srv.reload(d_nan, block=True)
        srv.run(artifact["feed8"], timeout=60)
        assert srv.report()["compiles_since_warmup"] == 0
    finally:
        srv.close(drain=True, timeout=10)


def test_drain_completes_despite_abandoned_hung_worker(pred):
    """close(drain=True) must not spin on a watchdog-abandoned worker
    whose dispatch never returns (the SIGTERM drain path)."""
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=2, queue_size=8, warmup=False,
                          watchdog_timeout=0.2)
    try:
        hung = srv.submit(_feed(8))
        with pytest.raises(WorkerHung):
            hung.result(timeout=60)
        t0 = time.monotonic()
        srv.close(drain=True)                # no timeout: must still return
        assert time.monotonic() - t0 < 10.0
        assert srv.health()["state"] == "stopped"
    finally:
        release.set()


# -- hot reload ---------------------------------------------------------------


def _export_variant(artifact, tmp_path, name, mutate):
    """Re-export the module model with mutated params."""
    params = jax.tree.map(np.asarray, artifact["params"])
    params = mutate(params)
    d = str(tmp_path / name)
    pio.save_inference_model(d, artifact["prog"], params, artifact["state"],
                             artifact["feed8"], batch_buckets=[4, 8])
    return d


def test_hot_reload_swaps_with_zero_dropped_requests(artifact, pred, tmp_path):
    d2 = _export_variant(artifact, tmp_path, "v2",
                         lambda p: jax.tree.map(lambda v: v * 0.5, p))
    golden_new = np.asarray(pio.load_inference_model(d2).run(
        artifact["feed8"])["logits"])
    golden_old = np.asarray(pred.run(artifact["feed8"])["logits"])
    srv = PredictorServer(pred, workers=2, queue_size=16,
                          golden_feed=artifact["feed8"])
    results, errors = [], []
    stop_pump = threading.Event()

    def pump():
        while not stop_pump.is_set():
            try:
                out = srv.run(artifact["feed8"], timeout=60)
                results.append(np.asarray(out["logits"]))
            except BaseException as e:          # pragma: no cover
                errors.append(e)
                return

    t = threading.Thread(target=pump)
    t.start()
    try:
        time.sleep(0.05)                        # in-flight traffic exists
        srv.reload(d2, block=True)
        assert srv.generation == 2
        for _ in range(3):                      # post-swap traffic
            results_len = len(results)
            while len(results) == results_len and not errors:
                time.sleep(0.01)
        stop_pump.set()
        t.join(timeout=120)
        assert not errors                       # ZERO dropped in-flight
        assert len(results) >= 4
        # every answer is exactly old-model or new-model output — the
        # swap is atomic, no half-reloaded frankenmodel
        for r in results:
            assert (r.tobytes() == golden_old.tobytes()
                    or r.tobytes() == golden_new.tobytes())
        assert results[-1].tobytes() == golden_new.tobytes()
        assert srv.report()["compiles_since_warmup"] == 0  # re-pinned
        assert srv.metrics.snapshot()["reloads"] == 1
    finally:
        stop_pump.set()
        t.join(timeout=5)
        srv.close(drain=True, timeout=10)


def test_hot_reload_corrupt_artifact_rolls_back(artifact, pred, tmp_path):
    d2 = _export_variant(artifact, tmp_path, "v2c",
                         lambda p: jax.tree.map(lambda v: v * 0.5, p))
    faults.flip_byte(d2, "params.npz")          # silent bitrot
    srv = PredictorServer(pred, workers=1, queue_size=8,
                          golden_feed=artifact["feed8"])
    try:
        inflight = [srv.submit(artifact["feed8"]) for _ in range(3)]
        with pytest.raises(CheckpointCorrupt, match="checksum"):
            srv.reload(d2, block=True)
        assert srv.generation == 1              # rolled back
        for p in inflight:                      # zero dropped
            p.result(timeout=60)
        srv.run(artifact["feed8"], timeout=60)  # still serving gen 1
        m = srv.metrics.snapshot()
        assert m["reload_failures"] == 1 and m["reloads"] == 0
        assert isinstance(srv.last_reload_error, CheckpointCorrupt)
    finally:
        srv.close(drain=True, timeout=10)


def test_hot_reload_canary_failure_rolls_back(artifact, pred, tmp_path):
    d_nan = _export_variant(
        artifact, tmp_path, "vnan",
        lambda p: jax.tree.map(lambda v: np.full_like(v, np.nan), p))
    srv = PredictorServer(pred, workers=1, queue_size=8,
                          golden_feed=artifact["feed8"])
    try:
        with pytest.raises(ReloadFailed, match="non-finite"):
            srv.reload(d_nan, block=True)
        assert srv.generation == 1
        srv.run(artifact["feed8"], timeout=60)
    finally:
        srv.close(drain=True, timeout=10)


def test_hot_reload_custom_canary_check(artifact, pred, tmp_path):
    d2 = _export_variant(artifact, tmp_path, "v2k",
                         lambda p: jax.tree.map(lambda v: v * 0.5, p))
    srv = PredictorServer(pred, workers=1, queue_size=8,
                          golden_feed=artifact["feed8"],
                          canary_check=lambda out: False)
    try:
        with pytest.raises(ReloadFailed, match="canary_check"):
            srv.reload(d2, block=True)
        assert srv.generation == 1
    finally:
        srv.close(drain=True, timeout=10)


def test_reload_succeeds_with_off_bucket_golden_feed(artifact, pred, tmp_path):
    """A legal golden feed whose batch is not itself a bucket pads on
    submit and resizes in warmup — the canary must do the same, not
    fail every reload with an exact-bucket InvalidRequest."""
    d2 = _export_variant(artifact, tmp_path, "v2g",
                         lambda p: jax.tree.map(lambda v: v * 0.5, p))
    golden6 = {k: np.asarray(v)[:6] for k, v in artifact["feed8"].items()}
    srv = PredictorServer(pred, workers=1, queue_size=8, golden_feed=golden6)
    try:
        srv.reload(d2, block=True)
        assert srv.generation == 2
    finally:
        srv.close(drain=True, timeout=10)


def test_reload_rejects_feed_shape_drift(artifact, pred, tmp_path):
    """Same feed names + buckets but a drifted per-feed shape: queued
    in-flight requests validated against the old shapes would all fail
    on the new model — rejected before the swap."""
    feed700 = {"image": np.asarray(artifact["feed8"]["image"])[:, :700].copy(),
               "label": np.asarray(artifact["feed8"]["label"])}
    params700, state700 = artifact["prog"].init(jax.random.PRNGKey(1),
                                                **feed700)
    d_drift = str(tmp_path / "vdrift")
    pio.save_inference_model(d_drift, artifact["prog"],
                             jax.tree.map(np.asarray, params700), state700,
                             feed700, batch_buckets=[4, 8])
    srv = PredictorServer(pred, workers=1, queue_size=8,
                          golden_feed=artifact["feed8"])
    try:
        with pytest.raises(ReloadFailed, match="feed signature drifted"):
            srv.reload(d_drift, block=True)
        assert srv.generation == 1
        srv.run(artifact["feed8"], timeout=60)
    finally:
        srv.close(drain=True, timeout=10)


def test_reload_rejects_signature_drift(artifact, pred, tmp_path):
    """A candidate whose bucket set shrank would send in-flight bucket
    traffic off-bucket: rejected before the swap."""
    d_small = str(tmp_path / "vsmall")
    pio.save_inference_model(d_small, artifact["prog"],
                             jax.tree.map(np.asarray, artifact["params"]),
                             artifact["state"], artifact["feed8"])  # only {8}
    srv = PredictorServer(pred, workers=1, queue_size=8,
                          golden_feed=artifact["feed8"])
    try:
        with pytest.raises(ReloadFailed, match="bucket set shrank"):
            srv.reload(d_small, block=True)
        assert srv.generation == 1
    finally:
        srv.close(drain=True, timeout=10)


# -- drain + health -----------------------------------------------------------


def test_graceful_drain_completes_queued_work(pred):
    srv = PredictorServer(pred, workers=1, queue_size=16)
    pending = [srv.submit(_feed(8)) for _ in range(6)]
    srv.close(drain=True, timeout=60)
    assert all(p.done() for p in pending)
    for p in pending:
        assert np.asarray(p.result(timeout=0)["logits"]).shape == (8, 10)
    with pytest.raises(ServerClosed):
        srv.submit(_feed(8))
    h = srv.health()
    assert h["state"] == "stopped" and not h["live"] and not h["ready"]


def test_close_without_drain_fails_queued_fast(pred):
    release = threading.Event()
    hang = faults.hanging_predictor(pred, release, hang_calls=1)
    srv = PredictorServer(hang, workers=1, queue_size=8, warmup=False,
                          watchdog_timeout=30.0)
    f = _feed(8)
    blocker = srv.submit(f)
    queued = [srv.submit(f) for _ in range(3)]
    release.set()
    srv.close(drain=False, timeout=10)
    for p in queued:
        if p.done():
            with pytest.raises((ServerClosed, Exception)):
                p.result(timeout=0)
    blocker  # the in-flight one may have completed either way


def test_health_state_machine(pred):
    srv = PredictorServer(pred, workers=1, queue_size=4, start=False)
    assert srv.health()["state"] == "starting"
    with pytest.raises(ServerClosed, match="not started"):
        srv.submit(_feed(8))
    srv.start()
    h = srv.health()
    assert h["state"] == "ready" and h["ready"] and h["live"]
    assert h["workers"] == 1 and h["queue_capacity"] == 4
    srv.close(drain=True, timeout=30)
    assert srv.health()["state"] == "stopped"


def test_metrics_report_schema(pred):
    with PredictorServer(pred, workers=1, queue_size=4) as srv:
        srv.run(_feed(8), timeout=60)
        rep = srv.report()
    for key in ("submitted", "completed", "rejected_invalid",
                "rejected_overload", "rejected_breaker", "timeouts", "errors",
                "hangs", "workers_replaced", "reloads", "reload_failures",
                "latency_ms", "health", "breaker", "batch_buckets",
                "compiles_since_warmup"):
        assert key in rep, key
    assert rep["completed"] == 1
    assert rep["latency_ms"]["p50"] is not None
    assert rep["latency_ms"]["p99"] >= rep["latency_ms"]["p50"]


def test_preemption_handler_drains_server(pred):
    """The SIGTERM path: PreemptionHandler.on_signal kicks the drain —
    queued work completes, then the server is stopped."""
    import signal

    from paddle_tpu.resilience import PreemptionHandler

    srv = PredictorServer(pred, workers=1, queue_size=16)
    drained = threading.Event()
    with PreemptionHandler() as ph:
        ph.on_signal(lambda: (srv.close(drain=True), drained.set()))
        pending = [srv.submit(_feed(8)) for _ in range(4)]
        os.kill(os.getpid(), signal.SIGTERM)
        assert drained.wait(timeout=60)
    assert ph.requested
    for p in pending:
        p.result(timeout=0)                     # all completed, none dropped
    assert srv.health()["state"] == "stopped"


# -- atomic inference artifacts ----------------------------------------------


def test_save_inference_model_atomic_crash_points(artifact, tmp_path):
    d = str(tmp_path / "m")
    params = jax.tree.map(np.asarray, artifact["params"])
    pio.save_inference_model(d, artifact["prog"], params, artifact["state"],
                             artifact["feed8"])
    golden = np.asarray(
        pio.load_inference_model(d).run(artifact["feed8"])["logits"])
    for tag in ("save_inference_model:files-written",
                "save_inference_model:manifest-written"):
        with faults.crashing(tag):
            with pytest.raises(faults.InjectedCrash):
                pio.save_inference_model(
                    d, artifact["prog"],
                    jax.tree.map(lambda v: v * 2.0, params),
                    artifact["state"], artifact["feed8"])
        # the committed artifact is untouched by the torn overwrite
        got = np.asarray(
            pio.load_inference_model(d).run(artifact["feed8"])["logits"])
        assert got.tobytes() == golden.tobytes()
    # the two-rename overwrite window: a crash between rename-aside and
    # commit leaves the OLD artifact preserved under the .tmp.*.old
    # marker (never silently torn), and the next save recovers
    with faults.crashing("save_inference_model:committing"):
        with pytest.raises(faults.InjectedCrash):
            pio.save_inference_model(
                d, artifact["prog"], jax.tree.map(lambda v: v * 2.0, params),
                artifact["state"], artifact["feed8"])
    olds = [n for n in os.listdir(str(tmp_path)) if n.endswith(".old")]
    assert len(olds) == 1 and not os.path.exists(d)
    kept = np.asarray(pio.load_inference_model(
        str(tmp_path / olds[0])).run(artifact["feed8"])["logits"])
    assert kept.tobytes() == golden.tobytes()
    # recovery save restores the .old BEFORE sweeping — if it crashes
    # pre-commit itself, the previous artifact is back at the committed
    # path, never deleted while it is the only copy
    with faults.crashing("save_inference_model:files-written"):
        with pytest.raises(faults.InjectedCrash):
            pio.save_inference_model(
                d, artifact["prog"], jax.tree.map(lambda v: v * 2.0, params),
                artifact["state"], artifact["feed8"])
    restored = np.asarray(
        pio.load_inference_model(d).run(artifact["feed8"])["logits"])
    assert restored.tobytes() == golden.tobytes()
    # the next successful save sweeps the stale tmp dirs and commits
    pio.save_inference_model(d, artifact["prog"],
                             jax.tree.map(lambda v: v * 2.0, params),
                             artifact["state"], artifact["feed8"])
    assert not [n for n in os.listdir(str(tmp_path)) if ".tmp." in n]
    got = np.asarray(
        pio.load_inference_model(d).run(artifact["feed8"])["logits"])
    assert got.tobytes() != golden.tobytes()


def test_load_inference_model_rejects_torn_and_bitflipped(artifact, tmp_path):
    for fault, match in ((faults.truncate_file, "truncated"),
                         (faults.flip_byte, "checksum")):
        d = str(tmp_path / f"m_{fault.__name__}")
        shutil.copytree(artifact["dir"], d)
        fault(d, "params.npz")
        with pytest.raises(CheckpointCorrupt, match=match):
            pio.load_inference_model(d)
    # a flipped executable is caught too (manifest covers EVERY file)
    d = str(tmp_path / "m_hlo")
    shutil.copytree(artifact["dir"], d)
    faults.flip_byte(d, "model.stablehlo")
    with pytest.raises(CheckpointCorrupt):
        pio.load_inference_model(d)


def test_legacy_artifact_without_manifest_still_loads(artifact, tmp_path):
    d = str(tmp_path / "legacy")
    shutil.copytree(artifact["dir"], d)
    os.remove(os.path.join(d, "manifest.json"))
    p = pio.load_inference_model(d)
    assert np.asarray(p.run(artifact["feed8"])["logits"]).shape == (8, 10)


def test_predictor_fallback_logs_reason(artifact, monkeypatch, caplog):
    """The old SILENT AOT→jit fallback is now loud: the degradation to
    trace-on-request names the exception that caused it."""
    import logging

    def boom(exported):
        raise RuntimeError("no PJRT executable for you")

    monkeypatch.setattr(pio, "_aot_compile", boom)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.io"):
        p = pio.load_inference_model(artifact["dir"])
    assert any("AOT compile failed" in r.getMessage()
               for r in caplog.records)
    assert any("no PJRT executable for you" in r.getMessage()
               for r in caplog.records)
    # the fallback still serves (first call traces)
    assert np.asarray(p.run(artifact["feed8"])["logits"]).shape == (8, 10)


# -- spans and dispatch counters ---------------------------------------------


def test_every_dispatch_has_a_turn_with_its_children_and_counters_agree(pred):
    """Each dispatch leaves one ``serving.turn`` whose six children lie
    inside it in order; each request one ``serving.queued`` naming the
    dispatch that served it; ``dispatches`` / ``dispatched_rows`` /
    ``queued_seconds`` are the same facts as counters."""
    from paddle_tpu.core import profiler
    from paddle_tpu.fleet import BatchPolicy

    since = time.time_ns()
    with PredictorServer(pred, workers=1, queue_size=32,
                         batch_policy=BatchPolicy(max_wait_ms=30.0)) as srv:
        pending = [srv.submit(_feed(1, seed=i)) for i in range(6)]
        srv.run(_feed(8, seed=9), timeout=60)      # a lone, full dispatch
        for p in pending:
            p.result(timeout=60)
        snap = srv.metrics.snapshot()
        worker = srv._workers[0].thread.ident
    spans = [s for s in profiler.spans(since) if s[0].startswith("serving.")]
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert "serving.warmup" in by and {s[4]["bucket"] for s in
                                       by["serving.warmup"]} == {4, 8}

    turns = {s[4]["dispatch"]: s for s in by["serving.turn"]}
    assert len(turns) == len(by["serving.turn"]) == snap["dispatches"] >= 2
    assert sum(s[4]["rows"] for s in turns.values()) \
        == snap["dispatched_rows"] == 6 + 8
    assert sum(s[4]["requests"] for s in turns.values()) == 7
    children = ("serving.dequeue", "serving.coalesce", "serving.merge",
                "serving.run", "serving.block", "serving.reply")
    for n, turn in turns.items():
        assert turn[3] == worker and turn[4]["worker"] == 0
        assert turn[4]["bucket"] in (4, 8)
        mine = [next(s for s in by[c] if s[4].get("dispatch") == n)
                for c in children]
        assert all(s[3] == worker for s in mine)
        assert mine[0][1] == turn[1]                # from the worker free
        for a, b in zip(mine, mine[1:]):            # in order, not overlapping
            assert a[1] + a[2] <= b[1] + 50_000
        assert mine[-1][1] + mine[-1][2] <= turn[1] + turn[2] + 50_000

    queued = by["serving.queued"]
    submits = {s[4]["req"]: s for s in by["serving.submit"]}
    assert len(queued) == len(submits) == snap["completed"] == 7
    assert {s[4]["req"] for s in queued} == set(submits) \
        >= {p.span for p in pending}
    for q in queued:
        assert q[4]["dispatch"] in turns
        sub = submits[q[4]["req"]]
        assert q[3] == sub[3] == threading.get_ident()    # the caller's thread
        assert sub[1] <= q[1] <= sub[1] + sub[2]          # starts inside submit
        run = next(s for s in by["serving.run"]
                   if s[4]["dispatch"] == q[4]["dispatch"])
        assert q[1] + q[2] <= run[1] + 1_000_000          # ends at the dispatch
    assert snap["queued_seconds"] == pytest.approx(
        sum(q[2] for q in queued) / 1e9, abs=1e-6)
    # coalesced_* keep their meaning: only dispatches of more than one
    coalesced = [t for t in turns.values() if t[4]["requests"] > 1]
    assert snap["coalesced_batches"] == len(coalesced)
    assert snap["coalesced_requests"] == sum(t[4]["requests"] for t in coalesced)
    fams = {f.name: f for f in srv.metrics.telemetry_families("0")}
    for name in ("dispatches", "dispatched_rows", "queued_seconds"):
        assert f"paddle_tpu_serving_{name}_total" in fams
