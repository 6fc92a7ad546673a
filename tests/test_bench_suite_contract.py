"""The bench suite's driver contract (bench.py): priority ordering,
config registry consistency, result assembly, and quick-mode overrides
— pure-Python, no device. The driver records BENCH_r{N}.json from this
machinery; a silent drift here loses the round's record."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import bench
import pytest


@pytest.fixture(autouse=True)
def _no_ambient_filter(monkeypatch):
    # a leaked BENCH_ONLY debug setting must not skew the contract tests
    monkeypatch.delenv("BENCH_ONLY", raising=False)


def test_priority_order_leads_with_baseline_configs():
    names = bench._suite_names()
    assert names[:5] == ["mnist_mlp", "resnet50", "transformer", "bert",
                         "deepfm"]
    assert names[5:8] == ["resnet50_infer_bf16", "resnet50_infer_int8",
                          "resnet50_infer_fp32"]
    assert names[8] == "gpt"
    # every registered config appears exactly once
    expect = (set(bench.TRAIN_CONFIGS) | set(bench.INFER_CONFIGS)
              | {"gpt_decode", "dispatch_overhead", "guard_overhead",
                 "quantized_allreduce", "zero_sharding", "input_pipeline",
                 "device_cache", "serving", "serving_fleet", "autoscale",
                 "fusion_profile", "elastic_reshard"})
    assert set(names) == expect and len(names) == len(expect)


def test_bench_only_filter(monkeypatch):
    monkeypatch.setenv("BENCH_ONLY", "bert, gpt_decode")
    assert bench._suite_names() == ["bert", "gpt_decode"]


def test_result_key_mapping():
    assert bench._result_key("bert") == "bert_train"
    assert bench._result_key("resnet50_infer_int8") == "resnet50_infer_int8"
    assert bench._result_key("gpt_decode") == "gpt_decode"


def test_run_one_rejects_unknown_and_applies_quick_overrides(monkeypatch):
    with pytest.raises(ValueError, match="unknown config"):
        bench._run_one("nope", 1.0)
    seen = {}
    monkeypatch.setitem(bench.TRAIN_CONFIGS, "gpt_32k",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("gpt_32k", 1.0, quick=True)
    assert seen == {"iters": 2, "seq": 2048}  # QUICK_OVERRIDES applied


def test_steps_per_dispatch_knob_recorded(monkeypatch):
    """--steps_per_dispatch / BENCH_STEPS_PER_DISPATCH rides the env so
    suite children inherit it, and every train row records the K it was
    measured under (a K=16 row must never be read as a K=1 row)."""
    monkeypatch.setitem(bench.TRAIN_CONFIGS, "mnist_mlp",
                        lambda peak, **kw: {"value": 1.0})
    monkeypatch.setenv("BENCH_STEPS_PER_DISPATCH", "16")
    assert bench._run_one("mnist_mlp", 1.0)["steps_per_dispatch"] == 16
    monkeypatch.delenv("BENCH_STEPS_PER_DISPATCH")
    assert bench._run_one("mnist_mlp", 1.0)["steps_per_dispatch"] == 1
    # infer configs have no step loop: no knob recorded
    monkeypatch.setitem(bench.INFER_CONFIGS, "googlenet_infer",
                        lambda peak, **kw: {"value": 1.0})
    assert "steps_per_dispatch" not in bench._run_one("googlenet_infer", 1.0)


def test_dispatch_overhead_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_dispatch_overhead",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("dispatch_overhead", 1.0, quick=True)
    assert seen == {"iters": 8, "k": 4}


def test_guard_overhead_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_guard_overhead",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("guard_overhead", 1.0, quick=True)
    assert seen == {"iters": 8, "k": 4}


def test_quantized_allreduce_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_quantized_allreduce",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("quantized_allreduce", 1.0, quick=True)
    assert seen == {"iters": 8, "k": 4}
    assert bench._result_key("quantized_allreduce") == "quantized_allreduce"


def test_zero_sharding_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_zero_sharding",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("zero_sharding", 1.0, quick=True)
    assert seen == {"iters": 8, "k": 4}
    assert bench._result_key("zero_sharding") == "zero_sharding"


def test_input_pipeline_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_input_pipeline",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("input_pipeline", 1.0, quick=True)
    assert seen == {"iters": 8, "k": 4}
    assert bench._result_key("input_pipeline") == "input_pipeline"


def test_device_cache_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_device_cache",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("device_cache", 1.0, quick=True)
    assert seen == {"iters": 8, "k": 4, "link_delay_ms": 20.0}
    assert bench._result_key("device_cache") == "device_cache"


def test_device_cache_row_schema():
    """The device_cache row (HBM-cached vs streamed vs compute-only +
    the slow-link overlap A/B) pins its schema: the round records are
    read for the ROADMAP gate (delivered >= 0.9x compute-only when the
    dataset fits residual HBM) and the overlap delta, so the keys and
    the zero-wire-bytes pin must not drift. Runs the real row at a
    tiny config — the cells are the contract, not the magnitudes."""
    row = bench.bench_device_cache(1e12, batch_size=8, iters=4, k=2,
                                   link_delay_ms=15.0)
    for key in ("value", "unit", "step_time_ms", "cached_vs_streamed_x",
                "h2d_bytes_epoch1", "h2d_bytes_epoch2",
                "overlap_vs_blocking", "cache", "steps_per_dispatch"):
        assert key in row, key
    assert set(row["step_time_ms"]) == {"streamed", "cached",
                                        "compute_only"}
    ob = row["overlap_vs_blocking"]
    assert set(ob) == {"blocking_step_ms", "overlap_step_ms",
                       "speedup_x", "link_delay_ms"}
    # the cache really served epoch 2: zero wire bytes moved
    assert row["h2d_bytes_epoch1"] > 0
    assert row["h2d_bytes_epoch2"] == 0
    assert row["cache"]["state"] == "full"
    assert row["cache"]["hits"] > 0
    assert row["steps_per_dispatch"] == 2


def test_serving_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_serving",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("serving", 1.0, quick=True)
    assert seen == {"requests": 40}
    assert bench._result_key("serving") == "serving"


def test_fusion_profile_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_fusion_profile",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("fusion_profile", 1.0, quick=True)
    assert seen == {"iters": 2, "batch_size": 4, "seq": 64}
    assert bench._result_key("fusion_profile") == "fusion_profile"


def test_elastic_reshard_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_elastic_reshard",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("elastic_reshard", 1.0, quick=True)
    assert seen == {"iters": 1}
    assert bench._result_key("elastic_reshard") == "elastic_reshard"


def test_train_rows_carry_top_fusions(monkeypatch):
    """Every train row records its top-k fusion table (the regression-
    attribution contract: two BENCH records diff via
    tools/profile_diff.py by these rows' stable keys), and a fusion
    failure degrades to an error field, never a lost row."""
    table = [{"key": "dot|dense/matmul|f32[8,8]", "name": "dot.1",
              "op": "dot", "kind": "dot", "computation": "main",
              "in_loop": False, "flops": 1024.0, "bytes": 768,
              "out_bytes": 256, "source_ops": ["dense/matmul"],
              "cost_frac": 0.9}]

    class _T:
        feed_wire = None

        def fusion_report(self, feed, top_k=8):
            return {"top_fusions": table, "n_units": 12,
                    "coverage_top_k": 0.97, "temp_mb": 1.5}

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_T(), feed={"x": 1})
    assert row["top_fusions"] == table
    assert row["fusion_n_units"] == 12
    assert row["fusion_coverage_top_k"] == 0.97
    assert row["temp_mb"] == 1.5

    class _Broken(_T):
        def fusion_report(self, feed, top_k=8):
            raise RuntimeError("no HLO text on this backend")

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_Broken(), feed={"x": 1})
    assert "top_fusions" not in row
    assert "no HLO text" in row["top_fusions_error"]
    assert row["value"] > 0  # the row itself survived

    # BENCH_FUSIONS=0 opt-out: no fusion work attempted
    monkeypatch.setenv("BENCH_FUSIONS", "0")
    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_Broken(), feed={"x": 1})
    assert "top_fusions" not in row and "top_fusions_error" not in row


def test_train_rows_carry_telemetry_snapshot():
    """Every train row records the measured window's registry counter
    deltas per step under `telemetry` (what _time_trainer snapshots
    around the pipelined loop); a trainer without a measured window
    (stubbed/infer paths) records none — never a crash."""

    class _T:
        feed_wire = None
        _bench_telemetry = {
            'paddle_tpu_trainer_steps_total{inst="0"}': 1.0,
            'paddle_tpu_feeder_h2d_bytes_total{inst="0"}': 25088.0,
        }

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_T())
    assert row["telemetry"] == _T._bench_telemetry

    class _Bare:
        feed_wire = None

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_Bare())
    assert "telemetry" not in row and row["value"] > 0


def test_rows_carry_shipper_deltas_when_collector_attached(monkeypatch):
    """With a telemetry collector attached (PDTPU_TELEMETRY_ADDR /
    ship_to), train and serving rows additionally record the measured
    window's SHIPPER counter deltas (events shipped/dropped, flush
    seconds) under `shipper`; without one the key is absent — never a
    crash."""

    # train row: _time_trainer snapshots into trainer._bench_shipper
    class _T:
        feed_wire = None
        _bench_telemetry = {'paddle_tpu_trainer_steps_total{inst="0"}': 1.0}
        _bench_shipper = {"events_shipped": 1.0, "events_dropped": 0.0,
                          "flush_seconds": 0.0002}

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_T())
    assert row["shipper"] == _T._bench_shipper

    class _Bare:
        feed_wire = None

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_Bare())
    assert "shipper" not in row

    # serving row: per-variant deltas, keyed like `telemetry`
    class _FakeShipper:
        def __init__(self):
            self.n = 0

        def counters(self):
            self.n += 1
            return {"events_shipped": 40.0 * self.n,
                    "events_dropped": 0.0,
                    "flush_seconds": 0.002 * self.n}

    class _Server:
        def close(self, drain=True, timeout=None):
            pass

    fake = _FakeShipper()
    monkeypatch.setattr(bench, "_shipper_snapshot",
                        lambda: (fake, fake.counters()))
    monkeypatch.setattr(bench, "_serving_predictors",
                        lambda bs: {"fp32": ("P32", {"x": 1}),
                                    "int8": ("P8", {"x": 1})})
    monkeypatch.setattr(bench, "_make_server",
                        lambda pred, workers, queue_size: _Server())
    monkeypatch.setattr(bench, "_calibrate_serving",
                        lambda server, feed, iters=8: 0.002)
    monkeypatch.setattr(bench, "_drive_serving",
                        lambda server, feed, n, rate: ([0.004] * n, 0))
    row = bench.bench_serving(1.0, batch_size=8, requests=20, workers=2,
                              queue_size=4)
    assert set(row["shipper"]) == {"fp32", "int8"}
    for ship in row["shipper"].values():
        assert isinstance(ship, dict)
        assert all(isinstance(v, float) for v in ship.values())
        assert ship["events_shipped"] == 40.0 / 20   # delta per request

    # no shipper active: the serving row omits the key
    monkeypatch.setattr(bench, "_shipper_snapshot", lambda: (None, None))
    row = bench.bench_serving(1.0, batch_size=8, requests=20, workers=2,
                              queue_size=4)
    assert "shipper" not in row


def test_rows_carry_collector_store_deltas_when_persistence_on(monkeypatch):
    """With a PERSISTING collector attached (store_dir), train and
    serving rows additionally record the store's ingest-write cost
    over the measured window (appends/bytes/append_seconds per step or
    request) under `collector_store`; a collector without persistence
    — or a shipper without a reachable collector — omits the key."""

    # train row: _time_trainer snapshots into trainer._bench_store
    class _T:
        feed_wire = None
        _bench_telemetry = {'paddle_tpu_trainer_steps_total{inst="0"}': 1.0}
        _bench_shipper = {"events_shipped": 1.0}
        _bench_store = {"appends": 0.5, "bytes": 120.0,
                        "append_seconds": 1e-5}

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_T())
    assert row["collector_store"] == _T._bench_store

    class _NoStore:
        feed_wire = None
        _bench_shipper = {"events_shipped": 1.0}

    row = bench._result(8, "samples/sec", 1e-3, 1e-3, 1e6, 1e12,
                        trainer=_NoStore())
    assert "collector_store" not in row and row["shipper"]

    # the snapshot source: persistence off (or stats unreachable) -> None
    class _FakeShipper:
        def __init__(self, stats):
            self._stats = stats
            self.n = 0

        def counters(self):
            self.n += 1
            return {"events_shipped": 10.0 * self.n}

        def collector_stats(self):
            if self._stats is not None:
                self._stats = dict(self._stats)
                store = self._stats.get("store")
                if store:
                    self._stats["store"] = {
                        k: v * 2 for k, v in store.items()}
            return self._stats

    assert bench._store_snapshot(None) is None
    assert bench._store_snapshot(_FakeShipper(None)) is None
    assert bench._store_snapshot(
        _FakeShipper({"persistence": False})) is None
    snap = bench._store_snapshot(_FakeShipper(
        {"persistence": True,
         "store": {"appends": 4, "bytes": 100, "append_seconds": 0.001,
                   "segments": 2}}))
    assert snap == {"appends": 8.0, "bytes": 200.0,
                    "append_seconds": 0.002}

    # serving row: per-variant deltas keyed like `shipper`
    class _Server:
        def close(self, drain=True, timeout=None):
            pass

    fake = _FakeShipper({"persistence": True,
                         "store": {"appends": 4.0, "bytes": 100.0,
                                   "append_seconds": 0.001}})
    monkeypatch.setattr(bench, "_shipper_snapshot",
                        lambda: (fake, fake.counters()))
    monkeypatch.setattr(bench, "_serving_predictors",
                        lambda bs: {"fp32": ("P32", {"x": 1}),
                                    "int8": ("P8", {"x": 1})})
    monkeypatch.setattr(bench, "_make_server",
                        lambda pred, workers, queue_size: _Server())
    monkeypatch.setattr(bench, "_calibrate_serving",
                        lambda server, feed, iters=8: 0.002)
    monkeypatch.setattr(bench, "_drive_serving",
                        lambda server, feed, n, rate: ([0.004] * n, 0))
    row = bench.bench_serving(1.0, batch_size=8, requests=20, workers=2,
                              queue_size=4)
    assert set(row["collector_store"]) == {"fp32", "int8"}
    for store in row["collector_store"].values():
        assert set(store) == {"appends", "bytes", "append_seconds"}
        assert all(isinstance(v, float) for v in store.values())


def test_telemetry_counter_deltas_math():
    """counter_deltas is the snapshot's whole math: only moved series,
    normalized by the measured step/request count."""
    from paddle_tpu.telemetry import counter_deltas

    before = {"a": 10.0, "b": 5.0}
    after = {"a": 26.0, "b": 5.0, "c": 4.0}
    assert counter_deltas(before, after, per=8) == {"a": 2.0, "c": 0.5}
    assert counter_deltas(before, after) == {"a": 16.0, "c": 4.0}


def test_serving_row_schema(monkeypatch):
    """The serving row (PredictorServer steady p50/p99 + saturated
    reject rate, fp32 vs int8) pins its schema: downstream readers
    compare rounds by these exact keys. Export/server/driver are
    stubbed — the assembly math is pure python."""

    class _Server:
        def close(self, drain=True, timeout=None):
            pass

    monkeypatch.setattr(bench, "_serving_predictors",
                        lambda bs: {"fp32": ("P32", {"x": 1}),
                                    "int8": ("P8", {"x": 1})})
    monkeypatch.setattr(bench, "_make_server",
                        lambda pred, workers, queue_size: _Server())
    monkeypatch.setattr(bench, "_calibrate_serving",
                        lambda server, feed, iters=8: 0.002)
    monkeypatch.setattr(
        bench, "_drive_serving",
        # saturated phase (rate > capacity) rejects half the offered load
        lambda server, feed, n, rate: ([0.004] * n,
                                       n // 2 if rate > 1000.0 else 0))
    row = bench.bench_serving(1.0, batch_size=8, requests=20, workers=2,
                              queue_size=4)
    for key in ("value", "unit", "latency_ms", "reject_rate_saturated",
                "offered_rps", "telemetry", "requests", "workers",
                "queue_size", "batch_size"):
        assert key in row, key
    # the telemetry snapshot is per-variant: steady-phase registry
    # counter deltas per offered request (dict of series -> delta)
    assert set(row["telemetry"]) == {"fp32", "int8"}
    for tel in row["telemetry"].values():
        assert isinstance(tel, dict)
        assert all(isinstance(v, float) for v in tel.values())
    assert set(row["latency_ms"]) == {"fp32", "int8"}
    for v in row["latency_ms"].values():
        assert set(v) == {"p50", "p99"}
    assert row["value"] == row["latency_ms"]["fp32"]["p99"] == 4.0
    # capacity = 2 workers / 2ms = 1000 rps: steady at 600 keeps 0
    # rejects, saturated at 3000 sheds half
    assert row["reject_rate_saturated"] == {"fp32": 0.5, "int8": 0.5}
    assert row["offered_rps"]["fp32"]["steady_rps"] == 600.0
    assert row["offered_rps"]["fp32"]["saturated_rps"] == 3000.0


def test_serving_fleet_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_serving_fleet",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("serving_fleet", 1.0, quick=True)
    assert seen == {"requests": 60, "replicas": 2}
    assert bench._result_key("serving_fleet") == "serving_fleet"


def test_autoscale_quick_overrides(monkeypatch):
    seen = {}
    monkeypatch.setattr(bench, "bench_autoscale",
                        lambda peak, **kw: seen.update(kw) or {"v": 1})
    bench._run_one("autoscale", 1.0, quick=True)
    assert seen == {"low_s": 0.8, "burst_s": 1.5, "max_replicas": 2}
    assert bench._result_key("autoscale") == "autoscale"


def test_autoscale_row_schema(monkeypatch):
    """The autoscale row (closed-loop autoscaler vs statically
    peak-provisioned fleet over the same diurnal curve) pins its
    schema: rounds are compared by the p99 + worker-seconds-per-1k +
    SLO-attainment cells, so the keys must not drift. Artifact/front/
    driver/variant-runner are stubbed — the assembly math is pure
    python."""

    class _Front:
        def close(self, drain=True, timeout=None):
            pass

    monkeypatch.setattr(bench, "_fleet_artifact",
                        lambda bs: ("DIR", {"x": 1}))
    monkeypatch.setattr(
        bench, "_make_fleet_front",
        lambda dirname, variant, replicas, workers, queue_size,
        max_wait_ms: _Front())
    # one replica's measured coalesced capacity: 500 rps
    monkeypatch.setattr(bench, "_saturation_probe",
                        lambda front, feed, n=128, inflight=16: 500.0)
    info_by_variant = {
        "fixed": {"provisioned": 3, "peak_replicas": 3},
        "autoscaled": {"provisioned": 1, "peak_replicas": 3,
                       "scale_ups": 2, "scale_downs": 2},
    }
    # fixed burns 3 workers the whole elapsed 10s; autoscaled 16 ws
    ws_by_variant = {"fixed": 30.0, "autoscaled": 16.0}
    lat_by_variant = {"fixed": 0.004, "autoscaled": 0.006}

    def run_variant(dirname, variant, max_replicas, workers, queue_size,
                    max_wait_ms, feed, phases):
        n = sum(k for k, _ in phases)
        return ([lat_by_variant[variant]] * n, 0, 10.0,
                ws_by_variant[variant], info_by_variant[variant])

    monkeypatch.setattr(bench, "_run_autoscale_variant", run_variant)
    row = bench.bench_autoscale(1.0, batch_size=8, low_s=2.0, burst_s=4.0,
                                max_replicas=3, workers=1, queue_size=4,
                                max_wait_ms=2.0, slo_ms=50.0)
    for key in ("value", "unit", "latency_ms", "worker_seconds_per_1k",
                "slo_attainment", "slo_ms", "reject_rate", "scale",
                "offered_rps", "phases", "requests", "max_replicas",
                "workers", "queue_size", "batch_size", "max_wait_ms"):
        assert key in row, key
    variants = {"fixed", "autoscaled"}
    for per_variant in ("latency_ms", "worker_seconds_per_1k",
                        "slo_attainment", "reject_rate", "scale"):
        assert set(row[per_variant]) == variants, per_variant
    for v in row["latency_ms"].values():
        assert set(v) == {"p50", "p99"}
    assert row["value"] == row["latency_ms"]["autoscaled"]["p99"] == 6.0
    # curve: low = 0.4 * 500 = 200 rps for 2s (400 reqs) twice, burst
    # = 2.5 * 500 = 1250 rps for 4s (5000 reqs)
    assert row["offered_rps"] == {"low": 200.0, "burst": 1250.0}
    assert row["requests"] == 400 + 5000 + 400
    # worker-seconds per 1k completed: ws / n * 1000
    assert row["worker_seconds_per_1k"]["fixed"] == round(
        30.0 / 5800 * 1000, 2)
    assert row["worker_seconds_per_1k"]["autoscaled"] == round(
        16.0 / 5800 * 1000, 2)
    # 4/6ms latencies both inside the 50ms SLO
    assert row["slo_attainment"] == {"fixed": 1.0, "autoscaled": 1.0}
    assert row["scale"]["autoscaled"]["scale_ups"] == 2
    assert row["scale"]["fixed"]["provisioned"] == 3


def test_serving_fleet_row_schema(monkeypatch):
    """The serving_fleet row (p99 + throughput/worker at 3x saturation
    for single-process vs fleet vs coalesced-fleet, with the
    fleet-vs-single and coalesced-vs-pad-alone deltas) pins its schema:
    downstream readers compare rounds by these exact keys. Artifact/
    front/driver are stubbed — the assembly math is pure python."""

    class _Front:
        def close(self, drain=True, timeout=None):
            pass

    monkeypatch.setattr(bench, "_fleet_artifact",
                        lambda bs: ("DIR", {"x": 1}))
    monkeypatch.setattr(
        bench, "_make_fleet_front",
        lambda dirname, variant, replicas, workers, queue_size,
        max_wait_ms: _Front())
    monkeypatch.setattr(bench, "_calibrate_serving",
                        lambda front, feed, iters=8: 0.002)
    lat_by_variant = {"single": 0.004, "fleet": 0.003,
                      "fleet_coalesced": 0.002}
    calls = []

    def drive(front, feed, n, rate):
        variant = ("single", "fleet", "fleet_coalesced")[len(calls)]
        calls.append(rate)
        # every variant completes all n in n/100 s, at its own latency
        return [lat_by_variant[variant]] * n, 0, n / 100.0

    monkeypatch.setattr(bench, "_drive_fleet", drive)
    row = bench.bench_serving_fleet(1.0, batch_size=8, requests=20,
                                    replicas=2, workers=1, queue_size=4,
                                    max_wait_ms=2.0)
    for key in ("value", "unit", "latency_ms", "throughput_per_worker_rps",
                "reject_rate", "deltas", "telemetry", "offered_rps",
                "requests", "replicas", "workers", "queue_size",
                "batch_size", "max_wait_ms"):
        assert key in row, key
    variants = {"single", "fleet", "fleet_coalesced"}
    assert set(row["latency_ms"]) == variants
    assert set(row["telemetry"]) == variants
    for v in row["latency_ms"].values():
        assert set(v) == {"p50", "p99"}
    # calibrated ONCE on the single front: 3x * 2 workers / 2ms = 3000
    # rps offered to every variant
    assert calls == [3000.0] * 3
    assert row["offered_rps"] == 3000.0
    # completed 20 in 0.2s over 2 workers = 50 rps/worker everywhere
    assert row["throughput_per_worker_rps"] == {
        "single": 50.0, "fleet": 50.0, "fleet_coalesced": 50.0}
    assert row["value"] == row["latency_ms"]["fleet_coalesced"]["p99"] == 2.0
    d = row["deltas"]
    assert set(d) == {"fleet_vs_single", "coalesced_vs_pad_alone"}
    assert d["fleet_vs_single"]["p99_ms"] == 3.0 - 4.0
    assert d["coalesced_vs_pad_alone"]["p99_ms"] == 2.0 - 3.0
    assert d["fleet_vs_single"]["throughput_per_worker_ratio"] == 1.0


def test_input_pipeline_row_schema(monkeypatch):
    """The input_pipeline row (fp32 vs bf16 vs uint8 wire at K=1/K=16)
    pins its schema here: the driver's round records are read by byte
    math downstream, so the wire/logical byte fields and the per-cell
    step-time keys must not silently drift. Timing and Trainer are
    stubbed — the byte math is pure python."""
    monkeypatch.setattr(bench, "_time_trainer",
                        lambda tr, feeds, **kw: (1e-3, 1e-3))

    class _T:
        feed_wire = None

        def startup(self, **kw):
            pass

    import paddle_tpu as pt
    monkeypatch.setattr(pt, "Trainer", lambda *a, **kw: _T())
    row = bench.bench_input_pipeline(1.0, batch_size=8, iters=2, k=2)
    for key in ("value", "unit", "step_time_ms", "feed_wire_bytes_per_step",
                "feed_logical_bytes_per_step", "steps_per_dispatch",
                "speedup_uint8_vs_fp32_k1", "speedup_uint8_vs_fp32_fused",
                "speedup_bf16_vs_fp32_fused"):
        assert key in row, key
    assert row["steps_per_dispatch"] == 2  # names the K "fused" measured
    # the acceptance lever: uint8 wire cuts >= 3.5x off the fp32 bytes
    assert row["value"] >= 3.5
    b = row["feed_wire_bytes_per_step"]
    assert b["fp32"] > b["bf16"] > b["uint8"]
    assert set(row["step_time_ms"]) == {f"{v}_k{kk}" for v in
                                        ("fp32", "bf16", "uint8")
                                        for kk in (1, 2)}


def test_assemble_headline_and_partial_shape():
    configs = {
        "mnist_mlp_train": {"mfu": 0.4, "value": 1.0},
        "bert_train": {"mfu": 0.55, "value": 2.0},
        "resnet50_train": {"mfu": 0.5, "value": 3.0, "vs_baseline": 24.0},
        "resnet50_infer_bf16": {"mfu": 0.9, "value": 4.0},  # infer: no headline
        "broken_train": {"error": "Timeout"},
    }
    res = bench._assemble(configs, "TPU v5 lite", 197e12, "table", "bfloat16")
    assert res["metric"] == "suite"
    assert res["value"] == 0.55          # max TRAIN mfu only
    assert res["vs_baseline"] == 24.0    # resnet50 ratio carried up
    assert res["device"] == "TPU v5 lite"
    assert res["configs"] is configs


def test_assemble_degraded_link_uses_compute_only():
    """Below LINK_DEGRADED_MBPS the pipelined numbers measure the
    link, not the framework: the headline must switch to the
    compute-only variant, say so in the unit, and flag the record."""
    configs = {
        "bert_train": {"mfu": 0.01, "mfu_compute_only": 0.55, "value": 2.0},
        "resnet50_train": {"mfu": 0.002, "mfu_compute_only": 0.3, "value": 3.0,
                           "compute_only": 2000.0, "vs_baseline": 0.2},
    }
    res = bench._assemble(configs, "TPU v5 lite", 197e12, "table", "bfloat16",
                          h2d_mbps=12.0)
    assert res["link_degraded"] is True
    assert res["value"] == 0.55
    assert "compute-only" in res["unit"]
    assert res["vs_baseline"] == round(2000.0 / bench.BASELINES["resnet50"], 2)
    # healthy link: pipelined headline, no flag
    res2 = bench._assemble(configs, "TPU v5 lite", 197e12, "table", "bfloat16",
                           h2d_mbps=8000.0)
    assert "link_degraded" not in res2 and res2["value"] == 0.01
    assert res2["unit"] == "MFU"


def test_baselines_match_baseline_md_rows():
    # the ratios the suite reports are anchored to these exact numbers
    assert bench.BASELINES["resnet50"] == 81.69
    assert bench.BASELINES["resnet50_infer_fp32"] == 217.69
    assert bench.BASELINES["googlenet_infer"] == 600.94
    assert abs(bench.BASELINES["lstm_big"] - 256 / 1.655) < 1e-9


def test_load_mid_round_picks_latest_valid(tmp_path):
    import json
    (tmp_path / "BENCH_mid_r03.json").write_text(json.dumps(
        {"configs": {"a_train": {"mfu": 0.1, "value": 1.0}}}))
    (tmp_path / "BENCH_mid_r04.json").write_text(json.dumps(
        {"configs": {"b_train": {"mfu": 0.2, "value": 2.0}}}))
    rec = bench._load_mid_round(root=str(tmp_path))
    assert "b_train" in rec["configs"]
    assert rec["_source"] == "BENCH_mid_r04.json"
    # a corrupt latest file falls through to the previous one
    (tmp_path / "BENCH_mid_r05.json").write_text("{not json")
    rec = bench._load_mid_round(root=str(tmp_path))
    assert rec["_source"] == "BENCH_mid_r04.json"
    assert bench._load_mid_round(root=str(tmp_path / "empty")) is None


def test_backfill_fills_only_holes(monkeypatch):
    """A live row (even a slow one) beats a carried row; errored and
    missing rows are backfilled from the mid-round record with the
    provenance marker so the judge can tell which is which."""
    mid = {"configs": {
        "resnet50_train": {"mfu": 0.3, "value": 2000.0},
        "bert_train": {"mfu": 0.4, "value": 5.0},
        "gpt_train": {"error": "timeout 600s"},   # errored mid rows never carry
    }}
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    configs = {
        "resnet50_train": {"mfu": 0.1, "value": 900.0},  # live wins
        "bert_train": {"error": "Timeout: config exceeded 600s"},
    }
    bench._backfill_from_mid_round(configs)
    assert configs["resnet50_train"]["value"] == 900.0
    assert "carried_from_mid_round" not in configs["resnet50_train"]
    assert configs["bert_train"]["value"] == 5.0
    assert configs["bert_train"]["carried_from_mid_round"] is True
    assert "exceeded 600s" in configs["bert_train"]["live_error"]
    assert "gpt_train" not in configs
    # mid record untouched (backfill must copy, not alias)
    assert "carried_from_mid_round" not in mid["configs"]["bert_train"]


def test_probe_fail_falls_back_to_mid_round(monkeypatch):
    # h2d None (the mid-round probe died before the bandwidth read) must
    # still force the compute-only headline: a failed probe IS a dead link
    mid = {"configs": {"bert_train": {"mfu": 0.01, "mfu_compute_only": 0.5,
                                      "value": 5.0}},
           "device": "TPU v5 lite", "peak_flops": 197e12,
           "peak_source": "table", "host_to_device_mbps": None,
           "compute_dtype": "bfloat16", "_source": "BENCH_mid_r04.json"}
    monkeypatch.setattr(bench, "_probe_device", lambda *a, **k: (None, None))
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    res = bench.run_suite()
    assert res["link_down_at_suite_time"] is True
    assert res["value"] == 0.5            # dead link -> compute-only
    assert "compute-only" in res["unit"]
    assert res["host_to_device_mbps"] is None
    assert res["configs"]["bert_train"]["carried_from_mid_round"] is True
    assert "mid-round" in res["note"]
    # no mid record at all: the old explicit-error record
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: None)
    res = bench.run_suite()
    assert "device probe failed" in res["error"]


def test_backfill_respects_scheduled_scope(monkeypatch):
    """BENCH_ONLY debug runs must not sprout rows they never attempted."""
    mid = {"configs": {"resnet50_train": {"mfu": 0.3, "value": 2000.0},
                       "bert_train": {"mfu": 0.4, "value": 5.0}}}
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    configs = {"mnist_mlp_train": {"mfu": 0.0, "value": 8000.0}}
    bench._backfill_from_mid_round(configs, scheduled={"mnist_mlp_train"})
    assert set(configs) == {"mnist_mlp_train"}


def test_backfill_never_carries_ab_variant_rows(monkeypatch):
    """chip_queue's A/B rows (key@variant) live in the mid record for
    the judge but must not leak into suite records: the suite never
    measures variant keys, so a carried one would persist forever."""
    mid = {"configs": {
        "transformer_train": {"mfu": 0.3, "value": 2000.0},
        "transformer_train@no_flash": {"mfu": 0.2, "value": 1500.0},
    }}
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    configs = {}
    bench._backfill_from_mid_round(configs,
                                   scheduled={"transformer_train"})
    assert set(configs) == {"transformer_train"}
    # unscoped (signal-handler) path skips variants too
    configs = {}
    bench._backfill_from_mid_round(configs)
    assert set(configs) == {"transformer_train"}


def test_assemble_carried_rows_never_drive_headline():
    """The one-line headline reflects the code under test: carried
    (prior-capture) rows are excluded from the max unless NO live train
    row was measured at all — and then the unit discloses it."""
    configs = {
        "bert_train": {"mfu": 0.9, "value": 5.0,
                       "carried_from_mid_round": True},
        "transformer_train": {"mfu": 0.2, "value": 2.0},
    }
    res = bench._assemble(configs, "TPU v5 lite", 197e12, "table", "bfloat16")
    assert res["value"] == 0.2                     # live row wins
    assert res["unit"] == "MFU"
    assert res["carried_configs"] == ["bert_train"]
    # all rows carried: headline falls back to them, unit says so
    res2 = bench._assemble(
        {"bert_train": configs["bert_train"]},
        "TPU v5 lite", 197e12, "table", "bfloat16")
    assert res2["value"] == 0.9
    assert "carried from mid-round" in res2["unit"]


def test_all_error_mid_record_yields_explicit_error(monkeypatch):
    """A mid record whose rows are ALL errors must not produce a
    success-shaped empty record on probe failure."""
    mid = {"configs": {"bert_train": {"error": "timeout"}},
           "compute_dtype": "bfloat16", "_source": "BENCH_mid_r04.json"}
    monkeypatch.setattr(bench, "_probe_device", lambda *a, **k: (None, None))
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    res = bench.run_suite()
    assert "error" in res and res["value"] == 0.0


def test_mid_record_dtype_and_quick_gating(monkeypatch):
    """Carried rows only make sense under the same measurement settings:
    quick mode and a different compute_dtype both disable the fallback."""
    mid = {"configs": {"bert_train": {"mfu": 0.5, "mfu_compute_only": 0.5,
                                      "value": 5.0}},
           "compute_dtype": "bfloat16", "_source": "BENCH_mid_r04.json"}
    monkeypatch.setattr(bench, "_probe_device", lambda *a, **k: (None, None))
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    assert "error" in bench.run_suite(compute_dtype="float32")
    assert "error" in bench.run_suite(quick=True)
    assert "error" not in bench.run_suite()   # matching settings: fallback


def test_assemble_live_headline_drops_carried_vs_baseline():
    configs = {
        "resnet50_train": {"mfu": 0.3, "value": 2000.0, "vs_baseline": 24.0,
                           "carried_from_mid_round": True},
        "transformer_train": {"mfu": 0.2, "value": 2.0},
    }
    res = bench._assemble(configs, "TPU v5 lite", 197e12, "table", "bfloat16")
    assert res["value"] == 0.2 and res["vs_baseline"] is None
    # fully-carried record: the ratio is allowed (unit already discloses)
    res2 = bench._assemble(
        {"resnet50_train": configs["resnet50_train"]},
        "TPU v5 lite", 197e12, "table", "bfloat16")
    assert res2["vs_baseline"] == 24.0


def test_unstamped_mid_record_rejected(monkeypatch):
    """A mid record with no compute_dtype field is a mismatch: rows of
    unknown dtype must not be presented as this run's compute_dtype."""
    mid = {"configs": {"bert_train": {"mfu": 0.5, "mfu_compute_only": 0.5,
                                      "value": 5.0}},
           "_source": "BENCH_mid_r04.json"}
    monkeypatch.setattr(bench, "_probe_device", lambda *a, **k: (None, None))
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: mid)
    assert "error" in bench.run_suite()


def test_load_mid_round_normalizes_envelope_rows(tmp_path):
    import json
    (tmp_path / "BENCH_mid_r04.json").write_text(json.dumps(
        {"configs": {"bert_train": {"result": {"mfu": 0.4, "value": 7.0},
                                    "device": "TPU v5 lite"}}}))
    rec = bench._load_mid_round(root=str(tmp_path))
    assert rec["configs"]["bert_train"] == {"mfu": 0.4, "value": 7.0}


def test_timed_out_configs_get_one_retry(monkeypatch):
    """The persistent compile cache makes attempt 1's compile reusable,
    so the suite retries each timed-out config once; a successful retry
    replaces the timeout row."""
    import subprocess as sp

    monkeypatch.setenv("BENCH_ONLY", "mnist_mlp")
    monkeypatch.setattr(bench, "_probe_device",
                        lambda *a, **k: ("TPU v5 lite", 9000.0))
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: None)
    calls = []

    class FakeChild:
        def __init__(self, attempt):
            self.attempt = attempt
            self.returncode = 0

        def communicate(self, timeout=None):
            if self.attempt == 0 and timeout is not None:
                # the post-kill reap calls communicate() with no timeout
                raise sp.TimeoutExpired("cmd", timeout)
            if self.attempt == 0:
                return ("", "")
            import json
            return (json.dumps({"result": {"value": 1.0, "unit": "u",
                                           "mfu": 0.5},
                                "device": "TPU v5 lite",
                                "peak_flops": 197e12,
                                "peak_source": "table"}) + "\n", "")

        def poll(self):
            return self.returncode

        def kill(self):
            pass

    def fake_popen(cmd, **kw):
        child = FakeChild(len(calls))
        calls.append(cmd)
        return child

    monkeypatch.setattr(sp, "Popen", fake_popen)
    res = bench.run_suite()
    assert len(calls) == 2                     # attempt + one retry
    assert res["configs"]["mnist_mlp_train"]["mfu"] == 0.5
    assert "timed_out" not in res["configs"]["mnist_mlp_train"]
    assert res["value"] == 0.5


def test_assemble_strips_retry_marker():
    configs = {"bert_train": {"error": "Timeout: ...", "timed_out": True}}
    res = bench._assemble(configs, "TPU", 197e12, "table", "bfloat16")
    assert "timed_out" not in res["configs"]["bert_train"]


def test_child_deadline_timeouts_also_retry(monkeypatch):
    """A child-side _ConfigTimeout (SIGALRM deadline inside the config
    subprocess) is the same rescue case as a parent-level kill: the
    retry pass must pick it up."""
    import json as _json
    import subprocess as sp

    monkeypatch.setenv("BENCH_ONLY", "mnist_mlp")
    monkeypatch.setattr(bench, "_probe_device",
                        lambda *a, **k: ("TPU v5 lite", 9000.0))
    monkeypatch.setattr(bench, "_load_mid_round", lambda root=None: None)
    calls = []

    class FakeChild:
        def __init__(self, attempt):
            self.attempt = attempt
            self.returncode = 0

        def communicate(self, timeout=None):
            if self.attempt == 0:
                return (_json.dumps(
                    {"error": "_ConfigTimeout: config exceeded 1200s"}), "")
            return (_json.dumps({"result": {"value": 2.0, "unit": "u",
                                            "mfu": 0.4},
                                 "device": "TPU", "peak_flops": 197e12,
                                 "peak_source": "table"}), "")

        def poll(self):
            return 0

        def kill(self):
            pass

    monkeypatch.setattr(sp, "Popen",
                        lambda cmd, **kw: (calls.append(cmd),
                                           FakeChild(len(calls) - 1))[1])
    res = bench.run_suite()
    assert len(calls) == 2
    assert res["configs"]["mnist_mlp_train"]["mfu"] == 0.4
