"""Feed wire formats (data/wire.py) + input-pipeline stage metrics.

Pinned here:
- WireSpec round-trip exactness: bf16 truncation, uint8/int8 affine
  quantization bounds and zero-point math, idempotent encode;
- wire-fed training == fp32-fed training within declared tolerance for
  plain / amp-dynamic-loss-scale / dp-sharded configs, on both the
  single-step and the stacked ``run_steps(k)`` fused path;
- the decode is FUSED into the step program: the lowered HLO of the
  fused K-step program takes uint8 parameters and converts inside, and
  a chunked fit performs exactly one device dispatch per chunk;
- ``fit(feed_wire=...)`` end-to-end incl. resume interplay and the
  ``Event.pipeline`` report;
- PipelineMetrics attribution: a synthetic slow reader names "reader"
  as the bottleneck and the h2d MB/s estimate is populated; a slow
  consumer shows up as dispatch wait instead;
- the ``feed:wire-candidate`` analysis lint;
- the lever end to end: uint8 wire puts >= 3.5x fewer bytes on the link
  than fp32.
"""

import os
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import paddle_tpu as pt
from paddle_tpu import analysis
from paddle_tpu import optimizer as opt
from paddle_tpu.core.errors import EnforceError
from paddle_tpu.data.feeder import DeviceFeeder, PipelineMetrics, stack_batches
from paddle_tpu.data.wire import (FeedWire, WireSpec, feed_logical_nbytes,
                                  feed_wire_nbytes)
from paddle_tpu.models import mnist
from paddle_tpu.parallel import DistStrategy


def _pixel_feeds(n, bs=16, seed=0):
    """(raw uint8 feeds, logically-identical fp32 feeds)."""
    r = np.random.RandomState(seed)
    raw, logical = [], []
    for _ in range(n):
        img = r.randint(0, 256, (bs, 784)).astype(np.uint8)
        lab = r.randint(0, 10, (bs, 1)).astype(np.int64)
        raw.append({"image": img, "label": lab})
        logical.append({"image": (img.astype(np.float32) - 127.0) / 64.0,
                        "label": lab})
    return raw, logical


IMG_WIRE = {"image": WireSpec.image_uint8()}


def _trainer(feed_wire=None, **kw):
    return pt.Trainer(pt.build(mnist.mlp), opt.SGD(0.1), loss_name="loss",
                      feed_wire=feed_wire, **kw)


def _assert_scopes_match(a, b, rtol=1e-5, atol=1e-6):
    for k in a.params:
        np.testing.assert_allclose(np.asarray(a.params[k]),
                                   np.asarray(b.params[k]),
                                   rtol=rtol, atol=atol, err_msg=k)


# ---------------------------------------------------------------------------
# WireSpec round-trip exactness
# ---------------------------------------------------------------------------


def test_bf16_cast_roundtrip_exact_on_representable_values():
    spec = WireSpec.cast("bfloat16")
    x = np.asarray(jnp.arange(-8, 8, dtype=jnp.bfloat16) * 0.25,
                   dtype=np.float32)  # exactly bf16-representable
    w = spec.encode(x)
    assert w.dtype == np.dtype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(spec.decode(jnp.asarray(w)),
                                             np.float32), x)
    # non-representable values truncate exactly like an astype round-trip
    y = np.random.RandomState(0).randn(64).astype(np.float32)
    expect = np.asarray(y.astype(jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(
        np.asarray(spec.decode(spec.encode(y)), np.float32), expect)


def test_uint8_quantize_zero_point_and_bounds():
    spec = WireSpec.quantize("uint8", scale=0.5, zero_point=10.0)
    # grid values round-trip exactly: v = (u - 10) * 0.5
    u = np.arange(0, 256, dtype=np.uint8)
    v = (u.astype(np.float32) - 10.0) * 0.5
    w = spec.encode(v)
    np.testing.assert_array_equal(w, u)
    np.testing.assert_allclose(np.asarray(spec.decode(w)), v)
    # out-of-range values clip to the wire dtype bounds, never wrap
    big = np.asarray([1e9, -1e9], np.float32)
    np.testing.assert_array_equal(spec.encode(big), [255, 0])
    # int8 wire clips at its own signed bounds
    s8 = WireSpec.quantize("int8", scale=1.0, zero_point=0.0)
    np.testing.assert_array_equal(s8.encode(np.asarray([300.0, -300.0])),
                                  [127, -128])


def test_encode_is_idempotent_on_wire_dtype():
    spec = WireSpec.image_uint8()
    raw = np.random.RandomState(0).randint(0, 256, (4, 7)).astype(np.uint8)
    enc = spec.encode(raw)
    assert enc.dtype == np.uint8
    np.testing.assert_array_equal(enc, raw)  # NOT re-quantized
    # double-encode through the FeedWire table is also a no-op
    fw = FeedWire({"x": spec})
    once = fw.encode({"x": (raw.astype(np.float32) - 127.0) / 64.0})
    twice = fw.encode(once)
    np.testing.assert_array_equal(once["x"], twice["x"])


def test_quantize_encode_refuses_nonfinite_input():
    """An integer wire dtype has no NaN/Inf: a corrupt reader batch must
    fail LOUDLY at encode, not be laundered into valid pixels the
    on-device NaN guard can never see. Cast wire formats carry the NaN
    through so the guard still fires for those."""
    spec = WireSpec.image_uint8()
    bad = np.asarray([1.0, np.nan, 3.0], np.float32)
    with pytest.raises(FloatingPointError, match="NaN/Inf"):
        spec.encode(bad)
    with pytest.raises(FloatingPointError, match="NaN/Inf"):
        spec.encode(np.asarray([np.inf], np.float32))
    enc = WireSpec.cast("bfloat16").encode(bad)
    assert np.isnan(np.asarray(enc, np.float32)[1])  # propagated, not hidden


def test_wirespec_validation():
    with pytest.raises(EnforceError, match="integer"):
        WireSpec.quantize("float16")
    with pytest.raises(EnforceError, match="label/id"):
        WireSpec.quantize("uint8", decode_dtype="int32")
    with pytest.raises(EnforceError, match="scale"):
        WireSpec.quantize("uint8", scale=0.0)
    with pytest.raises(EnforceError, match="no-op"):
        WireSpec.cast("float32", "float32")
    with pytest.raises(EnforceError, match="GROWS"):
        WireSpec.cast("float32", "float16")
    with pytest.raises(EnforceError, match="WireSpec"):
        FeedWire({"x": "uint8"})
    with pytest.raises(EnforceError, match="feed_wire"):
        FeedWire.make(["not", "a", "dict"])


def test_byte_helpers_count_wire_vs_logical():
    fw = FeedWire.make(IMG_WIRE)
    raw, logical = _pixel_feeds(1, bs=8)
    for feed in (raw[0], logical[0]):  # arrival dtype must not matter
        assert feed_wire_nbytes(feed, fw) == 8 * 784 * 1 + 8 * 8
        assert feed_logical_nbytes(feed, fw) == 8 * 784 * 4 + 8 * 8
    # no wire table: both count the raw host bytes
    assert feed_wire_nbytes(raw[0]) == feed_logical_nbytes(raw[0])


# ---------------------------------------------------------------------------
# train equivalence: wire-fed == fp32-fed within tolerance
# ---------------------------------------------------------------------------


def test_uint8_wire_training_matches_fp32_plain():
    raw, logical = _pixel_feeds(4)
    t_ref = _trainer()
    t_ref.startup(sample_feed=logical[0])
    ref = [t_ref.step(f) for f in logical]

    t_wire = _trainer(feed_wire=IMG_WIRE)
    t_wire.startup(sample_feed=raw[0])
    got = [t_wire.step(f) for f in raw]

    np.testing.assert_allclose([float(o["loss"]) for o in got],
                               [float(o["loss"]) for o in ref],
                               rtol=1e-6, atol=1e-7)
    _assert_scopes_match(t_ref.scope, t_wire.scope)


def test_bf16_wire_training_matches_fp32_within_tolerance():
    _, logical = _pixel_feeds(4, seed=1)
    t_ref = _trainer()
    t_ref.startup(sample_feed=logical[0])
    ref = [t_ref.step(f) for f in logical]

    t_wire = _trainer(feed_wire={"image": WireSpec.cast("bfloat16")})
    t_wire.startup(sample_feed=logical[0])
    got = [t_wire.step(f) for f in logical]

    # bf16 truncation of the input: ~2-3 decimal digits of mantissa
    np.testing.assert_allclose([float(o["loss"]) for o in got],
                               [float(o["loss"]) for o in ref],
                               rtol=5e-3)
    _assert_scopes_match(t_ref.scope, t_wire.scope, rtol=5e-2, atol=5e-3)


def test_uint8_wire_training_matches_fp32_amp_dynamic_loss_scale():
    raw, logical = _pixel_feeds(4, seed=2)
    strat = lambda: DistStrategy(dynamic_loss_scale=True,
                                 loss_scale_growth_interval=2)
    with pt.amp_guard("bfloat16"):
        t_ref = _trainer(strategy=strat())
        t_ref.startup(sample_feed=logical[0])
        ref = [t_ref.step(f) for f in logical]

        t_wire = _trainer(feed_wire=IMG_WIRE, strategy=strat())
        t_wire.startup(sample_feed=raw[0])
        got = [t_wire.step(f) for f in raw]

    np.testing.assert_allclose([float(o["loss"]) for o in got],
                               [float(o["loss"]) for o in ref],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        [float(o["loss_scale"]) for o in got],
        [float(o["loss_scale"]) for o in ref])
    _assert_scopes_match(t_ref.scope, t_wire.scope, rtol=1e-4, atol=1e-5)


def test_uint8_wire_training_matches_fp32_dp_sharded():
    raw, logical = _pixel_feeds(4, seed=3)
    t_ref = _trainer()
    t_ref.startup(sample_feed=logical[0])
    ref = [t_ref.step(f) for f in logical]

    mesh = pt.make_mesh({"dp": 8})
    t_wire = _trainer(feed_wire=IMG_WIRE, mesh=mesh,
                      sharding_rules=pt.parallel.replicated())
    t_wire.startup(sample_feed=raw[0])
    got = [t_wire.step(f) for f in raw]

    np.testing.assert_allclose([float(o["loss"]) for o in got],
                               [float(o["loss"]) for o in ref],
                               rtol=1e-4, atol=1e-5)
    _assert_scopes_match(t_ref.scope, t_wire.scope, rtol=1e-4, atol=1e-5)
    # the wire array really is sharded from the wire dtype
    dev = t_wire._put_feed(raw[0])
    assert dev["image"].dtype == jnp.uint8
    assert dev["image"].sharding.spec[0] == "dp"


def test_uint8_wire_stacked_run_steps_matches_sequential_fp32():
    raw, logical = _pixel_feeds(4, seed=4)
    t_ref = _trainer()
    t_ref.startup(sample_feed=logical[0])
    ref = [t_ref.step(f) for f in logical]

    t_wire = _trainer(feed_wire=IMG_WIRE)
    t_wire.startup(sample_feed=raw[0])
    outs = t_wire.run_steps(stack_batches(raw))

    assert t_wire.global_step == 4
    np.testing.assert_allclose(np.asarray(outs["loss"]),
                               [float(o["loss"]) for o in ref],
                               rtol=1e-6, atol=1e-7)
    _assert_scopes_match(t_ref.scope, t_wire.scope)


# ---------------------------------------------------------------------------
# fused decode: no extra dispatch, wire dtype on the wire
# ---------------------------------------------------------------------------


def test_decode_is_fused_into_the_step_program():
    """The lowered fused K-step program TAKES uint8 parameters and
    converts them inside — one module, no separate decode program —
    and a chunked fit dispatches exactly once per chunk."""
    raw, _ = _pixel_feeds(4, seed=5)
    tr = _trainer(feed_wire=IMG_WIRE)
    tr.startup(sample_feed=raw[0])
    feed_dev = tr._put_feed(stack_batches(raw), stacked=True)
    assert feed_dev["image"].dtype == jnp.uint8  # wire dtype crossed the link
    ls = getattr(tr.scope, "loss_scale_state", None) or {}
    lowered = tr._multi_step_fn.lower(
        tr.scope.params, tr.scope.opt_state, tr.scope.state,
        jax.random.PRNGKey(0), np.int32(0), feed_dev, ls)
    txt = lowered.as_text()
    assert ("ui8" in txt) or ("u8[" in txt), "uint8 never reached the program"
    assert "convert" in txt  # the on-device decode
    # launch count: one compiled-fn call per chunk, zero extra
    calls = {"multi": 0, "single": 0}
    multi, single = tr._multi_step_fn, tr._step_fn

    def count_multi(*a, **kw):
        calls["multi"] += 1
        return multi(*a, **kw)

    def count_single(*a, **kw):
        calls["single"] += 1
        return single(*a, **kw)

    tr._multi_step_fn, tr._step_fn = count_multi, count_single

    r = np.random.RandomState(9)
    samples = [[(r.randint(0, 256, (784,)).astype(np.uint8),
                 np.asarray([r.randint(0, 10)], np.int64))
                for _ in range(16)] for _ in range(8)]
    pt.fit(tr, lambda: iter(samples), num_epochs=1,
           feed_names=["image", "label"], dtypes=["uint8", "int64"],
           steps_per_dispatch=4, feed_wire=IMG_WIRE)
    assert calls == {"multi": 2, "single": 0}, calls


def test_prestaged_logical_device_feed_is_not_double_decoded():
    """A pre-staged device feed of LOGICAL (already-decoded) values —
    which encode cannot reach, it skips jax.Arrays — must pass through
    the decode untouched, not get dequantized a second time; and a
    dtype that is neither wire nor logical fails loudly at trace time."""
    raw, logical = _pixel_feeds(2, seed=8)
    tr = _trainer(feed_wire=IMG_WIRE)
    tr.startup(sample_feed=raw[0])
    ref = float(tr.step(raw[0])["loss"])

    tr2 = _trainer(feed_wire=IMG_WIRE)
    tr2.startup(sample_feed=raw[0])
    staged = {"image": jax.device_put(logical[0]["image"]),
              "label": jax.device_put(logical[0]["label"])}
    got = float(tr2.step(staged)["loss"])
    assert got == pytest.approx(ref, rel=1e-6)

    spec = WireSpec.image_uint8()
    with pytest.raises(EnforceError, match="decode"):
        spec.decode(np.zeros((2,), np.float16))


def test_check_accepts_plain_dict_feed_wire_with_wire_typed_feed():
    """analysis.check(feed_wire={name: WireSpec}) must map a wire-typed
    sample feed to logical dtypes exactly like a FeedWire — not trace
    uint8 into f32 matmuls and collapse to analysis:trace-failed."""
    raw, _ = _pixel_feeds(1, bs=4)
    rep = analysis.check(pt.build(_normalizing_model), raw[0],
                         feed_wire=dict(IMG_WIRE))
    assert "analysis:trace-failed" not in rep.codes(), rep.render()
    assert not rep.by_code("feed:wire-candidate"), rep.render()


def test_no_retrace_across_wire_chunks():
    raw, _ = _pixel_feeds(6, seed=6)
    tr = _trainer(feed_wire=IMG_WIRE)
    tr.startup(sample_feed=raw[0])
    tr.run_steps(stack_batches(raw[:4]))
    tr.step(raw[4])
    warm = tr._trace_count
    tr.run_steps(stack_batches(raw[:4]))
    tr.step(raw[5])
    assert tr._trace_count == warm


# ---------------------------------------------------------------------------
# fit(feed_wire=...): end-to-end, pipeline event, resume interplay
# ---------------------------------------------------------------------------


def _sample_reader(num_batches, bs=16, seed=0):
    r = np.random.RandomState(seed)
    batches = [[(r.randint(0, 256, (784,)).astype(np.uint8),
                 np.asarray([r.randint(0, 10)], np.int64))
                for _ in range(bs)] for _ in range(num_batches)]

    def f():
        yield from batches
    return f


def test_fit_feed_wire_pipeline_event_and_metrics():
    tr = _trainer(feed_wire=None)  # installed via fit below
    raw, _ = _pixel_feeds(1)
    tr.startup(sample_feed=raw[0])
    events = []
    pt.fit(tr, _sample_reader(8), num_epochs=1,
           feed_names=["image", "label"], dtypes=["uint8", "int64"],
           event_handler=events.append, steps_per_dispatch=4,
           feed_wire=IMG_WIRE)
    assert tr.global_step == 8
    end = [e for e in events if e.kind == "end_epoch"]
    assert len(end) == 1 and isinstance(end[0].pipeline, dict)
    rep = end[0].pipeline
    assert set(rep["stages_s"]) == {"reader", "encode", "stack", "h2d",
                                    "dispatch"}
    assert rep["batches"] == 8 and rep["chunks"] == 2
    # spec-aware accounting: raw-uint8 arrival still reports ~4x saving
    assert rep["wire_reduction"] is not None and rep["wire_reduction"] > 3.0
    assert rep["h2d_bytes"] < rep["logical_bytes"]
    assert tr.pipeline_report()["bottleneck"] in rep["stages_s"]


def test_fit_resume_with_wire_matches_uninterrupted():
    def run(epochs, ckpt_dir=None, resume=False):
        tr = _trainer(feed_wire=IMG_WIRE)
        raw, _ = _pixel_feeds(1)
        tr.startup(sample_feed=raw[0])
        cfg = (pt.CheckpointConfig(ckpt_dir, epoch_interval=1)
               if ckpt_dir else None)
        pt.fit(tr, _sample_reader(6), num_epochs=epochs,
               feed_names=["image", "label"], dtypes=["uint8", "int64"],
               checkpoint_config=cfg, resume=resume, steps_per_dispatch=2)
        return tr

    ref = run(2)
    with tempfile.TemporaryDirectory() as d:
        run(1, ckpt_dir=d)                      # epoch 0, checkpointed
        resumed = run(2, ckpt_dir=d, resume=True)  # continues at epoch 1
    assert resumed.global_step == ref.global_step == 12
    _assert_scopes_match(ref.scope, resumed.scope, rtol=1e-6, atol=1e-7)


def test_set_feed_wire_after_startup_rebuilds():
    raw, logical = _pixel_feeds(2, seed=7)
    tr = _trainer()
    tr.startup(sample_feed=logical[0])
    tr.step(logical[0])
    tr.set_feed_wire(IMG_WIRE)   # rebuilds the step with the decode
    out = tr.step(raw[1])
    assert np.isfinite(float(out["loss"]))
    # same table again: no rebuild (object stays)
    fn = tr._step_fn
    tr.set_feed_wire(dict(IMG_WIRE))
    assert tr._step_fn is fn


# ---------------------------------------------------------------------------
# pipeline metrics: bottleneck attribution
# ---------------------------------------------------------------------------


def test_pipeline_report_slow_reader_names_reader_bottleneck():
    def slow_batches():
        for i in range(6):
            time.sleep(0.03)
            yield {"x": np.full((32, 64), i, np.float32)}

    m = PipelineMetrics()
    f = DeviceFeeder(slow_batches, metrics=m)
    assert sum(1 for _ in f) == 6
    rep = f.pipeline_report()
    assert rep["bottleneck"] == "reader"
    assert rep["input_bound"] is True         # the consumer starved
    assert rep["batches"] == 6 and rep["chunks"] == 6
    assert rep["stages_s"]["reader"] >= 0.15
    assert rep["h2d_mbps"] is not None and rep["h2d_mbps"] > 0
    assert rep["h2d_bytes"] == 6 * 32 * 64 * 4


def test_pipeline_report_slow_consumer_accumulates_dispatch_wait():
    def batches():
        for i in range(6):
            yield {"x": np.full((8,), i, np.float32)}

    m = PipelineMetrics()
    f = DeviceFeeder(batches, metrics=m, capacity=1)
    for _ in f:
        time.sleep(0.03)  # consumer is the bottleneck
    rep = f.pipeline_report()
    assert rep["stages_s"]["dispatch"] > 0.05
    assert rep["input_bound"] is False


def test_encode_runs_on_the_fill_thread():
    main = threading.get_ident()
    seen = []
    fw = FeedWire.make(IMG_WIRE)

    def encode(b):
        seen.append(threading.get_ident())
        return fw.encode(b)

    raw, logical = _pixel_feeds(5)
    f = DeviceFeeder(lambda: iter(logical), encode_fn=encode,
                     metrics=PipelineMetrics(), stack_k=2,
                     logical_nbytes_fn=fw.logical_nbytes)
    items = list(f)
    assert [n for n, _ in items] == [2, 2, 1]
    assert seen and all(t != main for t in seen)
    # encode ran BEFORE stacking: the stacked device array is uint8
    assert np.asarray(items[0][1]["image"]).dtype == np.uint8
    rep = f.pipeline_report()
    assert rep["logical_bytes"] > rep["h2d_bytes"]


# ---------------------------------------------------------------------------
# analysis: feed:wire-candidate lint
# ---------------------------------------------------------------------------


def _normalizing_model(image, label):
    from paddle_tpu.framework import create_parameter
    img = (image - 127.0) / 64.0
    w = create_parameter((784, 10), name="fc/w")
    logits = jnp.matmul(img, w)
    return {"loss": jnp.mean((logits - 0.0) ** 2), "logits": logits}


def test_lint_flags_normalize_only_feed():
    feed = {"image": np.zeros((4, 784), np.float32),
            "label": np.zeros((4, 1), np.int64)}
    rep = analysis.check(pt.build(_normalizing_model), feed)
    hits = rep.by_code("feed:wire-candidate")
    assert [f.where for f in hits] == ["image"], rep.render()
    assert "uint8" in hits[0].message
    assert rep.ok("warning")  # info severity: advisory, not a failure


def test_lint_skips_wired_integer_and_compute_first_feeds():
    # already covered by the trainer's wire table -> not re-suggested
    feed = {"image": np.zeros((4, 784), np.float32),
            "label": np.zeros((4, 1), np.int64)}
    tr = pt.Trainer(pt.build(_normalizing_model), opt.SGD(0.1),
                    loss_name="loss", feed_wire=IMG_WIRE)
    tr.startup(sample_feed=feed)
    rep = analysis.check_trainer(tr, feed)
    assert not rep.by_code("feed:wire-candidate"), rep.render()

    # a feed consumed directly by a matmul is NOT a wire candidate
    def direct(image, label):
        from paddle_tpu.framework import create_parameter
        w = create_parameter((784, 10), name="fc/w")
        return {"loss": jnp.mean(jnp.matmul(image, w) ** 2)}

    rep2 = analysis.check(pt.build(direct), feed)
    assert not rep2.by_code("feed:wire-candidate"), rep2.render()


def test_lint_traces_wire_typed_sample_feed_at_logical_dtype():
    """A wire-typed sample feed (raw uint8 pixels) must not break the
    jaxpr-level lint families: check_trainer maps it to the logical
    dtype exactly as startup does, instead of degrading every rule to
    analysis:trace-failed on a uint8-into-f32 type error."""
    raw, _ = _pixel_feeds(1, bs=4)
    tr = pt.Trainer(pt.build(_normalizing_model), opt.SGD(0.1),
                    loss_name="loss", feed_wire=IMG_WIRE)
    tr.startup(sample_feed=raw[0], lint="error")  # must not raise
    rep = analysis.check_trainer(tr, raw[0])
    assert "analysis:trace-failed" not in rep.codes(), rep.render()
    assert "collective:step-trace-failed" not in rep.codes(), rep.render()
    assert not rep.by_code("feed:wire-candidate")  # wired: not re-suggested


def test_lint_flags_cast_only_feed_as_bf16_candidate():
    def cast_first(image, label):
        from paddle_tpu.framework import create_parameter
        w = create_parameter((784, 10), name="fc/w", dtype="bfloat16")
        h = jnp.matmul(image.astype(jnp.bfloat16), w)
        return {"loss": jnp.mean(h.astype(jnp.float32) ** 2)}

    feed = {"image": np.zeros((4, 784), np.float32),
            "label": np.zeros((4, 1), np.int64)}
    rep = analysis.check(pt.build(cast_first), feed)
    hits = rep.by_code("feed:wire-candidate")
    assert [f.where for f in hits] == ["image"], rep.render()
    assert "bfloat16" in hits[0].message


# ---------------------------------------------------------------------------
# the lever itself: what crosses the link, trained end to end
# ---------------------------------------------------------------------------


def test_uint8_wire_moves_3p5x_fewer_h2d_bytes_than_fp32():
    """The same pixels trained host batches -> DeviceFeeder -> step as
    fp32, bf16 wire and uint8 wire: the bytes the trainer put on the
    link (pipeline_report's h2d_bytes, not the byte helpers) fall
    fp32 > bf16 > uint8, and uint8 moves >= 3.5x fewer than fp32."""
    raw, logical = _pixel_feeds(4, bs=32)
    variants = {"fp32": (None, logical),
                "bf16": ({"image": WireSpec.cast("bfloat16")}, logical),
                "uint8": (IMG_WIRE, raw)}
    moved = {}
    for name, (fw, feeds) in variants.items():
        tr = _trainer(feed_wire=fw)
        tr.startup(sample_feed=feeds[0])
        tr.pipeline_metrics.reset()
        for feed in DeviceFeeder(lambda: iter(feeds), put_fn=tr._put_feed,
                                 capacity=2):
            out = tr.step(feed)
        assert np.isfinite(float(out["loss"]))
        rep = tr.pipeline_report()
        moved[name] = rep["h2d_bytes"]
        assert rep["h2d_bytes"] == len(feeds) * feed_wire_nbytes(
            feeds[0], FeedWire.make(fw))
    assert moved["fp32"] > moved["bf16"] > moved["uint8"] > 0
    assert moved["fp32"] / moved["uint8"] >= 3.5, moved
