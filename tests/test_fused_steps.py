"""Fused multi-step dispatch: ``Trainer.run_steps(stacked_feed, k)``
compiles ONE ``lax.scan`` over K per-step batches with the full training
carry (params, opt_state, state, loss-scale state) donated end-to-end,
and ``fit(steps_per_dispatch=K)`` feeds it stacked super-batches from
the DeviceFeeder background thread.

Pinned here:
- K fused steps == K sequential ``step()`` calls (params, opt_state,
  metrics, loss-scale state) under plain, amp dynamic-loss-scale, and
  dp-sharded configs — same rng stream, same math;
- remainder batches (< K) fall through to the single-step function with
  NO fused-program retrace;
- ``fit(steps_per_dispatch=K)`` event/metric/checkpoint semantics
  (per-chunk events, stacked metrics, chunk-boundary checkpoint
  rounding, exact global_step);
- the DeviceFeeder fill-thread cancel path (the abandoned-iterator leak);
- dispatch overhead on the CPU: run_steps(k=16) beats 16 ``step()``
  calls per step on the MNIST MLP config;
- the persistent-compile-cache flag wiring in ``Trainer.startup``.
"""

import logging
import os
import tempfile
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.core.config import set_flag
from paddle_tpu.core.errors import EnforceError
from paddle_tpu.data.feeder import DeviceFeeder, iter_chunked, stack_batches
from paddle_tpu.models import mnist
from paddle_tpu.parallel import DistStrategy


def _feeds(n, bs=32, seed=0):
    r = np.random.RandomState(seed)
    return [{"image": r.randn(bs, 784).astype(np.float32),
             "label": r.randint(0, 10, (bs, 1)).astype(np.int64)}
            for _ in range(n)]


def _trainer(**kw):
    prog = pt.build(mnist.mlp)
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", **kw)
    return tr


def _assert_scopes_match(a, b, rtol=1e-5, atol=1e-6):
    for k in a.params:
        np.testing.assert_allclose(np.asarray(a.params[k]),
                                   np.asarray(b.params[k]),
                                   rtol=rtol, atol=atol, err_msg=k)
    flat_a = jax.tree.leaves(a.opt_state)
    flat_b = jax.tree.leaves(b.opt_state)
    assert len(flat_a) == len(flat_b)
    for la, lb in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# equivalence: K fused steps == K sequential steps
# ---------------------------------------------------------------------------


def test_run_steps_matches_sequential_plain():
    feeds = _feeds(4)
    t_seq = _trainer()
    t_seq.startup(sample_feed=feeds[0])
    outs_seq = [t_seq.step(f) for f in feeds]

    t_fused = _trainer()
    t_fused.startup(sample_feed=feeds[0])
    outs = t_fused.run_steps(stack_batches(feeds))

    assert t_fused.global_step == 4
    # stacked fetch: every metric gains a leading (K,) axis
    assert np.asarray(outs["loss"]).shape == (4,)
    assert np.asarray(outs["logits"]).shape == (4, 32, 10)
    np.testing.assert_allclose(
        np.asarray(outs["loss"]),
        np.array([float(o["loss"]) for o in outs_seq]), rtol=1e-5, atol=1e-6)
    _assert_scopes_match(t_seq.scope, t_fused.scope)


def test_run_steps_matches_sequential_amp_dynamic_loss_scale():
    """Loss-scale state threads through the scan carry: dynamic growth
    (growth_interval=2 over 4 steps -> two doublings) and the fetch's
    per-step loss_scale column must match the sequential path exactly."""
    feeds = _feeds(4)
    strat = lambda: DistStrategy(dynamic_loss_scale=True,
                                 loss_scale_growth_interval=2)
    with pt.amp_guard("bfloat16"):
        t_seq = _trainer(strategy=strat())
        t_seq.startup(sample_feed=feeds[0])
        outs_seq = [t_seq.step(f) for f in feeds]

        t_fused = _trainer(strategy=strat())
        t_fused.startup(sample_feed=feeds[0])
        outs = t_fused.run_steps(stack_batches(feeds))

    np.testing.assert_allclose(
        np.asarray(outs["loss"]),
        np.array([float(o["loss"]) for o in outs_seq]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(outs["loss_scale"]),
        np.array([float(o["loss_scale"]) for o in outs_seq]))
    for key in ("scale", "good_steps", "overflows"):
        assert float(t_seq.scope.loss_scale_state[key]) == \
            float(t_fused.scope.loss_scale_state[key]), key
    # the dynamic policy actually ran inside the scan (2^15 -> 2^17)
    assert float(t_fused.scope.loss_scale_state["scale"]) == 2.0 ** 17
    _assert_scopes_match(t_seq.scope, t_fused.scope, rtol=1e-4, atol=1e-5)


def test_run_steps_matches_sequential_dp_sharded():
    """dp-sharded fused scan vs plain single-device sequential steps:
    the outer scan composes with GSPMD batch sharding (stacked feed
    sharded from dim 1, steps axis replicated)."""
    feeds = _feeds(4)
    t_seq = _trainer()
    t_seq.startup(sample_feed=feeds[0])
    outs_seq = [t_seq.step(f) for f in feeds]

    mesh = pt.make_mesh({"dp": 8})
    t_fused = _trainer(mesh=mesh, sharding_rules=pt.parallel.replicated())
    t_fused.startup(sample_feed=feeds[0])
    outs = t_fused.run_steps(stack_batches(feeds))

    np.testing.assert_allclose(
        np.asarray(outs["loss"]),
        np.array([float(o["loss"]) for o in outs_seq]), rtol=1e-4, atol=1e-5)
    # atol 5e-5, not the dp-1 tests' 1e-5: the gradient here is an
    # eight-way float32 all-reduce against one device's serial sum, and
    # Adam divides by sqrt(v), so an element whose gradient is at the
    # reduce-order noise floor moves by a fraction of lr (1e-3) either
    # way. Seen: one element of fc_0/w in 156,800 off by 1.74e-5 after
    # four steps, every other leaf within 1e-6.
    _assert_scopes_match(t_seq.scope, t_fused.scope, rtol=1e-4, atol=5e-5)


def test_stacked_put_batch_shards_from_dim_one():
    """The super-batch's steps axis stays replicated; the per-step batch
    sharding applies from dim 1 (parallel.api.put_batch stacked=True)."""
    from paddle_tpu.parallel import api as par_api

    mesh = pt.make_mesh({"dp": 8})
    rules = pt.parallel.replicated()
    feed = {"image": np.zeros((4, 16, 784), np.float32)}
    out = par_api.put_batch(mesh, rules, feed, stacked=True)
    spec = out["image"].sharding.spec
    assert spec[0] is None and spec[1] == "dp", spec
    # unstacked: same feed's dim 0 is the batch
    out2 = par_api.put_batch(mesh, rules, {"x": np.zeros((16, 8), np.float32)})
    assert out2["x"].sharding.spec[0] == "dp"


# ---------------------------------------------------------------------------
# retrace + validation
# ---------------------------------------------------------------------------


def test_remainder_falls_through_with_no_retrace():
    """After one fused K-chunk and one single-step compile, further
    chunks and remainder singles of the same shapes must not trace
    anything new (the no-retrace guarantee fit relies on)."""
    feeds = _feeds(6)
    tr = _trainer()
    tr.startup(sample_feed=feeds[0])
    tr.run_steps(stack_batches(feeds[:4]))   # fused program compiles
    tr.step(feeds[4])                        # single-step compiles
    warm = tr._trace_count
    tr.run_steps(stack_batches(feeds[:4]))
    tr.step(feeds[5])
    tr.run_steps(stack_batches(feeds[2:6]))
    assert tr._trace_count == warm, (
        f"retraced: {tr._trace_count - warm} new traces after warmup")
    assert tr.global_step == 4 + 1 + 4 + 1 + 4


def test_run_steps_validates_inputs():
    feeds = _feeds(2)
    tr = _trainer()
    with pytest.raises(EnforceError, match="startup"):
        tr.run_steps(stack_batches(feeds))
    tr.startup(sample_feed=feeds[0])
    with pytest.raises(EnforceError, match="leading axis"):
        tr.run_steps(stack_batches(feeds), k=3)


# ---------------------------------------------------------------------------
# fit(steps_per_dispatch=K): events, metrics, checkpoints, global_step
# ---------------------------------------------------------------------------


def _reader(num_batches, bs=16, seed=0):
    r = np.random.RandomState(seed)
    batches = [[(r.randn(784).astype(np.float32),
                 np.asarray([r.randint(0, 10)], np.int64))
                for _ in range(bs)] for _ in range(num_batches)]

    def f():
        yield from batches
    return f


@pytest.mark.parametrize("prefetch", [True, False])
def test_fit_steps_per_dispatch_semantics(prefetch):
    """10 batches at K=4: two fused chunks + two remainder singles.
    Events fire per chunk (num_steps, stacked metrics), global_step is
    exact, and step_interval=3 checkpoints round to the chunk boundary
    that crossed each multiple (4, 8) plus the exact hit at 9."""
    tr = _trainer()
    tr.startup(sample_feed=_feeds(1, bs=16)[0])
    events = []
    with tempfile.TemporaryDirectory() as d:
        cfg = pt.CheckpointConfig(d, epoch_interval=0, step_interval=3,
                                  max_num_checkpoints=10)
        pt.fit(tr, _reader(10), num_epochs=1, feed_names=["image", "label"],
               dtypes=["float32", "int64"],
               event_handler=events.append, checkpoint_config=cfg,
               prefetch=prefetch, steps_per_dispatch=4)
        assert sorted(os.listdir(d)) == ["step_4", "step_8", "step_9"]
    assert tr.global_step == 10
    steps = [e for e in events if e.kind == "end_step"]
    assert [(e.step, e.num_steps) for e in steps] == \
        [(4, 4), (8, 4), (9, 1), (10, 1)]
    begin = [e for e in events if e.kind == "begin_step"]
    assert [(e.step, e.num_steps) for e in begin] == \
        [(0, 4), (4, 4), (8, 1), (9, 1)]
    # chunk metrics come back stacked (num_steps,); singles stay scalar
    assert np.asarray(steps[0].metrics["loss"]).shape == (4,)
    assert np.asarray(steps[2].metrics["loss"]).shape == ()


def test_fit_steps_per_dispatch_matches_plain_fit():
    """Same reader, same seed: fit with K=4 fused dispatch lands the
    same params as the per-step fit loop (the rng stream is keyed by
    global_step either way)."""
    def run(k):
        tr = _trainer()
        tr.startup(sample_feed=_feeds(1, bs=16)[0])
        pt.fit(tr, _reader(10), num_epochs=1,
               feed_names=["image", "label"], dtypes=["float32", "int64"],
               steps_per_dispatch=k)
        return tr

    a, b = run(1), run(4)
    assert a.global_step == b.global_step == 10
    _assert_scopes_match(a.scope, b.scope, rtol=1e-4, atol=1e-5)


def test_fit_closes_feeder_on_early_exit(monkeypatch):
    """A raising event handler must not strand the fill thread blocked
    on the queue holding device buffers (the DeviceFeeder leak)."""
    from paddle_tpu.data import feeder as feeder_mod

    made = []
    orig = feeder_mod.DeviceFeeder

    class Capturing(orig):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(feeder_mod, "DeviceFeeder", Capturing)
    tr = _trainer()
    tr.startup(sample_feed=_feeds(1, bs=16)[0])

    def boom(e):
        if e.kind == "end_step":
            raise RuntimeError("abort training")

    with pytest.raises(RuntimeError, match="abort training"):
        pt.fit(tr, _reader(64), num_epochs=1,
               feed_names=["image", "label"], dtypes=["float32", "int64"],
               event_handler=boom, steps_per_dispatch=4)
    assert made, "fit did not go through DeviceFeeder"
    for f in made:
        for t in f._threads:
            t.join(timeout=5.0)
            assert not t.is_alive(), "fill thread leaked after early exit"


# ---------------------------------------------------------------------------
# DeviceFeeder: stacking + cancellation
# ---------------------------------------------------------------------------


def test_device_feeder_stacks_full_chunks_and_singles_remainder():
    batches = [{"x": np.full((2,), i, np.float32)} for i in range(7)]
    f = DeviceFeeder(lambda: iter(batches), stack_k=3)
    items = list(f)
    assert [(n, tuple(np.asarray(d["x"]).shape)) for n, d in items] == \
        [(3, (3, 2)), (3, (3, 2)), (1, (2,))]
    # stacking preserves per-step order
    np.testing.assert_array_equal(np.asarray(items[0][1]["x"])[:, 0],
                                  [0.0, 1.0, 2.0])


def test_device_feeder_shape_mismatch_flushes_singly():
    """A short (last) reader batch must not poison the stack: buffered
    same-shape batches flush through the single path, never np.stack'd
    against a mismatched shape."""
    batches = [{"x": np.zeros((4, 2))}, {"x": np.zeros((4, 2))},
               {"x": np.zeros((3, 2))},  # short batch mid-buffer
               {"x": np.zeros((4, 2))}]
    f = DeviceFeeder(lambda: iter(batches), stack_k=3)
    ns = [n for n, _ in f]
    assert ns == [1, 1, 1, 1]


def test_device_feeder_abandoned_iterator_releases_fill_thread():
    """break-ing out of the loop (the old leak: daemon thread parked on
    q.put holding device buffers forever) now cancels the fill."""
    def endless():
        i = 0
        while True:
            yield {"x": np.full((2,), i, np.float32)}
            i += 1

    f = DeviceFeeder(endless, capacity=2)
    for item in f:
        break  # generator finalization must release the thread
    f.close()
    for t in f._threads:
        t.join(timeout=5.0)
        assert not t.is_alive(), "fill thread still blocked after close()"


def test_device_feeder_cross_thread_close_unblocks_parked_consumer():
    """close() from a DIFFERENT thread while the consumer is parked in
    q.get() (slow reader, empty queue): the END sentinel must still be
    delivered so the consumer returns instead of hanging forever."""
    import time

    gate = threading.Event()

    def reader():
        yield {"x": np.zeros((2,))}
        gate.wait(timeout=10.0)  # park the fill thread inside the reader

    f = DeviceFeeder(lambda: reader(), capacity=2)
    got = []
    consumer = threading.Thread(target=lambda: [got.append(i) for i in f])
    consumer.start()
    time.sleep(0.3)  # consumer drains item 1 and parks in q.get()
    closer = threading.Thread(target=f.close)
    closer.start()
    time.sleep(0.2)
    gate.set()  # reader returns; fill must deliver END despite stop set
    consumer.join(timeout=5.0)
    closer.join(timeout=10.0)
    assert not consumer.is_alive(), "consumer hung after cross-thread close()"
    assert len(got) == 1


def test_device_feeder_close_is_idempotent_and_reiterable():
    f = DeviceFeeder(lambda: iter([{"x": np.zeros((2,))}] * 3))
    assert len(list(f)) == 3
    f.close()
    f.close()
    assert len(list(f)) == 3  # closing does not poison later iterations


def test_device_feeder_propagates_reader_errors():
    def bad():
        yield {"x": np.zeros((2,))}
        raise ValueError("reader broke")

    with pytest.raises(ValueError, match="reader broke"):
        list(DeviceFeeder(lambda: bad()))


def test_iter_chunked_sync_path():
    batches = [{"x": np.full((2,), i, np.float32)} for i in range(5)]
    ident = lambda d: d
    items = list(iter_chunked(iter(batches), 2, put_fn=ident,
                              put_stacked_fn=ident))
    assert [n for n, _ in items] == [2, 2, 1]
    np.testing.assert_array_equal(np.asarray(items[1][1]["x"])[:, 0],
                                  [2.0, 3.0])


# ---------------------------------------------------------------------------
# dispatch overhead (acceptance: fused K=16 beats 16 launches)
# ---------------------------------------------------------------------------


def test_fused_dispatch_reduces_per_step_wall_time():
    """run_steps(k=16) must reduce per-step wall time vs 16 sequential
    step() calls on the MNIST MLP config, feeds pre-staged both ways so
    the delta is launch + host-loop overhead — the whole point of fusing
    the step loop into one launch. Standalone the fused path wins 2-3x;
    under a loaded suite run a single measurement can still lose to a
    scheduler spike, so best of three interleaved, up to 3 attempts —
    any observed reduction demonstrates the win."""
    import time

    k, iters = 16, 32
    feeds = _feeds(4, bs=64)
    tr = pt.Trainer(pt.build(mnist.mlp), opt.SGD(0.01), loss_name="loss",
                    fetch_list=["loss"])
    tr.startup(sample_feed=feeds[0])
    staged = [tr._put_feed(b) for b in feeds[:2]]
    stacked = tr._put_feed(
        stack_batches([feeds[i % len(feeds)] for i in range(k)]),
        stacked=True)

    def per_step(dispatch, n):
        jax.block_until_ready(dispatch(0))  # compiled and warm
        t0 = time.perf_counter()
        for i in range(n):
            out = dispatch(i)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    for _ in range(3):
        dt1 = dtk = float("inf")
        for _ in range(3):
            dt1 = min(dt1, per_step(lambda i: tr.step(staged[i % 2]), iters))
            dtk = min(dtk, per_step(lambda i: tr.run_steps(stacked, k=k),
                                    iters // k))
        if dtk < dt1:
            break
    assert dtk < dt1, (dtk, dt1)


# ---------------------------------------------------------------------------
# persistent compile cache wiring (Trainer.startup, behind the flag)
# ---------------------------------------------------------------------------


def test_compile_cache_flag_wires_and_logs(tmp_path, caplog, monkeypatch):
    # the flag yields to this variable (core/config.compile_cache_dir)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min_t = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_min_b = jax.config.jax_persistent_cache_min_entry_size_bytes
    cache_dir = str(tmp_path / "cc")
    feeds = _feeds(2, bs=8, seed=3)
    try:
        set_flag("compile_cache_dir", cache_dir)
        with caplog.at_level(logging.INFO, logger="paddle_tpu.trainer"):
            tr = _trainer()
            tr.startup(sample_feed=feeds[0])
            tr.step(feeds[0])
        assert os.path.isdir(cache_dir) and len(os.listdir(cache_dir)) > 0
        assert jax.config.jax_compilation_cache_dir == cache_dir
        msgs = [r.message for r in caplog.records]
        assert any("persistent compilation cache" in m for m in msgs)
        assert any("compile cache MISS" in m for m in msgs), msgs
    finally:
        set_flag("compile_cache_dir", "")
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min_t)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev_min_b)
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()  # re-latch the restored (conftest) cache dir
    # flag off: startup leaves the jax config alone
    tr2 = _trainer()
    tr2.startup(sample_feed=feeds[0])
    assert jax.config.jax_compilation_cache_dir == prev_dir
