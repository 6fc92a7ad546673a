"""Device-resident data path (PR 15): HBM dataset cache, the
double-buffered h2d staging ring, and on-device augmentation.

Pinned here:
- multi-epoch cache bit-identity: epoch 2+ of a cached fit moves ZERO
  h2d wire bytes (PipelineMetrics pin) and its losses/params are
  bit-identical to the streamed reference — plain, amp-dynamic-loss-
  scale, and dp-sharded trainers;
- partial caching (budget admits a prefix, the rest streams) and the
  over-budget / no-budget fallbacks to off;
- cache invalidation on resume-restore and ``reshard_restore``
  (elastic rejoin), with re-admission on the next clean epoch;
- augmentation fused-vs-sequential equivalence (crop/flip/normalize
  keyed off the step rng: ``run_steps(K)`` == K ``step()`` calls
  exactly) and eval determinism (random ops are train-only);
- the h2d-starved slow-link story: under a ``testing.faults.slow_h2d``
  throttled put, the 2-deep staging ring recovers throughput the
  blocking put serializes away, and ``overlap_hidden_s`` attributes
  the hidden transfer time;
- honest ``h2d_mbps``: cache-served chunks contribute neither bytes
  nor h2d seconds;
- the ``feed:cacheable-dataset`` lint (check_trainer door).
"""

import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import paddle_tpu as pt
from paddle_tpu import analysis
from paddle_tpu import io as pio
from paddle_tpu import optimizer as opt
from paddle_tpu import resilience
from paddle_tpu.data.augment import AugmentSpec, FeedAugment
from paddle_tpu.data.device_cache import (DeviceCache, device_feed_nbytes,
                                          device_feed_resident_nbytes)
from paddle_tpu.data.feeder import DeviceFeeder, PipelineMetrics
from paddle_tpu.data.wire import WireSpec
from paddle_tpu.models import mnist
from paddle_tpu.parallel import DistStrategy
from paddle_tpu.testing import faults

IMG_WIRE = {"image": WireSpec.image_uint8()}
BS = 16


def _batches(num, bs=BS, seed=0):
    r = np.random.RandomState(seed)
    return [[(r.randint(0, 256, (784,)).astype(np.uint8),
              np.asarray([r.randint(0, 10)], np.int64))
             for _ in range(bs)] for _ in range(num)]


def _sample(batches):
    return {"image": np.stack([s[0] for s in batches[0]]),
            "label": np.stack([s[1] for s in batches[0]])}


def _trainer(**kw):
    return pt.Trainer(pt.build(mnist.mlp), opt.SGD(0.1), loss_name="loss",
                      feed_wire=IMG_WIRE, **kw)


def _fit(tr, batches, epochs, device_cache=None, k=4, handler=None,
         **kw):
    return pt.fit(tr, lambda: iter(batches), num_epochs=epochs,
                  feed_names=["image", "label"], dtypes=["uint8", "int64"],
                  steps_per_dispatch=k, device_cache=device_cache,
                  event_handler=handler, **kw)


def _run(epochs=3, device_cache=None, trainer_kw=None, batches=None,
         amp=None):
    batches = batches if batches is not None else _batches(8)
    losses, epoch_reports = [], []

    def handler(e):
        if e.kind == "end_step":
            losses.extend(np.asarray(e.metrics["loss"]).reshape(-1).tolist())
        elif e.kind == "end_epoch":
            epoch_reports.append(e.pipeline)

    import contextlib
    ctx = pt.amp_guard(amp) if amp else contextlib.nullcontext()
    with ctx:
        tr = _trainer(**(trainer_kw or {}))
        tr.startup(sample_feed=_sample(batches))
        _fit(tr, batches, epochs, device_cache=device_cache,
             handler=handler)
    return tr, losses, epoch_reports


def _assert_scopes_equal(a, b):
    for k in a.params:
        np.testing.assert_array_equal(np.asarray(a.params[k]),
                                      np.asarray(b.params[k]), err_msg=k)


def _epoch_h2d_deltas(reports):
    """Per-epoch h2d byte deltas from the cumulative end_epoch pipeline
    reports."""
    vals = [r["h2d_bytes"] for r in reports]
    return [b - a for a, b in zip([0] + vals[:-1], vals)]


# ---------------------------------------------------------------------------
# multi-epoch bit-identity + the zero-h2d pin
# ---------------------------------------------------------------------------


def test_cached_epochs_zero_h2d_and_bit_identical_plain():
    ref, ref_losses, _ = _run(device_cache=None)
    tr, losses, reports = _run(device_cache=1 << 30)
    assert losses == ref_losses  # BIT-identical, not approx
    _assert_scopes_equal(ref.scope, tr.scope)
    deltas = _epoch_h2d_deltas(reports)
    assert deltas[0] > 0                      # epoch 1 streamed
    assert deltas[1] == 0 and deltas[2] == 0  # epoch 2+ moved NOTHING
    assert reports[-1]["cache_hit_bytes"] > 0
    assert reports[-1]["cache_hits"] == 4     # 2 chunks x 2 cached epochs
    assert tr.device_cache.report()["state"] == "full"


def test_cached_epochs_bit_identical_amp_dynamic_loss_scale():
    strat = lambda: DistStrategy(dynamic_loss_scale=True,
                                 loss_scale_growth_interval=2)
    ref, ref_losses, _ = _run(trainer_kw={"strategy": strat()},
                              amp="bfloat16")
    tr, losses, reports = _run(device_cache=1 << 30,
                               trainer_kw={"strategy": strat()},
                               amp="bfloat16")
    assert losses == ref_losses
    _assert_scopes_equal(ref.scope, tr.scope)
    assert _epoch_h2d_deltas(reports)[1] == 0


def test_cached_epochs_bit_identical_dp_sharded_shard_resident():
    # the reference is the STREAMED run at the SAME dp mesh: cached vs
    # streamed must be bit-identical (dp vs single-device legitimately
    # differs in reduction order and is not this test's claim)
    dp_kw = lambda: {"mesh": pt.make_mesh({"dp": 8}),
                     "sharding_rules": pt.parallel.replicated()}
    ref, ref_losses, _ = _run(device_cache=None, trainer_kw=dp_kw())
    tr, losses, reports = _run(device_cache=1 << 30, trainer_kw=dp_kw())
    assert losses == ref_losses
    _assert_scopes_equal(ref.scope, tr.scope)
    assert _epoch_h2d_deltas(reports)[1] == 0
    # sharded cache: each replica holds its shard only — per-device
    # residency is a fraction of the chunk's wire bytes (the batch
    # axis is dp-sharded; only small replicated leaves count full)
    rep = tr.device_cache.report()
    assert rep["state"] == "full"
    total_wire = rep["hit_bytes"] // 2  # one epoch's worth (2 epochs hit)
    assert rep["resident_bytes"] < total_wire


# ---------------------------------------------------------------------------
# partial cache + fallbacks
# ---------------------------------------------------------------------------


def test_partial_cache_serves_prefix_streams_rest():
    # one K=4 chunk is 4 x (784 u8 + 8 i64) x BS = 50688 B resident:
    # a budget of one-and-a-half chunks admits exactly the first chunk
    chunk_bytes = 4 * BS * (784 + 8)
    ref, ref_losses, _ = _run(device_cache=None)
    tr, losses, reports = _run(device_cache=int(1.5 * chunk_bytes))
    assert losses == ref_losses
    _assert_scopes_equal(ref.scope, tr.scope)
    rep = tr.device_cache.report()
    assert rep["state"] == "partial"
    assert rep["cached_chunks"] == 1 and rep["cached_steps"] == 4
    deltas = _epoch_h2d_deltas(reports)
    # epoch 2 streamed only the un-cached half
    assert 0 < deltas[1] < deltas[0]
    assert reports[-1]["cache_hits"] == 2  # 1 chunk x 2 cached epochs


def test_over_budget_cache_off_streams_everything():
    ref, ref_losses, _ = _run(device_cache=None)
    tr, losses, reports = _run(device_cache=64)  # smaller than any chunk
    assert losses == ref_losses
    rep = tr.device_cache.report()
    assert rep["state"] == "off"
    assert "exceeds" in rep["off_reason"]
    deltas = _epoch_h2d_deltas(reports)
    assert deltas[1] == deltas[0] > 0  # every epoch streams the same


def test_auto_budget_without_hbm_stats_degrades_off():
    # CPU exposes no memory budget: device_cache=True must degrade to
    # off (with the reason recorded), never crash the fit
    tr, losses, _ = _run(epochs=2, device_cache=True)
    rep = tr.device_cache.report()
    assert rep["state"] == "off"
    assert "budget" in rep["off_reason"]
    assert len(losses) == 16  # trained normally


def test_auto_budget_resolves_from_stacked_chunks(monkeypatch):
    """fit(device_cache=True, steps_per_dispatch=K) — the flagship
    config: the advisor's residual estimate must be computed from a
    PER-STEP slice of the (K, batch, ...) chunk, not the stacked
    shape (whose trace fails and silently turned the cache off)."""
    import paddle_tpu.profiling.advisor as advisor
    monkeypatch.setattr(advisor, "device_hbm_bytes",
                        lambda device=None: 1 << 30)
    tr, losses, reports = _run(device_cache=True)
    rep = tr.device_cache.report()
    assert rep["state"] == "full", rep
    assert rep["budget_bytes"] is not None and rep["budget_bytes"] > 0
    assert _epoch_h2d_deltas(reports)[1] == 0


def test_device_cache_make_rejects_garbage():
    with pytest.raises(TypeError, match="device_cache"):
        DeviceCache.make("yes please")
    assert DeviceCache.make(None) is None
    assert DeviceCache.make(False) is None
    assert isinstance(DeviceCache.make(True), DeviceCache)
    assert DeviceCache.make(1024).budget_bytes == 1024


# ---------------------------------------------------------------------------
# invalidation: resume restore + elastic reshard
# ---------------------------------------------------------------------------


def test_resume_restore_invalidates_then_readmits(tmp_path):
    batches = _batches(8)
    cache = DeviceCache(budget_bytes=1 << 30)
    reasons = []
    orig = cache.invalidate
    cache.invalidate = lambda reason: (reasons.append(reason),
                                       orig(reason))[1]
    cfg = pt.CheckpointConfig(str(tmp_path), epoch_interval=1)
    tr = _trainer()
    tr.startup(sample_feed=_sample(batches))
    _fit(tr, batches, 1, device_cache=cache, checkpoint_config=cfg)
    assert cache.report()["state"] == "full"

    tr2 = _trainer()
    tr2.startup(sample_feed=_sample(batches))
    _fit(tr2, batches, 2, device_cache=cache, checkpoint_config=cfg,
         resume=True)
    assert any("restore" in r for r in reasons)
    # the resumed run's epoch 2 started clean: the cache re-armed,
    # re-admitted, and sealed again
    assert cache.report()["state"] == "full"
    # continuity: resumed == uninterrupted
    ref = _trainer()
    ref.startup(sample_feed=_sample(batches))
    _fit(ref, batches, 2)
    _assert_scopes_equal(ref.scope, tr2.scope)


def test_reshard_restore_invalidates_cache(tmp_path):
    batches = _batches(2, bs=16)
    feed = _sample(batches)
    mesh4 = pt.make_mesh({"dp": 4}, devices=jax.devices()[:4])
    src = _trainer(mesh=mesh4, sharding_rules=pt.parallel.replicated())
    src.startup(sample_feed=feed)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)

    mesh2 = pt.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tgt = _trainer(mesh=mesh2, sharding_rules=pt.parallel.replicated())
    tgt.startup(sample_feed=feed)
    cache = DeviceCache(budget_bytes=1 << 30, trainer=tgt)
    assert cache.offer(1, tgt._put_feed(feed))
    cache.seal(1)
    tgt.device_cache = cache
    assert cache.ready
    resilience.reshard_restore(ck, tgt, sample_feed=feed)
    assert cache.state == "invalid"
    assert cache.invalid_reason == "reshard_restore"
    assert not cache.ready and cache.resident_bytes == 0


# ---------------------------------------------------------------------------
# on-device augmentation
# ---------------------------------------------------------------------------


def _img_model(image, label):
    """(bs, 28, 28) image -> flatten -> linear, so crop/flip have real
    spatial axes to work on."""
    import jax.numpy as jnp
    from paddle_tpu.framework import create_parameter
    w = create_parameter((784, 10), name="fc/w")
    h = jnp.matmul(image.reshape((image.shape[0], -1)), w)
    lab = jnp.squeeze(label, -1)
    logp = jax.nn.log_softmax(h)
    return {"loss": -jnp.mean(jnp.take_along_axis(
        logp, lab[:, None], axis=1))}


AUG = {"image": AugmentSpec()
       .random_crop(padding=2, axes=(1, 2))
       .random_flip(axis=2)
       .normalize(mean=127.0, std=64.0)}


def _img_feeds(n, bs=8, seed=3):
    r = np.random.RandomState(seed)
    return [{"image": r.randint(0, 256, (bs, 28, 28)).astype(np.uint8),
             "label": r.randint(0, 10, (bs, 1)).astype(np.int64)}
            for _ in range(n)]


def _aug_trainer():
    tr = pt.Trainer(pt.build(_img_model), opt.SGD(0.1), loss_name="loss",
                    augment=AUG)
    tr.startup(sample_feed=_img_feeds(1)[0])
    return tr


def test_augment_fused_k_equals_sequential_exactly():
    from paddle_tpu.data.feeder import stack_batches
    feeds = _img_feeds(4)
    t_seq = _aug_trainer()
    seq = [float(t_seq.step(f)["loss"]) for f in feeds]
    t_fused = _aug_trainer()
    fused = np.asarray(t_fused.run_steps(stack_batches(feeds))["loss"])
    np.testing.assert_array_equal(fused, np.asarray(seq, fused.dtype))
    _assert_scopes_equal(t_seq.scope, t_fused.scope)


def test_augment_randomness_advances_with_global_step():
    feeds = _img_feeds(1)
    tr = _aug_trainer()
    l0 = float(tr.step(feeds[0])["loss"])
    l1 = float(tr.step(feeds[0])["loss"])  # same batch, new step rng
    # same data, different crop/flip draw (and one SGD update): the
    # point is the stream ADVANCES — identical values would mean the
    # augmentation rng is frozen
    assert l0 != l1


def test_augment_eval_applies_only_deterministic_ops():
    feeds = _img_feeds(2)
    tr = _aug_trainer()
    a = np.asarray(tr.eval(feeds[0])["loss"])
    b = np.asarray(tr.eval(feeds[0])["loss"])
    np.testing.assert_array_equal(a, b)  # no randomness in eval
    # eval equals a normalize-only trainer's eval: crop/flip skipped
    tn = pt.Trainer(pt.build(_img_model), opt.SGD(0.1), loss_name="loss",
                    augment={"image": AugmentSpec().normalize(127.0, 64.0)})
    tn.startup(sample_feed=feeds[0])
    np.testing.assert_array_equal(a, np.asarray(tn.eval(feeds[0])["loss"]))


def test_augment_init_sees_logical_dtype_and_cache_composes():
    # uint8 feed + normalize: the model initializes at float32, and the
    # cache serves augmented training bit-identically (augment runs
    # inside the step, downstream of the cached encoded feed)
    batches = [[(s["image"][i], s["label"][i]) for i in range(8)]
               for s in _img_feeds(4, seed=5)]

    def run(device_cache=None):
        losses = []
        tr = pt.Trainer(pt.build(_img_model), opt.SGD(0.1),
                        loss_name="loss", augment=AUG)
        tr.startup(sample_feed=_img_feeds(1, seed=5)[0])
        pt.fit(tr, lambda: iter(batches), num_epochs=2,
               feed_names=["image", "label"], dtypes=["uint8", "int64"],
               steps_per_dispatch=2, device_cache=device_cache,
               event_handler=lambda e: losses.extend(
                   np.asarray(e.metrics["loss"]).reshape(-1).tolist())
               if e.kind == "end_step" else None)
        return tr, losses

    ref, ref_losses = run()
    tr, losses = run(device_cache=1 << 30)
    assert losses == ref_losses
    _assert_scopes_equal(ref.scope, tr.scope)


def test_augment_field_stream_stable_under_table_extension():
    """A field's augmentation stream is keyed by its NAME, not its
    table position: adding an unrelated field must not perturb the
    'image' field's crops/flips (the resumed-run-with-extended-table
    reproducibility contract)."""
    spec = AugmentSpec().random_flip(axis=2)
    feed = _img_feeds(1)[0]
    key = jax.random.PRNGKey(7)
    a = FeedAugment({"image": spec}).apply(feed, key, training=True)
    extended = dict(feed, aaa=np.zeros((feed["image"].shape[0], 3, 3),
                                       np.float32))
    b = FeedAugment({"image": spec,
                     "aaa": AugmentSpec().random_flip(axis=2)}).apply(
        extended, key, training=True)
    np.testing.assert_array_equal(np.asarray(a["image"]),
                                  np.asarray(b["image"]))


def test_augment_spec_validation():
    from paddle_tpu.core.errors import EnforceError
    with pytest.raises(EnforceError, match="batch"):
        AugmentSpec().random_flip(axis=0)
    with pytest.raises(EnforceError, match="padding"):
        AugmentSpec().random_crop(padding=0)
    with pytest.raises(EnforceError, match="std"):
        AugmentSpec().normalize(std=0.0)
    with pytest.raises(EnforceError, match="AugmentSpec"):
        FeedAugment({"x": "flip"})
    # value semantics: builders return new specs
    base = AugmentSpec()
    assert base.normalize() is not base and base.ops == ()


# ---------------------------------------------------------------------------
# slow-link overlap: the staging ring vs the blocking put
# ---------------------------------------------------------------------------


def _overlap_epoch(depth, delay_ms=30.0, chunks=6, consume_s=0.010):
    done = []

    def gen():
        for i in range(chunks):
            yield {"x": np.full((64,), i, np.float32)}

    m = PipelineMetrics()
    f = DeviceFeeder(gen, metrics=m, wait_fn=faults.slow_h2d(delay_ms),
                     overlap_depth=depth)
    t0 = time.perf_counter()
    for item in f:
        time.sleep(consume_s)  # the consumer's "K-step scan"
        done.append(item)
    dt = time.perf_counter() - t0
    assert len(done) == chunks
    return dt, m.report()


def test_slow_link_overlap_recovers_throughput():
    """The h2d-starved case: a 30 ms/chunk link against a 10 ms/chunk
    consumer. The blocking put serializes fill-thread work behind each
    transfer (one in flight, ~delay per chunk); the 2-deep ring
    pipelines two transfers and hides the consumer's time under them.
    What is held is the report's own accounting of each arm's transfer
    seconds, which other workers' load stretches but cannot turn round:
    a ratio of the two arms' raced wall-clock epochs failed by the
    host's mood (ROADMAP D13; the bench `device_cache` row records the
    real delta)."""
    _, rep_block = _overlap_epoch(depth=1)
    _, rep_overlap = _overlap_epoch(depth=2)
    # both arms saw the same simulated link in h2d (full transfer wall:
    # a sleep is never shorter than asked)
    assert rep_block["stages_s"]["h2d"] >= 0.9 * 6 * 0.030
    assert rep_overlap["stages_s"]["h2d"] >= 0.9 * 6 * 0.030
    # attribution: the blocking put hid none of it ...
    assert rep_block["overlap_hidden_s"] == 0.0
    assert rep_block["h2d_exposed_s"] == pytest.approx(
        rep_block["stages_s"]["h2d"], abs=1e-5)
    # ... the ring hid transfer time: its first chunk goes into an empty
    # ring, so the fill thread paid that put call and no wait, and the
    # whole of that transfer's 30 ms ran under other work, whatever the
    # scheduler did to the rest (half of it asked for: the put call's own
    # time is exposed)
    assert rep_overlap["overlap_hidden_s"] >= 0.5 * 0.030
    assert rep_overlap["h2d_exposed_s"] < rep_overlap["stages_s"]["h2d"]

    # the share of the link's seconds the pipeline stalled for, from
    # those seconds: all of them behind the blocking put, fewer in the ring
    def exposed_share(rep):
        return rep["h2d_exposed_s"] / rep["stages_s"]["h2d"]

    assert exposed_share(rep_overlap) < exposed_share(rep_block)


def test_staging_ring_reader_error_still_propagates():
    def bad():
        yield {"x": np.zeros((4,), np.float32)}
        raise RuntimeError("reader exploded")

    f = DeviceFeeder(bad, metrics=PipelineMetrics())
    got = []
    with pytest.raises(RuntimeError, match="reader exploded"):
        for item in f:
            got.append(item)
    assert len(got) == 1  # the good batch drained first


def test_staging_ring_wait_error_propagates_and_unblocks():
    def boom(dev, t_submit):
        raise OSError("DMA engine fell over")

    def gen():
        for i in range(4):
            yield {"x": np.zeros((4,), np.float32)}

    f = DeviceFeeder(gen, metrics=PipelineMetrics(), wait_fn=boom)
    with pytest.raises(OSError, match="DMA"):
        list(f)
    f.close()  # no hung threads
    assert not any(t.is_alive() for t in f._threads)


def test_h2d_mbps_excludes_cache_served_chunks():
    m = PipelineMetrics()
    m.record_h2d(1_000_000, 0.1)          # a real 10 MB/s transfer
    m.record_cache_hit(50_000_000)        # a served chunk: free
    rep = m.report()
    assert rep["h2d_mbps"] == 10.0        # the link, not the cache
    assert rep["cache_hit_bytes"] == 50_000_000
    assert rep["cache_hits"] == 1
    assert rep["chunks"] == 1             # transfers only


def test_device_feed_byte_accounting():
    feed = {"x": jax.device_put(np.zeros((8, 4), np.uint8)),
            "y": np.zeros((8, 1), np.int64)}
    assert device_feed_nbytes(feed) == 8 * 4 + 8 * 8
    assert device_feed_resident_nbytes(feed) > 0


# ---------------------------------------------------------------------------
# the feed:cacheable-dataset lint
# ---------------------------------------------------------------------------


def test_lint_cacheable_dataset_fires_and_suppresses():
    batches = _batches(1)
    feed = _sample(batches)
    tr = _trainer()
    tr.startup(sample_feed=feed)
    # multi-epoch, dataset fits the (explicit) budget, cache off: flag
    rep = analysis.check_trainer(tr, feed, num_epochs=5,
                                 dataset_batches=100,
                                 hbm_budget_bytes=1 << 30)
    hits = rep.by_code("feed:cacheable-dataset")
    assert [f.where for f in hits] == ["device_cache"], rep.render()
    assert "device_cache=True" in hits[0].message
    assert rep.ok("warning")  # advisory

    # cache already on: not re-suggested
    rep2 = analysis.check_trainer(tr, feed, num_epochs=5,
                                  dataset_batches=100,
                                  hbm_budget_bytes=1 << 30,
                                  device_cache=True)
    assert not rep2.by_code("feed:cacheable-dataset"), rep2.render()

    # dataset does NOT fit the residual budget: silent
    rep3 = analysis.check_trainer(tr, feed, num_epochs=5,
                                  dataset_batches=100,
                                  hbm_budget_bytes=1 << 20)
    assert not rep3.by_code("feed:cacheable-dataset"), rep3.render()

    # single epoch: nothing to cache for
    rep4 = analysis.check_trainer(tr, feed, num_epochs=1,
                                  dataset_batches=100,
                                  hbm_budget_bytes=1 << 30)
    assert not rep4.by_code("feed:cacheable-dataset"), rep4.render()


def test_lint_cacheable_dataset_program_door_takes_explicit_budget():
    batches = _batches(1)
    feed = _sample(batches)
    rep = analysis.check(pt.build(mnist.mlp), feed,
                         feed_wire=IMG_WIRE, num_epochs=3,
                         dataset_batches=50,
                         cache_budget_bytes=1 << 30)
    assert rep.by_code("feed:cacheable-dataset"), rep.render()
