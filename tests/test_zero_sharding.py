"""ZeRO-style cross-replica sharded weight update
(``DistStrategy(zero_sharding=True)``): params + optimizer state live
as per-replica 1/N shard rows, gradients reduce-scatter, the update
applies shard-locally, and fresh params all-gather at the top of every
(fused) step.

Pinned here:
- train equivalence vs the replicated update (SGD / Momentum / amp
  dynamic loss scaling) — allclose, NOT bitwise: the exchange program's
  reduce order changes, so exact equality is the wrong contract;
- the bitwise pins that DO hold: fused-K dispatch == K sequential
  steps with the sharded carry donated end-to-end, and
  ``zero_sharding=False`` == no strategy at all (today's path,
  bit-identical);
- composition with ``quantized_allreduce="int8"`` (the error-feedback
  residuals stay shard-local) and the ``collective`` line's
  ``zero`` attribution (all-gather bytes/step);
- shard-aware checkpoints: per-shard ``*.zero{i}.npz`` files, manifest
  + ``meta.zero`` coverage, same-N restore shard-local and bit-exact,
  zero<->replicated restores gated as structured ``ReshardError``,
  N→M via explicit gather-then-repartition (``reshard_restore``);
- the elastic acceptance drill: SIGTERM kills a dp=4 ZeRO run, the job
  rejoins at dp=2 with ``fit(resume=True, elastic=True)``, and the
  resumed tail matches a bare-step continuation bit-for-bit;
- torn/stray shard files: ``restore_latest`` treats a damaged shard
  set as corrupt AS A UNIT (falls back to the previous checkpoint, no
  Frankenstein mix);
- the lint flip (``sharding:replicated-optstate`` quiet under ZeRO,
  ``sharding:zero-active`` info with realized per-device bytes), the
  ``ckpt:zero-mismatch`` finding, the advisor/device-cache HBM
  dividend, and the dividend on the MNIST MLP with its all-gather bytes.
"""

import os
import signal

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import layers as L
from paddle_tpu import optimizer as opt
from paddle_tpu import resilience
from paddle_tpu.data.feeder import DataFeeder
from paddle_tpu.parallel import DistStrategy
from paddle_tpu.testing import faults

DIM, CLASSES, BS, N_BATCHES = 6, 4, 8, 8


def _net(x, label):
    h = L.fc(x, 16, name="fc1")
    logits = L.fc(h, CLASSES, name="fc2")
    return {"loss": L.mean(L.softmax_with_cross_entropy(logits, label))}


_FEED = {"x": np.random.RandomState(3).randn(BS, DIM).astype(np.float32),
         "label": np.random.RandomState(4).randint(
             0, CLASSES, (BS, 1)).astype(np.int64)}

ZERO = DistStrategy(zero_sharding=True)


def _mesh(n):
    return (pt.make_mesh({"dp": n}, devices=jax.devices()[:n])
            if n > 1 else None)


def _trainer(n=4, strategy=ZERO, optim=None, **kw):
    tr = pt.Trainer(pt.build(_net), optim or opt.SGD(0.1),
                    loss_name="loss", mesh=_mesh(n), strategy=strategy, **kw)
    tr.startup(sample_feed=_FEED)
    return tr


def _feeds(k, seed=11):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(BS, DIM).astype(np.float32),
             "label": rng.randint(0, CLASSES, (BS, 1)).astype(np.int64)}
            for _ in range(k)]


def _run(tr, feeds):
    return [float(tr.step(f)["loss"]) for f in feeds]


def _params_equal(a, b):
    a, b = jax.device_get(a), jax.device_get(b)
    return set(a) == set(b) and all(np.array_equal(a[k], b[k]) for k in a)


def _flat_equal(tree_a, tree_b):
    fa = pio._flatten(jax.device_get(tree_a))
    fb = pio._flatten(jax.device_get(tree_b))
    return set(fa) == set(fb) and all(np.array_equal(fa[k], fb[k])
                                      for k in fa)


def _logical(tr):
    return jax.device_get(tr._logical_params())


def _reader(n_batches=N_BATCHES, seed=7):
    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(n_batches):
            x = rng.randn(BS, DIM).astype(np.float32)
            y = rng.randint(0, CLASSES, (BS,)).astype(np.int64)
            yield [(x[j], y[j:j + 1]) for j in range(BS)]
    return reader


def _fit(tr, cfg=None, epochs=2, handler=None, **kw):
    return pt.fit(tr, _reader(), num_epochs=epochs,
                  feed_names=["x", "label"], dtypes=["float32", "int64"],
                  checkpoint_config=cfg, event_handler=handler, **kw)


def _manual_continue(tr, meta, epochs=2, n_batches=N_BATCHES):
    feeder = DataFeeder(["x", "label"], ["float32", "int64"])
    losses = []
    for epoch in range(int(meta.get("epoch", 0)), epochs):
        skip = int(meta.get("epoch_step", 0)) \
            if epoch == int(meta.get("epoch", 0)) else 0
        for i, samples in enumerate(_reader(n_batches)()):
            if i < skip:
                continue
            losses.append(float(tr.step(feeder.feed(samples))["loss"]))
    return losses


# -- train equivalence vs the replicated update ------------------------------


@pytest.mark.parametrize("optim", [lambda: opt.SGD(0.1),
                                   lambda: opt.Momentum(0.05, 0.9)],
                         ids=["sgd", "momentum"])
def test_train_equivalence_vs_replicated(optim):
    """6 steps at dp=4: the sharded update tracks the replicated one to
    float tolerance (the exchange reduce order changes, so bitwise is
    not the contract) and the shard trees really are 1/N rows."""
    feeds = _feeds(6)
    rep = _trainer(4, strategy=None, optim=optim())
    zer = _trainer(4, strategy=ZERO, optim=optim())
    assert zer._zero is not None and zer._zero.n == 4
    for name, leaf in zer.scope.params.items():
        assert leaf.ndim == 2 and leaf.shape[0] == 4, (name, leaf.shape)
    rl, zl = _run(rep, feeds), _run(zer, feeds)
    np.testing.assert_allclose(zl, rl, rtol=1e-5, atol=1e-7)
    want, got = jax.device_get(rep.scope.params), _logical(zer)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-7)


def test_amp_dynamic_loss_scale_composes():
    """ZeRO + amp dynamic loss scaling: losses track the replicated amp
    run and the scaler state stays identical (unscale happens before
    the reduce-scatter, so overflow accounting must not diverge)."""
    amp = dict(loss_scale=2.0 ** 10, dynamic_loss_scale=True)
    feeds = _feeds(5)
    rep = _trainer(4, strategy=DistStrategy(**amp))
    zer = _trainer(4, strategy=DistStrategy(zero_sharding=True, **amp))
    rl, zl = _run(rep, feeds), _run(zer, feeds)
    np.testing.assert_allclose(zl, rl, rtol=1e-5, atol=1e-7)
    ls_rep = jax.device_get(rep.scope.loss_scale_state)
    ls_zer = jax.device_get(zer.scope.loss_scale_state)
    assert {k: float(v) for k, v in ls_rep.items()} \
        == {k: float(v) for k, v in ls_zer.items()}


def test_fused_k_equals_sequential_bitwise():
    """run_steps(K=6) on the sharded carry == 6 sequential step() calls
    BITWISE — loss stream, shard params, and opt state (the fused scan
    must thread the exact same shard trees it donates)."""
    feeds = _feeds(6, seed=13)
    seq = _trainer(4)
    fused = _trainer(4)
    seq_losses = _run(seq, feeds)
    stacked = {k: np.stack([f[k] for f in feeds]) for k in feeds[0]}
    out = fused.run_steps(stacked, k=6)
    fused_losses = np.asarray(out["loss"]).reshape(-1).tolist()
    assert fused_losses == seq_losses
    assert _params_equal(seq.scope.params, fused.scope.params)
    assert _flat_equal(seq.scope.opt_state, fused.scope.opt_state)


def test_zero_off_is_bitwise_noop():
    """zero_sharding=False is today's path bit-for-bit: same losses,
    same params as a strategy-less trainer, and no ZeroSpec is built."""
    feeds = _feeds(4)
    base = _trainer(4, strategy=None)
    off = _trainer(4, strategy=DistStrategy(zero_sharding=False))
    assert off._zero is None
    assert _run(base, feeds) == _run(off, feeds)
    assert _params_equal(base.scope.params, off.scope.params)


def test_quantized_allreduce_int8_composes():
    """ZeRO + int8 quantized exchange: the error-feedback residuals
    live shard-local on the data axis (never replicated back), training
    stays finite and tracks fp32-exchange ZeRO loosely, and the
    collective line carries both attributions."""
    feeds = _feeds(6)
    q = DistStrategy(zero_sharding=True, quantized_allreduce="int8")
    zq = _trainer(4, strategy=q)
    losses = _run(zq, feeds)
    assert np.all(np.isfinite(losses))
    resid = zq.scope.quant_resid
    assert resid, "error-feedback residuals missing"
    for name, leaf in resid.items():
        spec = tuple(leaf.sharding.spec)
        assert spec and spec[0] == "dp", (name, spec)
    coll = zq.collective_bytes
    assert coll["zero"]["shards"] == 4
    assert coll["zero"]["allgather_bytes_per_step"] > 0


# -- shard-aware checkpoints -------------------------------------------------


def test_save_restore_same_n_bitwise(tmp_path):
    """Save at dp=4 after 3 Momentum steps, restore into a fresh dp=4
    ZeRO trainer: shard-local (per-shard row files, no gather), params
    AND opt state bit-exact, manifest covers every shard file, and the
    next step out of each trainer is bitwise identical."""
    feeds = _feeds(4)
    src = _trainer(4, optim=opt.Momentum(0.1, 0.9))
    _run(src, feeds[:3])
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)

    names = sorted(os.listdir(ck))
    assert [f"params.zero{i}.npz" for i in range(4)] == \
        [n for n in names if n.startswith("params.zero")]
    assert [f"opt_state.zero{i}.npz" for i in range(4)] == \
        [n for n in names if n.startswith("opt_state.zero")]
    man = resilience.read_manifest(ck)
    assert man["meta"]["zero_axes"] == {"dp": 4}
    assert man["meta"]["zero"]["shards"] == 4
    for i in range(4):
        assert f"params.zero{i}.npz" in man["files"]

    tgt = _trainer(4, optim=opt.Momentum(0.1, 0.9))
    pio.load_trainer(ck, tgt)
    assert tgt.global_step == src.global_step
    assert _params_equal(src.scope.params, tgt.scope.params)
    assert _flat_equal(src.scope.opt_state, tgt.scope.opt_state)
    a = float(src.step(feeds[3])["loss"])
    b = float(tgt.step(feeds[3])["loss"])
    assert a == b
    assert _params_equal(src.scope.params, tgt.scope.params)


def test_zero_layout_change_is_gated_then_reshardable(tmp_path):
    """zero<->replicated (and zero N→M) restores are structured
    ReshardErrors on the plain path, and reshard_restore performs the
    explicit gather-then-repartition with bytes reported — landing
    bit-exact against the saved logical state."""
    src = _trainer(4, optim=opt.Momentum(0.1, 0.9))
    _run(src, _feeds(3))
    logical_before = _logical(src)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)

    with pytest.raises(resilience.ReshardError, match="zero_sharding"):
        pio.load_trainer(ck, _trainer(4, strategy=None,
                                      optim=opt.Momentum(0.1, 0.9)))
    rep_ck = str(tmp_path / "rep")
    rep_src = _trainer(4, strategy=None, optim=opt.Momentum(0.1, 0.9))
    pio.save_trainer(rep_ck, rep_src)
    with pytest.raises(resilience.ReshardError, match="zero_sharding"):
        pio.load_trainer(rep_ck, _trainer(4, optim=opt.Momentum(0.1, 0.9)))

    # dp 4 -> 2 with ZeRO on both sides: explicit fallback door
    tgt = _trainer(2, optim=opt.Momentum(0.1, 0.9))
    rep = resilience.reshard_restore(ck, tgt, sample_feed=_FEED)
    assert rep["bytes_moved"] > 0
    assert tgt._zero is not None and tgt._zero.n == 2
    got = _logical(tgt)
    assert set(got) == set(logical_before)
    for k in got:
        np.testing.assert_array_equal(got[k], logical_before[k])
    assert np.isfinite(float(tgt.step(_FEED)["loss"]))


def test_elastic_fit_kill_and_rejoin_zero(tmp_path):
    """Acceptance drill with ZeRO on: SIGTERM kills a dp=4 sharded run
    at step 5 (boundary checkpoint writes SHARD manifests), the job
    rejoins at dp=2 with fit(resume=True, elastic=True), and the
    resumed tail matches a bare-step dp=2 continuation bit-for-bit."""
    mesh4, mesh2 = faults.membership_meshes([4, 2])
    cfg = pt.CheckpointConfig(str(tmp_path), epoch_interval=0,
                              step_interval=0, max_num_checkpoints=3)

    def kill5(e):
        if e.kind == "end_step" and e.step == 5:
            os.kill(os.getpid(), signal.SIGTERM)

    killed = _fit(_trainer(4), cfg, handler=kill5)
    assert killed.global_step == 5
    ck = str(tmp_path / "step_5")
    man = resilience.read_manifest(ck)
    assert man["meta"]["zero"]["shards"] == 4
    assert any(n.startswith("params.zero") for n in man["files"])

    losses = []
    rejoined = _fit(_trainer(2), cfg, resume=True, elastic=True,
                    handler=lambda e: losses.append(float(e.metrics["loss"]))
                    if e.kind == "end_step" else None)
    assert rejoined.global_step == 2 * N_BATCHES
    assert rejoined._zero is not None and rejoined._zero.n == 2

    ref = _trainer(2)
    rep = resilience.reshard_restore(ck, ref, sample_feed=_FEED)
    ref_losses = _manual_continue(ref, rep["meta"])
    assert losses == ref_losses
    assert _params_equal(rejoined.scope.params, ref.scope.params)


def test_torn_shard_falls_back_as_unit(tmp_path):
    """One flipped byte in ONE shard file of the newest checkpoint
    condemns the whole checkpoint: restore_latest falls back to the
    previous intact one — never a Frankenstein mix of generations."""
    src = _trainer(4)
    src.step(_FEED)
    src.global_step = 2
    pio.save_trainer(str(tmp_path / "step_2"), src,
                     extra_meta={"epoch": 0, "epoch_step": 2})
    src.step(_FEED)
    src.global_step = 4
    pio.save_trainer(str(tmp_path / "step_4"), src,
                     extra_meta={"epoch": 0, "epoch_step": 4})
    faults.flip_byte(str(tmp_path / "step_4"), name="params.zero1.npz")
    with pytest.raises(resilience.CheckpointCorrupt):
        resilience.validate_checkpoint(str(tmp_path / "step_4"))

    tgt = _trainer(4)
    meta = resilience.restore_latest(str(tmp_path), tgt)
    assert meta is not None and tgt.global_step == 2


def test_stray_shard_file_is_corrupt(tmp_path):
    """A shard file on disk that the manifest does not cover (a mix of
    two checkpoint generations) fails validation as a unit."""
    src = _trainer(4)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)
    with open(os.path.join(ck, "params.zero9.npz"), "wb") as f:
        f.write(b"stray")
    with pytest.raises(resilience.CheckpointCorrupt, match="manifest"):
        resilience.validate_checkpoint(ck)


# -- lint flip, contracts, advisor dividend ----------------------------------


def test_lint_replicated_optstate_flips_to_zero_active():
    """The sharding:replicated-optstate warning goes quiet under ZeRO;
    the companion sharding:zero-active info reports the realized
    per-device opt-state bytes (1/N of the replicated figure)."""
    from paddle_tpu.analysis.contracts import check_artifacts

    rep = _trainer(8, strategy=None, optim=opt.Momentum(0.1, 0.9))
    r1 = check_artifacts(trainer=rep, sample_feed=_FEED,
                         replicated_optstate_bytes=1)
    assert r1.by_code("sharding:replicated-optstate")
    assert not r1.by_code("sharding:zero-active")

    zer = _trainer(8, optim=opt.Momentum(0.1, 0.9))
    r2 = check_artifacts(trainer=zer, sample_feed=_FEED,
                         replicated_optstate_bytes=1)
    assert not r2.by_code("sharding:replicated-optstate")
    info = r2.by_code("sharding:zero-active")
    assert info and info[0].severity == "info"
    assert info[0].data["data_shards"] == 8
    rep_bytes = sum(
        int(np.prod(v.shape or (1,))) * np.dtype(v.dtype).itemsize
        for v in jax.tree.leaves(rep.scope.opt_state))
    assert info[0].data["opt_state_bytes_per_device"] < rep_bytes


def test_check_artifacts_zero_mismatch_finding(tmp_path):
    """check_artifacts understands shard-aware manifests: a ZeRO
    checkpoint against a non-ZeRO trainer (and vice versa) is a
    structured ckpt:zero-mismatch WARNING — while the matching pair
    compares logical-vs-logical specs with no drift noise."""
    from paddle_tpu.analysis.contracts import check_artifacts

    zer = _trainer(4)
    rep = _trainer(4, strategy=None)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, zer)

    r = check_artifacts(trainer=rep, checkpoint_dir=ck, sample_feed=_FEED)
    zm = r.by_code("ckpt:zero-mismatch")
    assert zm and zm[0].severity == "warning"
    assert zm[0].data["got"] == {"dp": 4}
    noise = ("ckpt:missing-entry", "ckpt:extra-entry", "ckpt:shape-drift",
             "ckpt:missing-collection")
    assert not any(r.by_code(c) for c in noise), r.render()

    r2 = check_artifacts(trainer=zer, checkpoint_dir=ck, sample_feed=_FEED)
    assert not r2.by_code("ckpt:zero-mismatch"), r2.render()
    assert not any(r2.by_code(c) for c in noise), r2.render()

    rep_ck = str(tmp_path / "rep")
    pio.save_trainer(rep_ck, rep)
    r3 = check_artifacts(trainer=zer, checkpoint_dir=rep_ck,
                         sample_feed=_FEED)
    assert r3.by_code("ckpt:zero-mismatch")


def test_advisor_dividend_and_device_cache_admits_more():
    """memory_estimate divides opt-state (and param) bytes by the data
    shard count under ZeRO (>= 6x at dp=8 — the acceptance number), so
    residual_hbm_bytes grows and a budget that admitted a partial
    prefix replicated admits STRICTLY MORE chunks sharded."""
    from paddle_tpu.data.device_cache import (DeviceCache,
                                              residual_hbm_bytes)
    from paddle_tpu.profiling.advisor import memory_estimate

    rep = _trainer(8, strategy=None, optim=opt.Momentum(0.1, 0.9))
    zer = _trainer(8, optim=opt.Momentum(0.1, 0.9))
    est_rep = memory_estimate(rep, _FEED, project_remat=False)
    est_zer = memory_estimate(zer, _FEED, project_remat=False)
    assert est_rep["opt_state_bytes"] >= 6 * est_zer["opt_state_bytes"]
    assert est_rep["param_bytes"] >= 6 * est_zer["param_bytes"]
    assert est_zer["opt_state_bytes_logical"] \
        == est_rep["opt_state_bytes_logical"]

    # fixed total budget, chunk-sized offers: the ZeRO trainer's larger
    # residual admits a strictly longer (still partial) prefix
    chunk = {"x": jax.device_put(np.zeros((4, BS, DIM), np.float32)),
             "label": jax.device_put(np.zeros((4, BS, 1), np.int64))}
    from paddle_tpu.data.device_cache import device_feed_resident_nbytes
    chunk_b = device_feed_resident_nbytes(chunk)
    budget = int(est_rep["est_total_bytes"] / 0.8) + 2 * chunk_b

    def admitted(tr):
        res = residual_hbm_bytes(tr, _FEED, hbm_budget_bytes=budget)
        cache = DeviceCache(budget_bytes=res)
        n = 0
        while cache.offer(4, chunk):
            n += 1
            if n > 64:
                break
        return n

    n_rep, n_zer = admitted(rep), admitted(zer)
    assert 0 < n_rep < n_zer, (n_rep, n_zer)


def test_mnist_mlp_opt_hbm_falls_6x_at_dp8_and_allgather_is_counted():
    """The MNIST MLP with Momentum, trained through the fused K-step
    dispatch both ways: per-device optimizer HBM falls >= 6x at dp 8
    (8 shards less the replicated step counter), and the trainer's
    collective attribution counts the top-of-step all-gather: (N-1)
    hops of every parameter's 1/N row, at dp 2 and dp 8."""
    from paddle_tpu.data.feeder import stack_batches
    from paddle_tpu.models import mnist
    from paddle_tpu.profiling.advisor import memory_estimate

    rng = np.random.RandomState(0)
    feeds = [{"image": rng.randn(16, 784).astype(np.float32),
              "label": rng.randint(0, 10, (16, 1)).astype(np.int64)}
             for _ in range(2)]

    def build(n, zero):
        tr = pt.Trainer(pt.build(mnist.mlp), opt.Momentum(0.01, momentum=0.9),
                        loss_name="loss", fetch_list=["loss"], mesh=_mesh(n),
                        sharding_rules=pt.parallel.replicated(),
                        strategy=DistStrategy(zero_sharding=zero))
        tr.startup(sample_feed=feeds[0])
        out = tr.run_steps(tr._put_feed(stack_batches(feeds), stacked=True),
                           k=2)
        assert np.isfinite(np.asarray(out["loss"])).all()
        return tr

    for n in (2, 8):
        rep, zer = build(n, False), build(n, True)
        est_rep = memory_estimate(rep, feeds[0], project_remat=False)
        est_zer = memory_estimate(zer, feeds[0], project_remat=False)
        reduction = est_rep["opt_state_bytes"] / est_zer["opt_state_bytes"]
        assert reduction >= (6.0 if n == 8 else 1.9), (n, reduction)
        assert not (rep.collective_bytes or {}).get("zero")
        param_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                          for v in zer._logical_params().values())
        moved = zer.collective_bytes["zero"]["allgather_bytes_per_step"]
        # rows are padded up to a multiple of N: at least the exact
        # share, and short of one whole copy
        assert (n - 1) * param_bytes // n <= moved < param_bytes, \
            (n, moved, param_bytes)
