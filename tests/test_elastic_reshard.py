"""Elastic resharding drills (fast, CPU, non-slow): bit-exact
checkpoint restore onto a DIFFERENT mesh (dp N→M in either direction,
``resilience.reshard_restore``), structured errors on the implicit
path (``ReshardError`` instead of a ``device_put`` stack trace),
``fit(resume=True, elastic=True)`` riding through a worker-count change
with pinned step/loss continuity — including across a
``steps_per_dispatch`` change — and the async-PS membership half:
pserver shard split/merge with full state preservation, crash-retryable
migration, and a deterministic kill-a-pserver-mid-split drill. Driven
by ``testing.faults`` (membership_meshes / acting / crashing) so every
drill replays exactly."""

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
from paddle_tpu.parallel import DistStrategy, ShardingRules
from paddle_tpu.testing import faults
from jax.sharding import PartitionSpec as P

DIM, CLASSES, BS, N_BATCHES = 6, 4, 8, 8


def _net(x, label):
    h = L.fc(x, 16, name="fc1")
    logits = L.fc(h, CLASSES, name="fc2")
    return {"loss": L.mean(L.softmax_with_cross_entropy(logits, label))}


_PROG_FN = _net
_FEED = {"x": np.zeros((BS, DIM), np.float32),
         "label": np.zeros((BS, 1), np.int64)}


def _mesh(n):
    return (pt.make_mesh({"dp": n}, devices=jax.devices()[:n])
            if n > 1 else None)


def _trainer(n=1, strategy=None, rules=None, optim=None):
    tr = pt.Trainer(pt.build(_PROG_FN), optim or opt.SGD(0.1),
                    loss_name="loss", mesh=_mesh(n), sharding_rules=rules,
                    strategy=strategy)
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


def _fit(tr, cfg=None, epochs=2, handler=None, **kw):
    return pt.fit(tr, _reader(), num_epochs=epochs,
                  feed_names=["x", "label"], dtypes=["float32", "int64"],
                  checkpoint_config=cfg, event_handler=handler, **kw)


def _params_equal(a, b):
    a, b = jax.device_get(a), jax.device_get(b)
    return set(a) == set(b) and all(np.array_equal(a[k], b[k]) for k in a)


def _flat_equal(tree_a, tree_b):
    fa = pio._flatten(jax.device_get(tree_a))
    fb = pio._flatten(jax.device_get(tree_b))
    return set(fa) == set(fb) and all(np.array_equal(fa[k], fb[k])
                                      for k in fa)


def _manual_continue(tr, meta, epochs=2, n_batches=N_BATCHES):
    """Replicate fit's resumed tail with bare step() calls: skip the
    batches the checkpoint already consumed, then one step per batch
    with the default rng stream — the reference the elastic fit must
    match bit-for-bit."""
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


# -- bit-exact reshard restore, dp N→M ---------------------------------------


@pytest.mark.parametrize("n,m", [(2, 1), (1, 2), (4, 2), (2, 4)])
def test_reshard_restore_bit_exact_params_and_optstate(tmp_path, n, m):
    """Acceptance: a checkpoint saved at dp=N restores at dp=M with
    bit-exact params AND opt_state (both directions, single-device
    included), and the restored trainer steps at the new mesh."""
    src = _trainer(n, optim=opt.Momentum(0.1, 0.9))  # accums: real state
    src.step(_FEED)
    src.step(_FEED)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)

    tgt = _trainer(m, optim=opt.Momentum(0.1, 0.9))
    rep = resilience.reshard_restore(ck, tgt, sample_feed=_FEED)
    assert tgt.global_step == 2
    assert rep["global_step"] == 2 and rep["bytes_moved"] > 0
    want_p, _, want_opt, _ = pio.load_persistables(ck)
    assert _params_equal(want_p, tgt.scope.params)
    assert _flat_equal(want_opt, tgt.scope.opt_state)
    # and the source trainer agrees leaf for leaf (same state, new mesh)
    assert _params_equal(src.scope.params, tgt.scope.params)
    assert np.isfinite(float(tgt.step(_FEED)["loss"]))


def test_reshard_restore_amp_dynamic_loss_scale(tmp_path):
    """The loss-scale carry reshards too: scale/good_steps/overflows
    survive a dp 2→4 restore exactly (the scaler must not re-calibrate
    across a worker-count change)."""
    amp = DistStrategy(loss_scale=2.0 ** 10, dynamic_loss_scale=True)
    src = _trainer(2, strategy=amp)
    src.step(_FEED)
    ls_before = {k: float(v) for k, v in
                 jax.device_get(src.scope.loss_scale_state).items()}
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)

    tgt = _trainer(4, strategy=amp)
    resilience.reshard_restore(ck, tgt, sample_feed=_FEED)
    assert _params_equal(src.scope.params, tgt.scope.params)
    ls_after = {k: float(v) for k, v in
                jax.device_get(tgt.scope.loss_scale_state).items()}
    assert ls_after == ls_before
    assert np.isfinite(float(tgt.step(_FEED)["loss"]))


def test_reshard_restore_param_sharded_rules(tmp_path):
    """Param-SHARDED trainers reshard too: weights sharded over dp at
    N=2 re-place as dp=4 shards (per the target ShardingRules — the
    same normalization training placement uses), bit-exact after
    gather, and the target really is sharded, not silently
    replicated."""
    rules = ShardingRules([(r".*/w$", P(None, "dp"))])
    src = _trainer(2, rules=rules)
    src.step(_FEED)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)

    tgt = _trainer(4, rules=rules)
    resilience.reshard_restore(ck, tgt, sample_feed=_FEED)
    assert _params_equal(src.scope.params, tgt.scope.params)
    spec = tgt.scope.params["fc1/w"].sharding.spec
    assert tuple(spec) == (None, "dp"), spec
    assert np.isfinite(float(tgt.step(_FEED)["loss"]))


# -- structured errors on the implicit path ----------------------------------


def test_mesh_mismatch_is_structured_not_device_put(tmp_path):
    """Satellite: load_trainer / restore_latest on a mesh-axes mismatch
    raise ReshardError naming saved vs. target axes — and resume does
    NOT silently fall back to an older checkpoint saved at the target
    mesh (that would discard progress)."""
    old = _trainer(2)
    old.step(_FEED)
    pio.save_trainer(str(tmp_path / "step_1"), old,
                     extra_meta={"epoch": 0, "epoch_step": 1})
    newer = _trainer(4)
    newer.global_step = 3
    pio.save_trainer(str(tmp_path / "step_3"), newer,
                     extra_meta={"epoch": 0, "epoch_step": 3})

    tgt = _trainer(2)
    with pytest.raises(resilience.ReshardError) as ei:
        pio.load_trainer(str(tmp_path / "step_3"), tgt)
    assert ei.value.saved_axes == {"dp": 4}
    assert ei.value.target_axes == {"dp": 2}
    assert "reshard_restore" in str(ei.value)  # the remedy is named
    # resume scanning re-raises instead of falling back to step_1
    with pytest.raises(resilience.ReshardError):
        resilience.restore_latest(str(tmp_path), _trainer(2))
    # elastic scanning reshards the NEWEST checkpoint instead
    tgt2 = _trainer(2)
    meta = resilience.restore_latest(str(tmp_path), tgt2, elastic=True)
    assert meta is not None and tgt2.global_step == 3


def test_fit_resume_without_elastic_surfaces_cleanly(tmp_path):
    """fit(resume=True) without elastic=True must surface the mesh
    mismatch as the structured ReshardError at startup — not a
    device_put/retrace stack trace mid-run — and fit(elastic=True)
    without resume is a loud misconfiguration."""
    cfg = pt.CheckpointConfig(str(tmp_path), epoch_interval=0,
                              step_interval=4, max_num_checkpoints=3)
    _fit(_trainer(4), cfg, epochs=1)
    with pytest.raises(resilience.ReshardError, match="elastic=True"):
        _fit(_trainer(2), cfg, resume=True)
    with pytest.raises(Exception, match="elastic"):
        _fit(_trainer(2), cfg, elastic=True)


def test_size_one_axes_do_not_trip_the_gate(tmp_path):
    """{"dp": 1} and no mesh place identically — the gate normalizes
    size-1 axes away, so the degenerate mesh round-trips through plain
    load_trainer."""
    src = _trainer(1)  # meshless
    src.step(_FEED)
    ck = str(tmp_path / "ck1")
    pio.save_trainer(ck, src)
    one = pt.Trainer(pt.build(_PROG_FN), opt.SGD(0.1), loss_name="loss",
                     mesh=pt.make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    one.startup(sample_feed=_FEED)
    pio.save_trainer(str(tmp_path / "ck2"), one)  # records {"dp": 1}
    pio.load_trainer(str(tmp_path / "ck2"), src)  # no gate either way
    pio.load_trainer(ck, one)


def test_single_device_checkpoint_is_gated_at_mesh_restore(tmp_path):
    """The 1→N direction is gated too: save_trainer records
    mesh_axes={} for a single-device trainer, so restoring it at dp=N
    without the elastic door is a structured ReshardError — only
    checkpoints that PREDATE mesh metadata pass ungated."""
    src = _trainer(1)
    src.step(_FEED)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)
    assert resilience.read_manifest(ck)["meta"]["mesh_axes"] == {}
    tgt = _trainer(2)
    with pytest.raises(resilience.ReshardError) as ei:
        pio.load_trainer(ck, tgt)
    assert ei.value.saved_axes is None  # normalized: single-device
    assert ei.value.target_axes == {"dp": 2}
    resilience.reshard_restore(ck, tgt, sample_feed=_FEED)
    assert _params_equal(src.scope.params, tgt.scope.params)


def test_infeasible_reshard_raises_before_touching_state(tmp_path):
    """An infeasible pair (batch can't divide the target shards) raises
    ReshardError from reshard_restore BEFORE any trainer state is
    replaced — the trainer keeps training at its own mesh."""
    src = _trainer(2)
    src.step(_FEED)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)
    tgt = _trainer(8)
    before = jax.device_get(tgt.scope.params)
    small = {"x": np.zeros((4, DIM), np.float32),
             "label": np.zeros((4, 1), np.int64)}
    with pytest.raises(resilience.ReshardError, match="does not divide"):
        resilience.reshard_restore(ck, tgt, sample_feed=small)
    assert _params_equal(before, tgt.scope.params)  # untouched
    assert tgt.global_step == 0


def test_elastic_fit_infeasible_batch_is_structured(tmp_path):
    """fit's elastic path peeks one reader batch for the feasibility
    proof: a rejoin whose per-step batch cannot divide the new data
    shards is a structured ReshardError AT STARTUP — never the raw
    put_batch NamedSharding ValueError mid-run."""
    cfg = pt.CheckpointConfig(str(tmp_path), epoch_interval=0,
                              step_interval=2, max_num_checkpoints=2)

    def reader6():  # batch 6: divides dp=2, not dp=4
        rng = np.random.RandomState(5)
        for _ in range(4):
            x = rng.randn(6, DIM).astype(np.float32)
            y = rng.randint(0, CLASSES, (6,)).astype(np.int64)
            yield [(x[j], y[j:j + 1]) for j in range(6)]

    pt.fit(_trainer(2), reader6, num_epochs=1, feed_names=["x", "label"],
           dtypes=["float32", "int64"], checkpoint_config=cfg)
    with pytest.raises(resilience.ReshardError, match="does not divide"):
        pt.fit(_trainer(4), reader6, num_epochs=1,
               feed_names=["x", "label"], dtypes=["float32", "int64"],
               checkpoint_config=cfg, resume=True, elastic=True)


# -- elastic fit: kill-and-rejoin at a different N ---------------------------


def test_elastic_fit_kill_and_rejoin_continuity(tmp_path):
    """Acceptance drill: SIGTERM kills a dp=4 run (boundary checkpoint
    via the preemption path), the job restarts at dp=2 with
    fit(resume=True, elastic=True), and the resumed tail matches a
    bare-step continuation at dp=2 from the same checkpoint bit-for-bit
    — step accounting, loss stream, and final params."""
    mesh4, mesh2 = faults.membership_meshes([4, 2])
    assert [d.id for d in mesh2.devices.ravel()] == [0, 1]  # deterministic
    cfg = pt.CheckpointConfig(str(tmp_path), epoch_interval=0,
                              step_interval=0, max_num_checkpoints=3)

    def kill5(e):
        if e.kind == "end_step" and e.step == 5:
            os.kill(os.getpid(), signal.SIGTERM)

    killed = _fit(_trainer(4), cfg, handler=kill5)
    assert killed.global_step == 5

    losses = []
    rejoined = _fit(_trainer(2), cfg, resume=True, elastic=True,
                    handler=lambda e: losses.append(float(e.metrics["loss"]))
                    if e.kind == "end_step" else None)
    assert rejoined.global_step == 2 * N_BATCHES

    ref = _trainer(2)
    rep = resilience.reshard_restore(str(tmp_path / "step_5"), ref,
                                     sample_feed=_FEED)
    ref_losses = _manual_continue(ref, rep["meta"])
    assert losses == ref_losses
    assert _params_equal(rejoined.scope.params, ref.scope.params)


def test_elastic_fit_rejoin_with_different_steps_per_dispatch(tmp_path):
    """The N→M boundary composes with fused dispatch: a run checkpointed
    under K=2 chunking at dp=2 rejoins at dp=4 with K=3 — chunks
    re-stack over the remaining batches, global-step accounting stays
    exact (remainder singles included), and the fused losses equal the
    sequential continuation."""
    cfg = pt.CheckpointConfig(str(tmp_path), epoch_interval=0,
                              step_interval=2, max_num_checkpoints=3)
    with pytest.raises(faults.InjectedCrash):
        _fit(_trainer(2), cfg, epochs=1, steps_per_dispatch=2,
             handler=faults.crash_at_step(4))
    # the crash fired at the chunk's end_step BEFORE its interval save:
    # newest committed checkpoint is step_2
    newest = resilience.list_checkpoints(str(tmp_path))[-1]
    assert newest.global_step == 2

    losses = []

    def collect(e):
        if e.kind == "end_step":
            losses.extend(np.asarray(e.metrics["loss"]).reshape(-1).tolist())

    rejoined = _fit(_trainer(4), cfg, epochs=1, steps_per_dispatch=3,
                    resume=True, elastic=True, handler=collect)
    assert rejoined.global_step == N_BATCHES

    ref = _trainer(4)
    rep = resilience.reshard_restore(newest.path, ref, sample_feed=_FEED)
    ref_losses = _manual_continue(ref, rep["meta"], epochs=1)
    np.testing.assert_array_equal(np.float32(losses), np.float32(ref_losses))
    assert _params_equal(rejoined.scope.params, ref.scope.params)


# -- async-PS membership change: shard split / merge -------------------------


def _group_kw():
    # tight retry budget so unreachable-server drills fail in ms, not
    # the production 30-retry backoff window
    return dict(retries=3, retry_backoff=0.01, retry_backoff_max=0.05)


def _split_names(old_addrs, new_addrs, n_move=3, n_stay=3):
    """Param names chosen AGAINST the actual server ports so that
    exactly ``n_move`` re-home and ``n_stay`` stay under a resize from
    ``old_addrs`` to ``new_addrs`` — rendezvous owners depend on the
    OS-assigned ephemeral ports, so hardcoded names would make the
    split/merge assertions a coin flip (~2% of runs move none or
    all)."""
    from paddle_tpu.parallel.async_ps import _rendezvous_score

    movers, stayers = [], []
    for i in range(10_000):
        if len(movers) >= n_move and len(stayers) >= n_stay:
            break
        name = f"p{i}"
        old = max(old_addrs, key=lambda a: _rendezvous_score(name, a))
        new = max(new_addrs, key=lambda a: _rendezvous_score(name, a))
        (movers if old != new else stayers).append(name)
    assert len(movers) >= n_move and len(stayers) >= n_stay
    return movers[:n_move], stayers[:n_stay]


def test_ps_shard_group_routing_deterministic_and_covering():
    from paddle_tpu.parallel.async_ps import PServerProcess, PSShardGroup

    with PServerProcess(lr=0.1) as s1, PServerProcess(lr=0.1) as s2:
        g = PSShardGroup([s1.addr, s2.addr], **_group_kw())
        names = [f"layer{i}/w" for i in range(8)]
        for n in names:
            assert g.init_param(n, np.zeros(4, np.float32))
        # stable routing: recomputing owners changes nothing
        owners = {n: g.owner(n) for n in names}
        assert owners == {n: g.owner(n) for n in names}
        smap = g.shard_map()
        assert sorted(sum(smap.values(), [])) == sorted(names)
        # pushes/pulls route to the owner; aggregate status sees all
        for n in names:
            g.push(n, np.ones(4, np.float32))
        assert g.status()["params"] == len(names)
        assert g.status()["pushes"] == len(names)
        np.testing.assert_allclose(g.pull(names[0], (4,)),
                                   -0.1 * np.ones(4), rtol=1e-6)
        g.close()


def test_ps_shard_split_and_merge_preserve_state():
    """Growing the server set moves ~1/N of the shards — with FULL state
    (value + adagrad accumulator + version), so post-split updates
    continue the optimizer trajectory; shrinking moves them back,
    equally lossless."""
    from paddle_tpu.parallel.async_ps import PServerProcess, PSShardGroup

    lr, g1 = 0.5, np.array([1.0, 2.0, 0.5], np.float32)
    with PServerProcess(lr=lr, optimizer="adagrad") as s1, \
            PServerProcess(lr=lr, optimizer="adagrad") as s2:
        g = PSShardGroup([s1.addr], **_group_kw())
        movers, stayers = _split_names([s1.addr], [s1.addr, s2.addr])
        w = {k: np.arange(3, dtype=np.float32) + i
             for i, k in enumerate(movers + stayers)}
        for k, v in w.items():
            g.init_param(k, v)
            g.push(k, g1)
        before = {k: g.pull(k, (3,)) for k in w}

        stale = PSShardGroup([s1.addr], **_group_kw())  # never rebound
        moved = g.resize([s1.addr, s2.addr])
        assert sorted(moved) == sorted(movers)
        assert set(moved) < set(w), "split must not move everything"
        for k in w:
            np.testing.assert_array_equal(g.pull(k, (3,)), before[k])
        # the old owner's copies were DELETEd after the switch: no
        # orphaned shards leaking memory or double-counting the fleet
        assert g.status()["params"] == len(w)
        # ...and a trainer that has NOT rebound fails loudly on a
        # migrated shard instead of silently updating an orphan
        with pytest.raises(RuntimeError, match="unknown param"):
            stale.push(moved[0], g1)
        stale.close()
        # accumulator moved too: a second identical push steps by
        # lr*g/(sqrt(2 g^2)+eps), NOT the fresh-accum lr*g/(sqrt(g^2)+eps)
        k = moved[0]
        g.push(k, g1)
        want = before[k] - lr * g1 / (np.sqrt(2 * g1 * g1) + 1e-6)
        np.testing.assert_allclose(g.pull(k, (3,)), want, rtol=1e-5)

        after_split = {k2: g.pull(k2, (3,)) for k2 in w}
        merged = g.resize([s1.addr])
        assert sorted(merged) == sorted(moved)
        for k2 in w:
            np.testing.assert_array_equal(g.pull(k2, (3,)), after_split[k2])
        g.close()


def test_ps_resize_crash_mid_split_is_retryable():
    """A coordinator crash mid-migration (armed crash point between
    export and import) leaves the OLD routing authoritative; re-running
    resize re-exports and re-imports idempotently — no shard lost, no
    double-applied state."""
    from paddle_tpu.parallel.async_ps import PServerProcess, PSShardGroup

    with PServerProcess(lr=0.1) as s1, PServerProcess(lr=0.1) as s2:
        g = PSShardGroup([s1.addr], **_group_kw())
        movers, stayers = _split_names([s1.addr], [s1.addr, s2.addr])
        w = {k: np.full(3, float(i), np.float32)
             for i, k in enumerate(movers + stayers)}
        for k, v in w.items():
            g.init_param(k, v)
        with faults.crashing("ps_resize:exported"):
            with pytest.raises(faults.InjectedCrash):
                g.resize([s1.addr, s2.addr])  # >=1 mover: the point fires
        # old membership still serves everything
        assert g.addrs == [s1.addr]
        for k, v in w.items():
            np.testing.assert_array_equal(g.pull(k, (3,)), v)
        moved = g.resize([s1.addr, s2.addr])  # retry completes
        assert moved
        for k, v in w.items():
            np.testing.assert_array_equal(g.pull(k, (3,)), v)
        g.close()


def test_kill_pserver_during_shard_split_drill(tmp_path):
    """The deterministic kill-a-pserver-mid-split drill: the import
    TARGET dies at the ps_resize:exported phase (faults.acting — a side
    effect, not a coordinator crash). The migration fails loudly after
    its bounded retries, the old routing stays authoritative, and a
    restarted server (same port, snapshot-recovered) lets the SAME
    resize succeed with state preserved."""
    from paddle_tpu.parallel.async_ps import PServerProcess, PSShardGroup

    snap = str(tmp_path / "s2.snap")
    with PServerProcess(lr=0.1) as s1:
        s2 = PServerProcess(lr=0.1, snapshot_path=snap)
        port2 = s2.port
        try:
            g = PSShardGroup([s1.addr], **_group_kw())
            movers, stayers = _split_names([s1.addr], [s1.addr, s2.addr])
            w = {k: np.full(2, float(i) + 1.0, np.float32)
                 for i, k in enumerate(movers + stayers)}
            for k, v in w.items():
                g.init_param(k, v)
            with faults.acting("ps_resize:exported", s2.stop):
                with pytest.raises(ConnectionError):
                    g.resize([s1.addr, s2.addr])
            assert g.addrs == [s1.addr]  # routing never switched
            for k, v in w.items():
                np.testing.assert_array_equal(g.pull(k, (2,)), v)
            s2 = PServerProcess(port=port2, lr=0.1, snapshot_path=snap)
            moved = g.resize([s1.addr, s2.addr])
            assert moved
            for k, v in w.items():
                np.testing.assert_array_equal(g.pull(k, (2,)), v)
            g.close()
        finally:
            s2.stop()


def test_async_trainer_rides_through_membership_change():
    """AsyncPSTrainer with a server LIST trains through a shard split
    and a merge mid-run: the step loop never changes, pulls stay
    idempotent, and every push is accounted (none silently resent —
    server push counters add up exactly)."""
    from paddle_tpu.parallel.async_ps import (AsyncPSTrainer, PSClient,
                                              PServerProcess)

    feed = {"x": np.random.RandomState(3).randn(BS, DIM).astype(np.float32),
            "label": np.random.RandomState(4).randint(
                0, CLASSES, (BS, 1)).astype(np.int64)}
    with PServerProcess(lr=0.05) as s1, PServerProcess(lr=0.05) as s2:
        t = AsyncPSTrainer(pt.build(_PROG_FN), [s1.addr],
                           fetch_list=["loss"])
        t.startup(sample_feed=feed)
        n_leaves = t.client.status()["params"]
        for _ in range(2):
            assert np.isfinite(float(t.step(feed)["loss"]))
        t.client.resize([s1.addr, s2.addr])       # split mid-run
        for _ in range(2):
            assert np.isfinite(float(t.step(feed)["loss"]))
        t.client.resize([s2.addr])                # merge onto the new one
        for _ in range(2):
            assert np.isfinite(float(t.step(feed)["loss"]))
        assert t.pushes_lost == 0
        # every push of every step landed on exactly one server — summed
        # across the whole fleet's lifetime counters, none lost or resent
        total = sum(PSClient(a).status()["pushes"]
                    for a in (s1.addr, s2.addr))
        assert total == 6 * n_leaves
        t.client.close()


# -- injectors + the report ----------------------------------------------------


def test_membership_injectors_are_deterministic():
    a, b = faults.membership_meshes([4, 2]), faults.membership_meshes([4, 2])
    for ma, mb in zip(a, b):
        assert ma.shape == mb.shape
        assert [d.id for d in ma.devices.ravel()] == \
            [d.id for d in mb.devices.ravel()]
    assert a[0].shape == {"dp": 4} and a[1].shape == {"dp": 2}
    with pytest.raises(ValueError, match="visible_devices"):
        faults.visible_devices(99)


def test_reshard_restore_dp2_to_dp1_moves_bytes_and_lands_on_new_axes(tmp_path):
    """A REAL dp 2->1 reshard-restore on the CPU mesh: the report names
    both meshes, every byte of the checkpoint's files is counted as
    moved, the restore is timed, and the parameters arrive on the target
    trainer's (one-device) mesh."""
    def make(n):  # _trainer(1) has no mesh at all; here dp 1 is a mesh
        tr = pt.Trainer(pt.build(_PROG_FN), opt.SGD(0.1), loss_name="loss",
                        mesh=pt.make_mesh({"dp": n},
                                          devices=jax.devices()[:n]))
        tr.startup(sample_feed=_FEED)
        return tr

    src = make(2)
    src.step(_FEED)
    ck = str(tmp_path / "ck")
    pio.save_trainer(ck, src)
    tgt = make(1)
    rep = resilience.reshard_restore(ck, tgt, sample_feed=_FEED)
    assert rep["saved_axes"] == {"dp": 2} and rep["target_axes"] == {"dp": 1}
    files = resilience.read_manifest(ck)["files"]
    assert rep["bytes_moved"] == sum(f["size"] for f in files.values()) > 0
    assert rep["seconds"] > 0
    assert _params_equal(src.scope.params, tgt.scope.params)
    for v in tgt.scope.params.values():
        assert v.sharding.mesh.shape == {"dp": 1}
