"""Pipeline parallelism as a first-class training path (VERDICT r2 #4):
the zoo transformer's stacked blocks train through pipeline_apply via
Trainer + DistStrategy(pp_microbatches), with loss parity against the
same model trained without a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.parallel import DistStrategy, transformer_tp_rules
from paddle_tpu.parallel.pipeline import bubble_fraction
from paddle_tpu.models import transformer


def _cfg(**kw):
    base = dict(src_vocab=64, trg_vocab=64, d_model=32, d_inner=64,
                num_heads=4, num_encoder_layers=4, num_decoder_layers=4,
                dropout=0.0, stacked=True)
    base.update(kw)
    return transformer.base_config(**base)


def _feed(bs, seq=12, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    src = rng.randint(3, vocab, (bs, seq)).astype(np.int32)
    trg = np.roll(src, 1, axis=1)
    trg[:, 0] = 1
    labels = np.concatenate([trg[:, 1:], np.full((bs, 1), 2)], axis=1).astype(np.int32)
    return {"src_ids": src, "trg_ids": trg, "labels": labels}


def _run_steps(trainer, feeds):
    trainer.startup(sample_feed=feeds[0])
    return [float(trainer.step(f)["loss"]) for f in feeds]


def test_stacked_matches_trainer_single_device():
    """The stacked representation itself trains and learns on one device
    (scan path)."""
    prog = pt.build(transformer.make_model(_cfg()))
    feeds = [_feed(4, seed=i) for i in range(3)]
    losses = _run_steps(pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss"), feeds)
    assert all(np.isfinite(l) for l in losses)


def _stack_from_unstacked(up, L_enc, L_dec):
    """Repack the unstacked transformer's per-layer params into the
    stacked program's param dict (fused qkv layout), so the two
    representations can be compared on identical weights."""

    def stk(names):
        return np.stack([np.asarray(up[n]) for n in names])

    sp = {}
    # encoder: per layer i → layer_norm_{2i} (ln1), mha_i, layer_norm_{2i+1},
    # ffn_i; final LN = layer_norm_{2·L_enc}
    for part, names in {
        "ln1": [f"encoder/layer_norm_{2 * i}" for i in range(L_enc)],
        "ln2": [f"encoder/layer_norm_{2 * i + 1}" for i in range(L_enc)],
    }.items():
        sp[f"encoder/encoder_stack/{part}/scale"] = stk([f"{n}/scale" for n in names])
        sp[f"encoder/encoder_stack/{part}/bias"] = stk([f"{n}/bias" for n in names])
    sp["encoder/encoder_stack/qkv/w"] = np.stack([
        np.stack([np.asarray(up[f"encoder/mha_{i}/{p}_proj/w"]) for p in "qkv"], axis=1)
        for i in range(L_enc)])
    sp["encoder/encoder_stack/qkv/b"] = np.stack([
        np.stack([np.asarray(up[f"encoder/mha_{i}/{p}_proj/b"]) for p in "qkv"])
        for i in range(L_enc)])
    sp["encoder/encoder_stack/out/w"] = stk([f"encoder/mha_{i}/out_proj/w" for i in range(L_enc)])
    sp["encoder/encoder_stack/out/b"] = stk([f"encoder/mha_{i}/out_proj/b" for i in range(L_enc)])
    for part in ("ffn_in", "ffn_out"):
        sp[f"encoder/encoder_stack/{part}/w"] = stk([f"encoder/ffn_{i}/{part}/w" for i in range(L_enc)])
        sp[f"encoder/encoder_stack/{part}/b"] = stk([f"encoder/ffn_{i}/{part}/b" for i in range(L_enc)])
    sp["encoder/layer_norm_0/scale"] = np.asarray(up[f"encoder/layer_norm_{2 * L_enc}/scale"])
    sp["encoder/layer_norm_0/bias"] = np.asarray(up[f"encoder/layer_norm_{2 * L_enc}/bias"])

    # decoder: LN numbering continues after the encoder's; mha/ffn
    # numbering is global across the program
    ln0 = 2 * L_enc + 1
    for part, off in (("ln1", 0), ("lnx", 1), ("ln2", 2)):
        names = [f"decoder/layer_norm_{ln0 + 3 * i + off}" for i in range(L_dec)]
        sp[f"decoder/decoder_stack/{part}/scale"] = stk([f"{n}/scale" for n in names])
        sp[f"decoder/decoder_stack/{part}/bias"] = stk([f"{n}/bias" for n in names])
    self_m = [f"decoder/mha_{L_enc + 2 * i}" for i in range(L_dec)]
    cross_m = [f"decoder/mha_{L_enc + 2 * i + 1}" for i in range(L_dec)]
    sp["decoder/decoder_stack/qkv/w"] = np.stack([
        np.stack([np.asarray(up[f"{m}/{p}_proj/w"]) for p in "qkv"], axis=1)
        for m in self_m])
    sp["decoder/decoder_stack/qkv/b"] = np.stack([
        np.stack([np.asarray(up[f"{m}/{p}_proj/b"]) for p in "qkv"]) for m in self_m])
    sp["decoder/decoder_stack/out/w"] = stk([f"{m}/out_proj/w" for m in self_m])
    sp["decoder/decoder_stack/out/b"] = stk([f"{m}/out_proj/b" for m in self_m])
    sp["decoder/decoder_stack/xq/w"] = stk([f"{m}/q_proj/w" for m in cross_m])
    sp["decoder/decoder_stack/xq/b"] = stk([f"{m}/q_proj/b" for m in cross_m])
    sp["decoder/decoder_stack/xkv/w"] = np.stack([
        np.stack([np.asarray(up[f"{m}/{p}_proj/w"]) for p in "kv"], axis=1)
        for m in cross_m])
    sp["decoder/decoder_stack/xkv/b"] = np.stack([
        np.stack([np.asarray(up[f"{m}/{p}_proj/b"]) for p in "kv"]) for m in cross_m])
    sp["decoder/decoder_stack/xout/w"] = stk([f"{m}/out_proj/w" for m in cross_m])
    sp["decoder/decoder_stack/xout/b"] = stk([f"{m}/out_proj/b" for m in cross_m])
    fin = ln0 + 3 * L_dec
    sp["decoder/layer_norm_1/scale"] = np.asarray(up[f"decoder/layer_norm_{fin}/scale"])
    sp["decoder/layer_norm_1/bias"] = np.asarray(up[f"decoder/layer_norm_{fin}/bias"])
    for part in ("ffn_in", "ffn_out"):
        sp[f"decoder/decoder_stack/{part}/w"] = stk(
            [f"decoder/ffn_{L_enc + i}/{part}/w" for i in range(L_dec)])
        sp[f"decoder/decoder_stack/{part}/b"] = stk(
            [f"decoder/ffn_{L_enc + i}/{part}/b" for i in range(L_dec)])
    for n in ("src/embedding_0/w", "trg/embedding_1/w", "logits_proj_0/w"):
        sp[n] = np.asarray(up[n])
    return {k: jnp.asarray(v) for k, v in sp.items()}


def test_stacked_matches_unstacked_semantics():
    """Same weights, both representations: identical losses and logits —
    pins mask handling, residual order, LN placement, fused-qkv layout
    against the per-layer reference implementation."""
    cfg_u = _cfg(stacked=False)
    cfg_s = _cfg()
    feed = _feed(4)

    prog_u = pt.build(transformer.make_model(cfg_u))
    up, _ = prog_u.init(jax.random.PRNGKey(0), **feed)
    prog_s = pt.build(transformer.make_model(cfg_s))
    sp0, _ = prog_s.init(jax.random.PRNGKey(0), **feed)
    sp = _stack_from_unstacked(up, cfg_u.num_encoder_layers, cfg_u.num_decoder_layers)
    assert set(sp) == set(sp0)
    for k in sp0:
        assert sp[k].shape == sp0[k].shape, k

    out_u, _ = prog_u.apply(up, {}, **feed)
    out_s, _ = prog_s.apply(sp, {}, **feed)
    np.testing.assert_allclose(float(out_s["loss"]), float(out_u["loss"]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_s["logits"]),
                               np.asarray(out_u["logits"]), atol=1e-4, rtol=1e-4)


def test_stacked_decoder_is_causal():
    """Future target tokens must not influence earlier positions'
    logits (the stacked self-attention carries the causal mask)."""
    prog = pt.build(transformer.make_model(_cfg()))
    feed = _feed(4)
    params, _ = prog.init(jax.random.PRNGKey(0), **feed)
    out1, _ = prog.apply(params, {}, **feed)

    feed2 = dict(feed)
    trg = feed["trg_ids"].copy()
    trg[:, 6:] = (trg[:, 6:] + 7) % 61 + 3  # perturb the tail
    feed2["trg_ids"] = trg
    out2, _ = prog.apply(params, {}, **feed2)
    np.testing.assert_allclose(np.asarray(out1["logits"])[:, :6],
                               np.asarray(out2["logits"])[:, :6],
                               atol=1e-5, rtol=1e-5)
    # and the perturbation genuinely changed the tail
    assert not np.allclose(np.asarray(out1["logits"])[:, 6:],
                           np.asarray(out2["logits"])[:, 6:], atol=1e-3)


def test_pipeline_transformer_e2e_loss_parity():
    """dp2×pp4 pipelined training == single-device training, step for
    step (same seed → same stacked init → same losses)."""
    feeds = [_feed(8, seed=i) for i in range(3)]

    prog_ref = pt.build(transformer.make_model(_cfg()))
    ref_losses = _run_steps(
        pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"), feeds)

    mesh = pt.make_mesh({"dp": 2, "pp": 4})
    prog_pp = pt.build(transformer.make_model(_cfg()))
    pp_losses = _run_steps(
        pt.Trainer(prog_pp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=transformer_tp_rules(),
                   strategy=DistStrategy(pp_microbatches=4)),
        feeds)

    np.testing.assert_allclose(pp_losses, ref_losses, atol=2e-4, rtol=2e-4)


def test_pipeline_transformer_3d_dp_tp_pp():
    """dp2×tp2×pp2: stacked blocks tp-shard heads inside each stage and
    psum the projections; losses stay parity with single-device."""
    feeds = [_feed(8, seed=i) for i in range(2)]

    prog_ref = pt.build(transformer.make_model(_cfg()))
    ref_losses = _run_steps(
        pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"), feeds)

    mesh = pt.make_mesh({"dp": 2, "tp": 2, "pp": 2})
    prog_pp = pt.build(transformer.make_model(_cfg()))
    pp_losses = _run_steps(
        pt.Trainer(prog_pp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=transformer_tp_rules(),
                   strategy=DistStrategy(pp_microbatches=4)),
        feeds)

    np.testing.assert_allclose(pp_losses, ref_losses, atol=2e-4, rtol=2e-4)


def test_pipeline_transformer_interleaved_loss_parity():
    """dp2×pp2 with pp_interleave=2 (Megatron virtual stages): each rank
    holds two non-adjacent block chunks; losses stay parity with
    single-device, step for step."""
    feeds = [_feed(8, seed=i) for i in range(3)]

    prog_ref = pt.build(transformer.make_model(_cfg()))
    ref_losses = _run_steps(
        pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"), feeds)

    mesh = pt.make_mesh({"dp": 2, "pp": 2}, devices=jax.devices()[:4])
    prog_pp = pt.build(transformer.make_model(_cfg()))
    pp_losses = _run_steps(
        pt.Trainer(prog_pp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=transformer_tp_rules(),
                   strategy=DistStrategy(pp_microbatches=4,
                                         pp_interleave=2)),
        feeds)

    np.testing.assert_allclose(pp_losses, ref_losses, atol=2e-4, rtol=2e-4)


def test_stacked_params_sharded_over_pp():
    """Structural check: the stacked leaves actually land pp-sharded
    (leading layer dim) under the rule table — exists ≠ integrated was
    the r2 finding; this pins the integration."""
    mesh = pt.make_mesh({"dp": 2, "pp": 4})
    prog = pt.build(transformer.make_model(_cfg()))
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    sharding_rules=transformer_tp_rules(),
                    strategy=DistStrategy(pp_microbatches=4))
    tr.startup(sample_feed=_feed(8))
    qkv = [k for k in tr.scope.params if k.endswith("encoder_stack/qkv/w")]
    assert qkv, sorted(tr.scope.params)[:20]
    spec = tr.scope.params[qkv[0]].sharding.spec
    assert spec[0] == "pp", spec


def test_interleaved_rest_layout_checkpoints_logical(tmp_path):
    """Trainer with pp_interleave=2 stores stacked rows chunk-
    interleaved at rest (Megatron layout, no per-step re-layout), but
    checkpoints in LOGICAL order: a single-device trainer restores the
    npz directly and matches eval; the interleaved trainer restores its
    own checkpoint and keeps training."""
    from paddle_tpu import io as pio

    feed = _feed(8, seed=13)
    mesh = pt.make_mesh({"dp": 2, "pp": 2}, devices=jax.devices()[:4])
    prog = pt.build(transformer.make_model(_cfg()))
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    sharding_rules=transformer_tp_rules(),
                    strategy=DistStrategy(pp_microbatches=4,
                                          pp_interleave=2))
    tr.startup(sample_feed=feed)
    assert tr._pp_perm, "interleaved trainer should have permuted leaves"
    tr.step(feed)
    ev = float(tr.eval(feed)["loss"])
    pio.save_trainer(str(tmp_path / "ck"), tr)

    # logical on disk: a pp-less trainer restores and agrees
    prog_s = pt.build(transformer.make_model(_cfg()))
    tr_s = pt.Trainer(prog_s, opt.Adam(1e-3), loss_name="loss")
    tr_s.startup(sample_feed=feed)
    # a mesh change is explicit now: reshard_restore is the door (the
    # {dp,pp} -> single-device restore is the dp N->1 elastic case)
    pt.resilience.reshard_restore(str(tmp_path / "ck"), tr_s,
                                  sample_feed=feed)
    np.testing.assert_allclose(float(tr_s.eval(feed)["loss"]), ev,
                               atol=2e-4, rtol=2e-4)

    # and the interleaved trainer round-trips its own checkpoint
    before = {k: np.asarray(v) for k, v in tr.scope.params.items()
              if k in tr._pp_perm}
    pio.load_trainer(str(tmp_path / "ck"), tr)
    for k, v in before.items():
        np.testing.assert_allclose(np.asarray(tr.scope.params[k]), v,
                                   atol=1e-6)
    assert np.isfinite(float(tr.step(feed)["loss"]))


def test_pipeline_composes_with_grad_accumulation():
    """pp_microbatches × accum_steps: the scan-microbatched feed halves
    feed the pipeline's own microbatching; parity vs plain single-device
    accumulation."""
    feeds = [_feed(16, seed=9)]

    prog_ref = pt.build(transformer.make_model(_cfg()))
    ref = _run_steps(
        pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss",
                   strategy=DistStrategy(accum_steps=2)), feeds)

    mesh = pt.make_mesh({"dp": 2, "pp": 4})
    prog_pp = pt.build(transformer.make_model(_cfg()))
    pp = _run_steps(
        pt.Trainer(prog_pp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=transformer_tp_rules(),
                   strategy=DistStrategy(accum_steps=2, pp_microbatches=4)),
        feeds)
    np.testing.assert_allclose(pp, ref, atol=2e-4, rtol=2e-4)


def test_pipeline_trained_model_eval_and_reshape_restore(tmp_path):
    """The pp-sharded stacked model evaluates (eval enters the same
    pipeline ctx as training, so its collectives ride the same mesh
    axes) and its sharded checkpoint restores onto a DIFFERENT mesh
    factoring with identical losses (the pserver slice/merge analog,
    io.py:881)."""
    from paddle_tpu import io as pio

    feed = _feed(16, seed=10)
    mesh_a = pt.make_mesh({"dp": 2, "pp": 4})
    prog = pt.build(transformer.make_model(_cfg()))
    tr_a = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh_a,
                      sharding_rules=transformer_tp_rules(),
                      strategy=DistStrategy(pp_microbatches=4))
    tr_a.startup(sample_feed=feed)
    tr_a.step(feed)
    ev = float(tr_a.eval(feed)["loss"])
    assert np.isfinite(ev)
    pio.save_trainer_sharded(str(tmp_path / "ck"), tr_a, async_save=False)

    mesh_b = pt.make_mesh({"dp": 4, "pp": 2})
    prog_b = pt.build(transformer.make_model(_cfg()))
    tr_b = pt.Trainer(prog_b, opt.Adam(1e-3), loss_name="loss", mesh=mesh_b,
                      sharding_rules=transformer_tp_rules(),
                      strategy=DistStrategy(pp_microbatches=4))
    tr_b.startup(sample_feed=feed)
    pio.load_trainer_sharded(str(tmp_path / "ck"), tr_b)
    np.testing.assert_allclose(float(tr_b.eval(feed)["loss"]), ev,
                               atol=1e-5, rtol=1e-5)
    # and training continues on the new factoring
    assert np.isfinite(float(tr_b.step(feed)["loss"]))


def test_stacked_dropout_trains_and_infers():
    """Dropout now works on the scan path (per-layer rng_fold): training
    produces a finite stochastic loss, inference is deterministic and
    matches the dropout-0 program exactly (same params)."""
    prog = pt.build(transformer.make_model(_cfg(dropout=0.3)))
    feed = _feed(4)
    params, state = prog.init(jax.random.PRNGKey(0), **feed)
    out1, _ = prog.apply(params, state, rng=jax.random.PRNGKey(1),
                         training=True, **feed)
    out2, _ = prog.apply(params, state, rng=jax.random.PRNGKey(2),
                         training=True, **feed)
    assert np.isfinite(float(out1["loss"]))
    # different rng -> different dropout masks -> different loss
    assert float(out1["loss"]) != float(out2["loss"])
    # inference: dropout is a no-op, so the dropout-0 program agrees
    ref = pt.build(transformer.make_model(_cfg(dropout=0.0)))
    out_inf, _ = prog.apply(params, state, training=False, **feed)
    ref_inf, _ = ref.apply(params, state, training=False, **feed)
    np.testing.assert_allclose(float(out_inf["loss"]),
                               float(ref_inf["loss"]), rtol=1e-6)


def test_stacked_dropout_masks_decorrelate_across_layers():
    """The scan body is traced once; without rng_fold every layer would
    get the SAME dropout mask. Statistical pin: an L-layer stack of
    dropout-only blocks keeps ~p^L of elements with independent masks
    vs ~p with a shared mask."""
    from paddle_tpu.layers import stacked as S

    p_keep = 0.5
    L, n = 2, 20000

    def make_drop_block(num_heads, use_flash, causal, tp_axis, sp_cfg,
                        dropout_rate=0.0):
        def block(x, lp):
            return S._drop(x, dropout_rate)
        return block

    def net(x):
        stack = {"dummy": jnp.zeros((L, 1))}
        return {"y": S.apply_stacked(x, stack, make_drop_block,
                                     dropout_rate=1 - p_keep)}

    prog = pt.build(net)
    x = np.ones((1, n), np.float32)
    params, state = prog.init(jax.random.PRNGKey(0), x=x)
    out, _ = prog.apply(params, state, rng=jax.random.PRNGKey(3),
                        training=True, x=x)
    frac = float((np.asarray(out["y"]) != 0).mean())
    # independent masks: E[frac]=0.25, sd~0.003; shared mask: 0.5
    assert abs(frac - p_keep ** L) < 0.03,         f"kept {frac:.3f}; shared-mask reuse would keep ~{p_keep}"


@pytest.mark.slow  # 39 s under -n 6 (31 s alone)
def test_dropout_on_pipeline_path():
    """The pipeline schedule threads rng per (layer, microbatch,
    data-shard): training under pp with dropout>0 yields finite,
    step-deterministic, rng-sensitive losses; eval stays deterministic
    (round-4 verdict #5, closing layers/stacked.py's old TODO)."""
    from paddle_tpu.framework import pipeline_mode

    devs = jax.devices("cpu")[:2]
    mesh = jax.sharding.Mesh(np.array(devs).reshape(2), ("pp",))
    prog = pt.build(transformer.make_model(_cfg(dropout=0.3)))
    feed = _feed(4)
    params, state = prog.init(jax.random.PRNGKey(0), **feed)
    with pipeline_mode(mesh, microbatches=2):
        o1, _ = prog.apply(params, state, rng=jax.random.PRNGKey(1),
                           training=True, **feed)
        o1b, _ = prog.apply(params, state, rng=jax.random.PRNGKey(1),
                            training=True, **feed)
        o2, _ = prog.apply(params, state, rng=jax.random.PRNGKey(2),
                           training=True, **feed)
        # same key → same masks; different key → different masks
        np.testing.assert_allclose(float(o1["loss"]), float(o1b["loss"]),
                                   rtol=1e-6)
        assert abs(float(o1["loss"]) - float(o2["loss"])) > 1e-6
        # eval is deterministic (dropout no-op) and matches the scan
        # path bit-for-bit outside the pipeline ctx
        ev, _ = prog.apply(params, state, training=False, **feed)
    ev_scan, _ = prog.apply(params, state, training=False, **feed)
    np.testing.assert_allclose(np.asarray(ev["loss"]),
                               np.asarray(ev_scan["loss"]),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_dropout_masks_decorrelate():
    """Distinct dropout masks per (layer, microbatch): a pp run of an
    identity stack with dropout must not reuse one mask across layers
    or across microbatches (the pre-fix failure mode: the scheduled
    body is traced once, so an unfolded key would repeat)."""
    from paddle_tpu.framework import pipeline_mode
    from paddle_tpu.layers.stacked import apply_stacked

    devs = jax.devices("cpu")[:2]
    mesh = jax.sharding.Mesh(np.array(devs).reshape(2), ("pp",))
    L, B, D = 2, 4, 64
    stacked = {"w": jnp.ones((L, 1), jnp.float32)}

    def make_block(num_heads, use_flash, causal, tp_axis, sp_cfg,
                   dropout_rate=0.0):
        def block(x, lp):
            from paddle_tpu.layers.nn import dropout
            return dropout(x * lp["w"][0], dropout_rate,
                           dropout_implementation="upscale_in_train")
        return block

    def net(x):
        h = apply_stacked(x, stacked, make_block, num_heads=1,
                          dropout_rate=0.5)
        return {"out": h, "loss": jnp.mean(h)}

    prog = pt.build(net)
    x = np.ones((B, D), np.float32)
    params, state = prog.init(jax.random.PRNGKey(0), x=x)
    with pipeline_mode(mesh, microbatches=2):
        out, _ = prog.apply(params, state, rng=jax.random.PRNGKey(7),
                            training=True, x=x)
    kept = np.asarray(out["out"]) != 0.0
    # microbatch 0 = rows [0,2), microbatch 1 = rows [2,4): the two
    # microbatches must see different composite masks
    assert not np.array_equal(kept[:2], kept[2:])
    # and the composite keep-rate of two layers of 0.5-dropout is ~0.25:
    # a single shared mask across layers would leave ~0.5 — distinguish
    rate = kept.mean()
    assert 0.1 < rate < 0.4, rate


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(4, 16) == pytest.approx(3 / 19)
    assert bubble_fraction(1, 8) == 0.0
    # raising microbatches amortizes the bubble monotonically
    fs = [bubble_fraction(4, m) for m in (2, 4, 8, 16, 64)]
    assert fs == sorted(fs, reverse=True)
