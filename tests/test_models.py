"""Model-zoo smoke + convergence tests (book-test analog for each
BASELINE config, at toy scale)."""

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.models import bert, deepfm, lstm, resnet, transformer, vgg, word2vec


@pytest.mark.slow  # 31 s under -n 6 (16 s alone; 44 s in tier-1's company)
def test_resnet50_forward_backward():
    model = pt.build(resnet.make_model(depth=50, class_num=10, image_size=32))
    x = np.random.randn(2, 3, 32, 32).astype(np.float32)
    y = np.random.randint(0, 10, (2, 1)).astype(np.int64)
    trainer = pt.Trainer(model, opt.Momentum(0.1, 0.9), loss_name="loss")
    trainer.startup(sample_feed={"image": x, "label": y})
    # param count sanity: ResNet-50 ImageNet head ~25.5M params; 10-class head smaller
    n_params = sum(int(np.prod(v.shape)) for v in trainer.scope.params.values())
    assert 23e6 < n_params < 26e6, f"ResNet-50 param count off: {n_params}"
    out = trainer.step({"image": x, "label": y})
    assert np.isfinite(float(out["loss"]))


def test_vgg16_forward():
    model = pt.build(vgg.make_model(depth=16, class_num=10))
    x = np.random.randn(2, 3, 32, 32).astype(np.float32)
    y = np.random.randint(0, 10, (2, 1)).astype(np.int64)
    trainer = pt.Trainer(model, opt.SGD(0.01), loss_name="loss")
    trainer.startup(sample_feed={"image": x, "label": y})
    out = trainer.step({"image": x, "label": y})
    assert np.isfinite(float(out["loss"]))


def test_lstm_text_classification_learns():
    model = pt.build(lstm.make_model(vocab_size=50, emb_dim=16, hidden_dim=16,
                                     num_layers=2))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 50, (8, 12)).astype(np.int64)
    # learnable rule: label = whether token 7 appears early
    label = (ids[:, :4] == 7).any(axis=1).astype(np.int64)[:, None]
    seq_len = np.full((8,), 12, np.int64)
    feed = {"word_ids": ids, "label": label, "sequence_length": seq_len}
    trainer = pt.Trainer(model, opt.Adam(0.01), loss_name="loss")
    trainer.startup(sample_feed=feed)
    losses = [float(trainer.step(feed)["loss"]) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5


def test_lstm_sequence_length_masking():
    """Padded positions must not affect pooled output (LoD analog)."""
    model = pt.build(lstm.make_model(vocab_size=20, emb_dim=8, hidden_dim=8,
                                     num_layers=1))
    ids1 = np.zeros((1, 10), np.int64)
    ids1[0, :5] = [3, 4, 5, 6, 7]
    ids2 = ids1.copy()
    ids2[0, 5:] = 9  # different padding content
    label = np.zeros((1, 1), np.int64)
    sl = np.array([5], np.int64)
    f1 = {"word_ids": ids1, "label": label, "sequence_length": sl}
    trainer = pt.Trainer(model, opt.SGD(0.1), loss_name="loss")
    trainer.startup(sample_feed=f1)
    o1 = trainer.eval(f1)
    o2 = trainer.eval({"word_ids": ids2, "label": label, "sequence_length": sl})
    np.testing.assert_allclose(np.asarray(o1["logits"]), np.asarray(o2["logits"]),
                               atol=1e-5)


def _tiny_transformer_cfg(**kw):
    d = dict(src_vocab=60, trg_vocab=60, d_model=32, d_inner=64, num_heads=4,
             num_encoder_layers=2, num_decoder_layers=2, dropout=0.0)
    d.update(kw)
    return transformer.base_config(**d)


def _translation_batch(bs=8, s=16, vocab=60, seed=0):
    rng = np.random.RandomState(seed)
    src = rng.randint(3, vocab, (bs, s)).astype(np.int64)
    trg = np.zeros_like(src)
    trg[:, 0] = 1
    trg[:, 1:] = (src[:, :-1] % (vocab - 3)) + 3
    labels = np.concatenate([trg[:, 1:], np.full((bs, 1), 2)], axis=1).astype(np.int64)
    return {"src_ids": src, "trg_ids": trg, "labels": labels}


def test_transformer_learns_copy_task():
    cfg = _tiny_transformer_cfg()
    model = pt.build(transformer.make_model(cfg))
    feed = _translation_batch()
    trainer = pt.Trainer(model, opt.Adam(1e-3), loss_name="loss")
    trainer.startup(sample_feed=feed)
    losses = [float(trainer.step(feed)["loss"]) for _ in range(40)]
    assert losses[-1] < losses[0] * 0.5, f"{losses[0]} -> {losses[-1]}"


def test_transformer_flash_matches_xla():
    feed = _translation_batch(bs=2, s=32)
    m_x = pt.build(transformer.make_model(_tiny_transformer_cfg(use_flash=False)))
    m_f = pt.build(transformer.make_model(_tiny_transformer_cfg(use_flash=True)))
    p, s = m_x.init(jax.random.PRNGKey(0), **feed)
    out_x, _ = m_x.apply(p, s, **feed)
    out_f, _ = m_f.apply(p, s, **feed)
    np.testing.assert_allclose(float(out_x["loss"]), float(out_f["loss"]),
                               rtol=1e-4)


def _fuse_param_tree(unfused, fused_names):
    """Stitch an unfused q/k/v param tree into the fuse_qkv layout:
    qkv_proj/w = stack([q,k,v], axis=1), kv_proj/w = stack([k,v], 1)."""
    import numpy as _np
    out = {}
    for fname in fused_names:
        for tag, parts in (("qkv_proj", "qkv"), ("kv_proj", "kv")):
            if f"{tag}/w" in fname or f"{tag}/b" in fname:
                leaf = "w" if fname.endswith("/w") else "b"
                prefix = fname[: fname.index(tag)]
                out[fname] = _np.stack(
                    [unfused[f"{prefix}{p}_proj/{leaf}"] for p in parts], axis=-2)
                break
        else:
            out[fname] = unfused[fname]
    return out


def test_transformer_fused_qkv_matches_unfused():
    """fuse_qkv is one [d,3,d] (self) / [d,2,d] (cross) matmul; with
    tied weights the math is identical to three separate projections."""
    feed = _translation_batch(bs=2, s=16)
    m_u = pt.build(transformer.make_model(_tiny_transformer_cfg()))
    m_f = pt.build(transformer.make_model(_tiny_transformer_cfg(fuse_qkv=True)))
    p_u, s_u = m_u.init(jax.random.PRNGKey(0), **feed)
    p_f, s_f = m_f.init(jax.random.PRNGKey(0), **feed)
    assert any(k.endswith("qkv_proj/w") for k in p_f), sorted(p_f)[:5]
    assert any(k.endswith("kv_proj/w") for k in p_f)  # decoder cross-attn
    p_f2 = _fuse_param_tree(p_u, list(p_f))
    out_u, _ = m_u.apply(p_u, s_u, **feed)
    out_f, _ = m_f.apply(p_f2, s_f, **feed)
    np.testing.assert_allclose(float(out_u["loss"]), float(out_f["loss"]),
                               rtol=1e-5)


def test_transformer_fused_qkv_decode_matches():
    """The incremental-decode (KV cache) path honors fuse_qkv and its
    param names round-trip from a trained scope."""
    cfg = _tiny_transformer_cfg(fuse_qkv=True)
    model = pt.build(transformer.make_model(cfg))
    feed = _translation_batch(bs=2, s=8)
    trainer = pt.Trainer(model, opt.Adam(1e-3), loss_name="loss")
    trainer.startup(sample_feed=feed)
    trainer.step(feed)
    dec = pt.build(transformer.make_decoder(cfg, max_len=8))
    out = dec.apply(trainer.scope.params, trainer.scope.state,
                    feed["src_ids"])[0]
    ids = np.asarray(out["ids"])
    assert ids.shape == (2, 8)


def test_transformer_fused_qkv_tp_sharding():
    """Fused [d,3,d] params shard on the last axis over tp with no
    resharding warnings."""
    import warnings
    from paddle_tpu.parallel import sharding as _sh
    mesh = pt.make_mesh({"dp": 2, "tp": 4})
    cfg = _tiny_transformer_cfg(fuse_qkv=True)
    model = pt.build(transformer.make_model(cfg))
    feed = _translation_batch(bs=4)
    trainer = pt.Trainer(model, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                         sharding_rules=pt.parallel.transformer_tp_rules())
    # the one-shot warning dedup would let an earlier test consume the
    # warning this test asserts against — reset it first
    _sh.reset_drop_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        trainer.startup(sample_feed=feed)
    qkvw = [k for k in trainer.scope.params if k.endswith("qkv_proj/w")][0]
    spec = trainer.scope.params[qkvw].sharding.spec
    assert spec[-1] == "tp", f"qkv_proj/w last axis not tp: {spec}"
    out = trainer.step(feed)
    assert np.isfinite(float(out["loss"]))


def test_transformer_tp_sharding_compiles():
    """TP+DP mesh on 8 virtual devices — the multi-chip path at toy size."""
    mesh = pt.make_mesh({"dp": 2, "tp": 4})
    cfg = _tiny_transformer_cfg()
    model = pt.build(transformer.make_model(cfg))
    feed = _translation_batch(bs=4)
    trainer = pt.Trainer(model, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                         sharding_rules=pt.parallel.transformer_tp_rules())
    trainer.startup(sample_feed=feed)
    # check a TP rule actually sharded a weight over tp
    qw = [k for k in trainer.scope.params if k.endswith("q_proj/w")][0]
    sh = trainer.scope.params[qw].sharding
    assert "tp" in str(sh.spec), f"q_proj/w not TP-sharded: {sh.spec}"
    out = trainer.step(feed)
    assert np.isfinite(float(out["loss"]))


def test_deepfm_learns():
    model = pt.build(deepfm.make_model(num_sparse_fields=5, sparse_feature_dim=20,
                                       embedding_size=4, num_dense=3,
                                       hidden_dims=(16, 16)))
    rng = np.random.RandomState(0)
    bs = 64
    dense = rng.randn(bs, 3).astype(np.float32)
    sparse = rng.randint(0, 20, (bs, 5)).astype(np.int64)
    label = (dense.sum(1, keepdims=True) > 0).astype(np.int64)
    feed = {"dense": dense, "sparse_ids": sparse, "label": label}
    trainer = pt.Trainer(model, opt.Adam(0.01), loss_name="loss")
    trainer.startup(sample_feed=feed)
    losses = [float(trainer.step(feed)["loss"]) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.7


def test_bert_pretrain_step():
    cfg = bert.base_config(vocab_size=100, max_len=32, d_model=32, d_inner=64,
                           num_heads=4, num_layers=2, dropout=0.0)
    model = pt.build(bert.make_pretrain_model(cfg))
    rng = np.random.RandomState(0)
    bs, s, m = 2, 16, 3
    feed = {
        "input_ids": rng.randint(0, 100, (bs, s)).astype(np.int64),
        "token_type_ids": np.zeros((bs, s), np.int64),
        "mlm_positions": rng.randint(0, s, (bs, m)).astype(np.int64),
        "mlm_labels": rng.randint(0, 100, (bs, m, 1)).astype(np.int64),
        "nsp_label": rng.randint(0, 2, (bs, 1)).astype(np.int64),
    }
    trainer = pt.Trainer(model, opt.AdamW(1e-3), loss_name="loss")
    trainer.startup(sample_feed=feed)
    o0 = trainer.step(feed)
    o1 = trainer.step(feed)
    assert float(o1["loss"]) < float(o0["loss"])


def test_bert_fused_ce_matches_dense_head():
    """BERT MLM head with chunked logits-free CE == the dense-logits
    head on identical params (loss and gradients) — the bench config's
    path, and the fix for the fsdp scatter-grad resharding."""
    kw = dict(vocab_size=100, max_len=32, d_model=32, d_inner=64,
              num_heads=4, num_layers=2, dropout=0.0)
    rng = np.random.RandomState(0)
    bs, s, m = 2, 16, 3
    feed = {
        "input_ids": rng.randint(0, 100, (bs, s)).astype(np.int32),
        "token_type_ids": np.zeros((bs, s), np.int32),
        "mlm_positions": rng.randint(0, s, (bs, m)).astype(np.int32),
        "mlm_labels": rng.randint(0, 100, (bs, m, 1)).astype(np.int64),
        "nsp_label": rng.randint(0, 2, (bs, 1)).astype(np.int64),
    }
    dense = pt.build(bert.make_pretrain_model(bert.base_config(**kw)))
    fused = pt.build(bert.make_pretrain_model(
        bert.base_config(fused_ce=True, ce_chunk=32, **kw)))
    params, state = dense.init(jax.random.PRNGKey(0), **feed)

    def loss_of(prog):
        def f(p):
            out, _ = prog.apply(p, state, **feed)
            return out["loss"]
        return f

    ld, gd = jax.value_and_grad(loss_of(dense))(params)
    lf, gf = jax.value_and_grad(loss_of(fused))(params)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for k in gd:
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gd[k]),
                                   atol=1e-5, rtol=1e-4, err_msg=k)


def test_word2vec_learns():
    model = pt.build(word2vec.make_model(dict_size=30, emb_dim=8, hidden=32))
    rng = np.random.RandomState(0)
    ctx = rng.randint(0, 30, (32, 4)).astype(np.int64)
    label = ((ctx.sum(axis=1)) % 30)[:, None].astype(np.int64)  # learnable fn
    feed = {"context_ids": ctx, "label": label}
    trainer = pt.Trainer(model, opt.Adam(0.05), loss_name="loss")
    trainer.startup(sample_feed=feed)
    # shared embedding used once
    assert "shared_emb/w" in trainer.scope.params
    losses = [float(trainer.step(feed)["loss"]) for _ in range(60)]
    assert losses[-1] < losses[0] * 0.5


def test_resnet_nhwc_matches_nchw():
    """NHWC (the TPU-native conv layout the benchmark runs) computes the
    same function as the reference's NCHW: identical loss/logits for the
    transposed input with identically-seeded params."""
    import jax

    def tiny(df):
        return resnet.make_model(depth=50, class_num=7, image_size=24,
                                 data_format=df)

    x = np.random.randn(2, 3, 24, 24).astype(np.float32)
    y = np.random.randint(0, 7, (2, 1)).astype(np.int64)
    m_nchw = pt.build(tiny("NCHW"))
    m_nhwc = pt.build(tiny("NHWC"))
    feed_c = {"image": x, "label": y}
    feed_h = {"image": x.transpose(0, 2, 3, 1), "label": y}
    p_c, s_c = m_nchw.init(jax.random.PRNGKey(0), **feed_c)
    p_h, s_h = m_nhwc.init(jax.random.PRNGKey(0), **feed_h)
    # same param tree (conv weights stay OIHW in both layouts)
    assert {k: v.shape for k, v in p_c.items()} \
        == {k: v.shape for k, v in p_h.items()}
    out_c, _ = m_nchw.apply(p_c, s_c, training=False, **feed_c)
    out_h, _ = m_nhwc.apply(p_c, s_h, training=False, **feed_h)
    np.testing.assert_allclose(np.asarray(out_h["logits"]),
                               np.asarray(out_c["logits"]),
                               rtol=2e-4, atol=2e-4)
