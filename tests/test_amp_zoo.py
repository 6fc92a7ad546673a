"""Whole-zoo bf16-amp invariant: every model family builds, traces, and
takes one optimizer step under ``amp_guard("bfloat16")``.

The bug class this pins: a hand-rolled scan cell (or any custom math)
that uses f32 parameters without ``cast_compute`` promotes the bf16
carry/activations — either a scan carry dtype error at trace time
(how the seq2seq decoder failed when it joined the bench) or silently
f32 matmuls at ~1/8 MXU rate. One step per family keeps it cheap;
train-path dtype CLEANLINESS (no f32×f32 dots) is pinned separately in
test_mxu_dtypes.py for the bench configs.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.framework import amp_guard

R = np.random.RandomState


def _seq_feed(rng, bs=2, s=6, vocab=32):
    src = rng.randint(3, vocab, (bs, s)).astype(np.int64)
    trg = np.zeros_like(src)
    trg[:, 0] = 1
    trg[:, 1:] = src[:, :-1]
    labels = np.concatenate([trg[:, 1:], np.full((bs, 1), 2)],
                            axis=1).astype(np.int64)
    return {"src_ids": src, "trg_ids": trg, "labels": labels,
            "src_lengths": np.full((bs,), s, np.int64)}


def _zoo():
    rng = R(0)

    def mnist():
        from paddle_tpu.models import mnist as m
        return m.conv_net, {
            "image": rng.randn(2, 1, 28, 28).astype(np.float32),
            "label": rng.randint(0, 10, (2, 1)).astype(np.int64)}

    def fit_a_line():
        from paddle_tpu.models import fit_a_line as m
        return m.make_model(), {
            "x": rng.randn(4, 13).astype(np.float32),
            "y": rng.randn(4, 1).astype(np.float32)}

    def resnet():
        from paddle_tpu.models import resnet as m
        return m.make_model(depth=50, class_num=4, image_size=32), {
            "image": rng.randn(2, 3, 32, 32).astype(np.float32),
            "label": rng.randint(0, 4, (2, 1)).astype(np.int64)}

    def vgg():
        from paddle_tpu.models import vgg as m
        return m.make_model(depth=16, class_num=4, fc_dim=64), {
            "image": rng.randn(2, 3, 32, 32).astype(np.float32),
            "label": rng.randint(0, 4, (2, 1)).astype(np.int64)}

    def lstm():
        from paddle_tpu.models import lstm as m
        return m.make_model(vocab_size=64, emb_dim=16, hidden_dim=16,
                            num_layers=2), {
            "word_ids": rng.randint(0, 64, (2, 6)).astype(np.int64),
            "label": rng.randint(0, 2, (2, 1)).astype(np.int64),
            "sequence_length": np.full((2,), 6, np.int64)}

    def transformer():
        from paddle_tpu.models import transformer as m
        cfg = m.base_config(src_vocab=64, trg_vocab=64, d_model=32,
                            d_inner=64, num_heads=2, num_encoder_layers=1,
                            num_decoder_layers=1, dropout=0.0,
                            dtype="bfloat16", fused_ce=True)
        f = _seq_feed(rng, vocab=64)
        f.pop("src_lengths")
        return m.make_model(cfg), {k: v.astype(np.int32) for k, v in f.items()}

    def seq2seq():
        from paddle_tpu.models import seq2seq as m
        return m.make_model(src_vocab=32, trg_vocab=32, emb_dim=8,
                            hidden=8), _seq_feed(rng)

    def gpt():
        from paddle_tpu.models import gpt as m
        cfg = m.base_config(vocab_size=64, d_model=32, d_inner=64,
                            num_heads=2, num_layers=1, max_len=8,
                            use_flash=False, fused_ce=True, dtype="bfloat16")
        ids = rng.randint(3, 64, (2, 8)).astype(np.int32)
        return m.make_model(cfg), {
            "ids": ids, "labels": np.roll(ids, -1, 1).astype(np.int32)}

    def bert():
        from paddle_tpu.models import bert as m
        cfg = m.base_config(vocab_size=64, d_model=32, d_inner=64,
                            num_heads=2, num_layers=1, max_len=16,
                            dropout=0.0, dtype="bfloat16")
        ids = rng.randint(3, 64, (2, 8)).astype(np.int32)
        return m.make_pretrain_model(cfg), {
            "input_ids": ids,
            "token_type_ids": np.zeros((2, 8), np.int32),
            "mlm_positions": rng.randint(0, 8, (2, 2)).astype(np.int32),
            "mlm_labels": rng.randint(0, 64, (2, 2, 1)).astype(np.int64),
            "nsp_label": rng.randint(0, 2, (2, 1)).astype(np.int64)}

    def moe():
        from paddle_tpu.models import moe_transformer as m
        cfg = m.base_config(vocab_size=64, d_model=32, num_heads=2,
                            num_layers=2, num_experts=2, max_len=8,
                            dtype="bfloat16")
        ids = rng.randint(3, 64, (2, 8)).astype(np.int32)
        return m.make_model(cfg), {
            "ids": ids, "labels": np.roll(ids, -1, 1).astype(np.int32)}

    def deepfm():
        from paddle_tpu.models import deepfm as m
        return m.make_model(num_sparse_fields=4, sparse_feature_dim=32,
                            embedding_size=4, num_dense=3,
                            hidden_dims=(8, 8)), {
            "dense": rng.randn(2, 3).astype(np.float32),
            "sparse_ids": rng.randint(0, 32, (2, 4)).astype(np.int32),
            "label": rng.randint(0, 2, (2, 1)).astype(np.int64)}

    def word2vec():
        from paddle_tpu.models import word2vec as m
        return m.make_model(dict_size=32, emb_dim=8, hidden=16, context=4), {
            "context_ids": rng.randint(0, 32, (2, 4)).astype(np.int64),
            "label": rng.randint(0, 32, (2, 1)).astype(np.int64)}

    def recommender():
        from paddle_tpu.models import recommender as m
        return m.make_model(emb_dim=8, fc_dim=16), {
            "user_id": rng.randint(1, 900, (2, 1)).astype(np.int64),
            "gender_id": rng.randint(0, 2, (2, 1)).astype(np.int64),
            "age_id": rng.randint(0, 7, (2, 1)).astype(np.int64),
            "job_id": rng.randint(0, 21, (2, 1)).astype(np.int64),
            "movie_id": rng.randint(1, 1600, (2, 1)).astype(np.int64),
            "category_ids": rng.randint(0, 18, (2, 3)).astype(np.int64),
            "title_ids": rng.randint(0, 1000, (2, 4)).astype(np.int64),
            "score": rng.rand(2, 1).astype(np.float32) * 5}

    def srl():
        from paddle_tpu.models import srl as m
        return m.make_model(vocab_size=64, num_labels=5, word_dim=8,
                            hidden_dim=16, depth=2), {
            "word_ids": rng.randint(0, 64, (2, 6)).astype(np.int64),
            "mark_ids": rng.randint(0, 2, (2, 6)).astype(np.int64),
            "label": rng.randint(0, 5, (2, 6)).astype(np.int64),
            "lengths": np.full((2,), 6, np.int64)}

    return {f.__name__: f for f in
            [mnist, fit_a_line, resnet, vgg, lstm, transformer, seq2seq,
             gpt, bert, moe, deepfm, word2vec, recommender, srl]}


_ZOO = _zoo()


@pytest.mark.parametrize("family", sorted(_ZOO))
def test_one_train_step_under_bf16_amp(family):
    with amp_guard("bfloat16"):
        model_fn, feed = _ZOO[family]()
        model = pt.build(model_fn)
        trainer = pt.Trainer(model, opt.Adam(1e-3), loss_name="loss")
        trainer.startup(sample_feed=feed)
        out = trainer.step(feed)
        loss = float(out["loss"])
    assert np.isfinite(loss), f"{family}: non-finite loss {loss} under amp"
