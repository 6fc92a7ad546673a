"""Smoke/convergence tests for seq2seq, AlexNet, GoogLeNet, SE-ResNeXt."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.models import convnets, seq2seq


def test_seq2seq_learns_copy():
    model = pt.build(seq2seq.make_model(src_vocab=15, trg_vocab=15, emb_dim=16,
                                        hidden=32))
    rng = np.random.RandomState(0)
    bs, s = 16, 5
    src = rng.randint(3, 15, (bs, s)).astype(np.int64)
    trg = np.zeros_like(src)
    trg[:, 0] = 1
    trg[:, 1:] = src[:, :-1]
    labels = np.concatenate([trg[:, 1:], np.full((bs, 1), 2)], axis=1).astype(np.int64)
    feed = {"src_ids": src, "trg_ids": trg, "labels": labels,
            "src_lengths": np.full((bs,), s, np.int64)}
    trainer = pt.Trainer(model, opt.Adam(5e-3), loss_name="loss")
    trainer.startup(sample_feed=feed)
    losses = [float(trainer.step(feed)["loss"]) for _ in range(80)]
    assert losses[-1] < losses[0] * 0.5, f"{losses[0]} -> {losses[-1]}"


def _img_feed(bs=2, size=64, classes=10):
    rng = np.random.RandomState(0)
    return {"image": rng.randn(bs, 3, size, size).astype(np.float32),
            "label": rng.randint(0, classes, (bs, 1)).astype(np.int64)}


def test_alexnet_step():
    model = pt.build(convnets.make_alexnet(class_num=10))
    feed = _img_feed(size=224)
    trainer = pt.Trainer(model, opt.Momentum(0.01, 0.9), loss_name="loss")
    trainer.startup(sample_feed=feed)
    out = trainer.step(feed)
    assert np.isfinite(float(out["loss"]))


@pytest.mark.slow  # 43 s under -n 6 (23 s alone; 46 s in tier-1's company)
def test_googlenet_step():
    model = pt.build(convnets.make_googlenet(class_num=10))
    feed = _img_feed(size=96)
    trainer = pt.Trainer(model, opt.Momentum(0.01, 0.9), loss_name="loss")
    trainer.startup(sample_feed=feed)
    out = trainer.step(feed)
    assert np.isfinite(float(out["loss"]))


@pytest.mark.slow  # 37 s under -n 6 (21 s alone)
def test_se_resnext_step():
    model = pt.build(convnets.make_se_resnext(depth=50, class_num=10))
    feed = _img_feed(size=64)
    trainer = pt.Trainer(model, opt.Momentum(0.01, 0.9), loss_name="loss")
    trainer.startup(sample_feed=feed)
    out = trainer.step(feed)
    assert np.isfinite(float(out["loss"]))
