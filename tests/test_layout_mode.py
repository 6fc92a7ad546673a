"""Ambient NHWC layout (framework.layout_mode) — the TPU-native conv
layout the benchmarks run. NHWC must compute the same function as the
reference's NCHW for every layer and zoo model: weights stay OIHW (one
checkpoint format), only the activation layout changes.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.framework import current_layout, layout_mode
from paddle_tpu.models import convnets, resnet, vgg


def _logits_pair(make_fn, img_hw, n=2, classes=5, seed=0):
    """Build the same model NCHW and ambient-NHWC with shared weights;
    return both logits on the same input."""
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 3, *img_hw).astype(np.float32)
    y = rng.randint(0, classes, (n, 1)).astype(np.int64)
    feed_c = {"image": x, "label": y}
    feed_h = {"image": x.transpose(0, 2, 3, 1), "label": y}

    m_c = pt.build(make_fn())
    with layout_mode("NHWC"):
        m_h = pt.build(make_fn())
    p, s = m_c.init(jax.random.PRNGKey(0), **feed_c)
    p_h, s_h = m_h.init(jax.random.PRNGKey(0), **feed_h)
    assert {k: v.shape for k, v in p.items()} \
        == {k: v.shape for k, v in p_h.items()}, "weight layout must not fork"
    out_c, _ = m_c.apply(p, s, training=False, **feed_c)
    out_h, _ = m_h.apply(p, s_h, training=False, **feed_h)
    return np.asarray(out_c["logits"]), np.asarray(out_h["logits"])


def test_layout_mode_resolution():
    assert current_layout() == "NCHW"
    with layout_mode("NHWC"):
        assert current_layout() == "NHWC"
        assert current_layout("NCHW") == "NCHW"  # explicit wins
        with layout_mode("NCHW"):
            assert current_layout() == "NCHW"
        assert current_layout() == "NHWC"
    assert current_layout() == "NCHW"


def test_program_captures_build_time_layout():
    """The ambient layout at pt.build() time governs LATER traces (init
    runs lazily, outside the with-block)."""
    def net(image):
        h = L.conv2d(image, 4, 3, padding=1, bias_attr=False, name="c")
        return {"y": L.pool2d(h, 2, "max", 2)}

    with layout_mode("NHWC"):
        prog = pt.build(net)
    x = np.random.RandomState(0).randn(2, 8, 8, 3).astype(np.float32)
    params, state = prog.init(jax.random.PRNGKey(0), image=x)  # outside ctx
    out, _ = prog.apply(params, state, image=x)
    assert out["y"].shape == (2, 4, 4, 4)  # NHWC: channels last
    assert params["c/w"].shape == (4, 3, 3, 3)  # weights stay OIHW


def test_conv_pool_bn_nhwc_matches_nchw():
    def net(image, label):
        h = L.conv2d(image, 6, 3, padding=1, bias_attr=False, name="c0")
        h = L.batch_norm(h, act="relu", name="bn")
        h = L.pool2d(h, 2, "avg", 2)
        logits = L.fc(L.to_chw_order(h), 5, name="fc")
        return {"loss": L.mean(L.softmax_with_cross_entropy(logits, label)),
                "logits": logits}

    got_c, got_h = _logits_pair(lambda: net, (8, 8))
    np.testing.assert_allclose(got_h, got_c, rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # 68 s under -n 6 (26 s alone; 89 s in tier-1's company)
def test_googlenet_nhwc_matches_nchw():
    """Inception concat must switch to the channel axis under NHWC."""
    got_c, got_h = _logits_pair(lambda: convnets.make_googlenet(class_num=5),
                                (64, 64))
    np.testing.assert_allclose(got_h, got_c, rtol=2e-4, atol=2e-4)


def test_se_resnext_nhwc_matches_nchw():
    """SE scale broadcast + shortcut channel check under NHWC."""
    got_c, got_h = _logits_pair(
        lambda: convnets.make_se_resnext(depth=50, class_num=5), (64, 64))
    np.testing.assert_allclose(got_h, got_c, rtol=5e-4, atol=5e-4)


def test_alexnet_and_vgg_nhwc_match_nchw():
    got_c, got_h = _logits_pair(lambda: convnets.make_alexnet(class_num=5),
                                (224, 224), n=1)
    np.testing.assert_allclose(got_h, got_c, rtol=2e-4, atol=2e-4)
    got_c, got_h = _logits_pair(lambda: vgg.make_model(depth=16, class_num=5),
                                (32, 32))
    np.testing.assert_allclose(got_h, got_c, rtol=2e-4, atol=2e-4)


def test_nhwc_model_exports_and_serves(tmp_path):
    """save_inference_model of an NHWC-built program: the build-time
    layout must govern the export trace (which runs OUTSIDE the
    layout_mode block), and the AOT Predictor must reproduce the NCHW
    export's outputs on the transposed input."""
    from paddle_tpu import io as pio

    def net(image):
        h = L.conv2d(image, 4, 3, padding=1, bias_attr=False, name="c")
        h = L.batch_norm(h, act="relu", name="bn")
        return {"y": L.fc(L.to_chw_order(h), 3, name="out")}

    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 6, 6).astype(np.float32)

    m_c = pt.build(net)
    with layout_mode("NHWC"):
        m_h = pt.build(net)
    p, s = m_c.init(jax.random.PRNGKey(0), image=x)
    _, s_h = m_h.init(jax.random.PRNGKey(0), image=x.transpose(0, 2, 3, 1))

    d_c, d_h = str(tmp_path / "nchw"), str(tmp_path / "nhwc")
    pio.save_inference_model(d_c, m_c, p, s, {"image": x})
    pio.save_inference_model(d_h, m_h, p, s_h,
                             {"image": x.transpose(0, 2, 3, 1)})
    out_c = pio.load_inference_model(d_c).run({"image": x})
    out_h = pio.load_inference_model(d_h).run(
        {"image": x.transpose(0, 2, 3, 1)})
    np.testing.assert_allclose(np.asarray(out_h["y"]),
                               np.asarray(out_c["y"]), rtol=2e-5, atol=2e-5)
