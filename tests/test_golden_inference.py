"""Golden-model inference regression — analyzer_*_tester.cc analog.

The reference pins its inference stack by running frozen trained models
through every deployment configuration and comparing outputs
(inference/tests/api/analyzer_resnet50_tester.cc: fp32 vs quantized vs
engine-rewritten, with stated tolerances). Here: train a small
conv+BN+fc classifier to convergence ONCE, export it, then pin the
whole export→AOT-Predictor surface against the trained program:

  * fp32 Predictor == in-process program outputs (the golden),
  * bf16-cast export within bf16 tolerance + top-1 agreement,
  * real-int8-datapath export within quantization tolerance + top-1
    agreement,
  * BN-fold rewrite (quantize.fold_batch_norms) numerically equal to
    the unfolded inference graph,
  * Clone() serves the same outputs as the parent predictor.

Every comparison is against a REAL trained artifact, not random init —
wrong scale handling or a broken rewrite that random weights mask
(e.g. near-zero BN stats) shows up here.
"""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import layers as L
from paddle_tpu import optimizer as opt
from paddle_tpu import quantize


def _net(image, label):
    """Small conv+BN+fc classifier: the three surfaces the deployment
    rewrites touch (conv for int8, BN for folding, fc for both)."""
    x = L.reshape(image, [-1, 1, 12, 12])
    x = L.conv2d(x, num_filters=8, filter_size=3, padding=1,
                 bias_attr=False, name="c0")
    x = L.batch_norm(x, act="relu", name="bn0")
    x = L.pool2d(x, pool_size=2, pool_stride=2, pool_type="max")
    x = L.conv2d(x, num_filters=16, filter_size=3, padding=1, act="relu",
                 name="c1")
    x = L.pool2d(x, pool_size=2, pool_stride=2, pool_type="avg")
    logits = L.fc(x, 4, name="head")
    loss = L.mean(L.softmax_with_cross_entropy(logits, label))
    from paddle_tpu.metrics import accuracy
    return {"loss": loss, "acc": accuracy(logits, label), "logits": logits}


def _batch(rng, n=64):
    img = rng.randn(n, 144).astype(np.float32)
    # 4-way quadrant-marker rule with a clear margin: quickly learnable
    # to ~100% (this is a serving regression, not a learning benchmark —
    # it just needs a genuinely trained, non-random artifact)
    lbl = rng.randint(0, 4, n)
    q = img.reshape(n, 12, 12)
    for i in range(n):
        r0, c0 = [(0, 0), (0, 6), (6, 0), (6, 6)][lbl[i]]
        q[i, r0:r0 + 6, c0:c0 + 6] += 0.6
    return {"image": img, "label": lbl.reshape(n, 1).astype(np.int64)}


@pytest.fixture(scope="module")
def golden():
    """Train once per module; everything else pins against this."""
    rng = np.random.RandomState(0)
    prog = pt.build(_net)
    tr = pt.Trainer(prog, opt.Adam(3e-3), loss_name="loss",
                    fetch_list=["loss", "acc"])
    tr.startup(sample_feed=_batch(rng))
    acc = 0.0
    for step in range(300):
        out = tr.step(_batch(rng))
        acc = float(out["acc"])
        if step > 50 and acc >= 0.97:
            break
    assert acc >= 0.9, f"golden model failed to train (acc={acc})"
    holdout = _batch(np.random.RandomState(999), n=32)
    ref_out, _ = prog.apply(tr.scope.params, tr.scope.state,
                            training=False, **holdout)
    return {"prog": prog, "params": tr.scope.params, "state": tr.scope.state,
            "holdout": holdout, "ref_logits": np.asarray(ref_out["logits"]),
            "acc": acc}


def _export_and_run(golden, params=None, ctx=None, state=None):
    import contextlib
    d = tempfile.mkdtemp()
    params = golden["params"] if params is None else params
    state = golden["state"] if state is None else state
    with (ctx or contextlib.nullcontext()):
        pio.save_inference_model(d, golden["prog"], params, state,
                                 golden["holdout"])
    pred = pio.load_inference_model(d)
    out = pred.run(golden["holdout"])
    return pred, np.asarray(out["logits"]).astype(np.float32)


def test_fp32_predictor_matches_program(golden):
    pred, got = _export_and_run(golden)
    np.testing.assert_allclose(got, golden["ref_logits"], rtol=1e-5, atol=1e-5)
    # Clone serves identical outputs (PaddlePredictor::Clone contract)
    clone_out = pred.clone().run(golden["holdout"])
    np.testing.assert_allclose(np.asarray(clone_out["logits"]), got,
                               rtol=1e-6, atol=1e-6)


def test_bf16_export_within_tolerance(golden):
    bf16_params = quantize.cast_params_for_inference(
        golden["params"], jnp.bfloat16)
    _, got = _export_and_run(golden, params=bf16_params)
    ref = golden["ref_logits"]
    # bf16 has ~3 decimal digits; logits of a trained model are O(1-10)
    rel = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-8)
    assert rel < 0.05, f"bf16 deviation {rel}"
    agree = (got.argmax(1) == ref.argmax(1)).mean()
    assert agree >= 0.95, f"bf16 top-1 agreement {agree}"


def test_int8_export_within_tolerance(golden):
    _, got = _export_and_run(golden, ctx=quantize.int8_serving())
    ref = golden["ref_logits"]
    rel = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-8)
    assert rel < 0.2, f"int8 deviation {rel}"
    agree = (got.argmax(1) == ref.argmax(1)).mean()
    assert agree >= 0.9, f"int8 top-1 agreement {agree}"


def test_bn_fold_rewrite_matches_trained_graph(golden):
    """fold_batch_norms on the TRAINED artifact reproduces the inference
    graph's conv+BN numerically (inference_transpiler conv+BN fuse) —
    random-init BN stats (mean≈0, var≈1) would hide scale bugs that
    trained stats expose."""
    params, state = golden["params"], golden["state"]
    folded = quantize.fold_batch_norms(params, state, [("c0", "bn0")])
    x = jnp.asarray(golden["holdout"]["image"].reshape(-1, 1, 12, 12))
    w = params["c0/w"]
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))

    def conv(v, wgt):
        return jax.lax.conv_general_dilated(v, wgt, (1, 1), [(1, 1), (1, 1)],
                                            dimension_numbers=dn)

    # inference-mode BN on trained moving stats
    g, b = params["bn0/scale"], params["bn0/bias"]
    m, v = state["bn0/moving_mean"], state["bn0/moving_variance"]
    ref = (conv(x, w) - m.reshape(1, -1, 1, 1)) * \
        (g * jax.lax.rsqrt(v + 1e-5)).reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)
    got = conv(x, folded["c0/w"]) + folded["c0/folded_bias"].reshape(1, -1, 1, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
