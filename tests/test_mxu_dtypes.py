"""MXU dtype regression pins: no f32×f32 matmuls in bf16 train steps.

The bug class: any (bf16, bf16)→f32 dot (``preferred_element_type``)
makes default autodiff compute its backward dots as (f32 cotangent) ×
(f32-upcast operand) — and f32×f32 runs at ~1/8 MXU rate on TPU. Found
three times in round 4 (dense attention backward, flash kernels' f32
operand upcast, MoE expert/dispatch einsums); these lowering-level pins
keep the whole class from regressing anywhere in the bench-path model
zoo. Router/gating dots are exempted by a whitelist of tiny shapes.

Reference analog: the reference pinned kernel dtypes per-op in its
op_test harness (op_test.py:43); XLA owns our kernels, so the pin
moves to the lowered HLO.
"""

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.config import set_flag

from op_test import find_dots


def _f32_dots(model, feed, min_dots=4, allow_trailing=()):
    """Lower grad(loss) and return f32×f32 dots.

    ``allow_trailing``: dims that mark a dot as part of the (legitimate
    f32) gating path — MoE router/dispatch-table dots always carry the
    num_experts or top_k axis as a trailing dim of an operand or the
    output; expert-bank matmuls never do (their trailing dims are
    d_model/d_ff/capacity)."""
    p, s = model.init(jax.random.PRNGKey(0), **feed)

    def loss_fn(p, s, feed):
        out, _ = model.apply(p, s, **feed)
        return out["loss"]

    txt = jax.jit(jax.grad(loss_fn)).lower(p, s, feed).as_text()
    dots = [d[1:] for d in find_dots(txt)]
    assert len(dots) >= min_dots, f"HLO regex matched too few dots: {len(dots)}"

    def gating(dot):
        return any(int(t.split('x')[-2]) in allow_trailing
                   for t in dot if 'x' in t)

    return [d for d in dots
            if d[0].endswith('f32') and d[1].endswith('f32')
            and not (allow_trailing and gating(d))]


@pytest.fixture(autouse=True)
def _bf16_flag():
    from paddle_tpu.framework import amp_guard
    with amp_guard("bfloat16"):
        yield


def test_gpt_train_step_mxu_clean():
    from paddle_tpu.models import gpt
    rng = np.random.RandomState(0)
    cfg = gpt.base_config(vocab_size=128, d_model=64, d_inner=128, num_heads=4,
                          num_layers=1, max_len=32, use_flash=False,
                          fused_ce=True, dtype="bfloat16")
    ids = rng.randint(3, 128, (2, 32)).astype(np.int32)
    bad = _f32_dots(pt.build(gpt.make_model(cfg)),
                    {"ids": ids, "labels": np.roll(ids, -1, 1).astype(np.int32)})
    assert not bad, f"f32xf32 dots in GPT train step: {bad}"


def test_transformer_train_step_mxu_clean():
    from paddle_tpu.models import transformer
    rng = np.random.RandomState(0)
    cfg = transformer.base_config(
        src_vocab=128, trg_vocab=128, d_model=64, d_inner=128, num_heads=4,
        num_encoder_layers=1, num_decoder_layers=1, dropout=0.1,
        dtype="bfloat16", fused_ce=True, fuse_qkv=True)
    feed = {"src_ids": rng.randint(3, 128, (2, 16)).astype(np.int32),
            "trg_ids": rng.randint(3, 128, (2, 16)).astype(np.int32),
            "labels": rng.randint(3, 128, (2, 16)).astype(np.int32)}
    bad = _f32_dots(pt.build(transformer.make_model(cfg)), feed)
    assert not bad, f"f32xf32 dots in transformer train step: {bad}"


def test_moe_train_step_mxu_clean():
    from paddle_tpu.models import moe_transformer as mt
    rng = np.random.RandomState(0)
    cfg = mt.base_config(vocab_size=128, d_model=64, num_heads=4,
                         num_layers=2, num_experts=4, max_len=32,
                         dtype="bfloat16")
    ids = rng.randint(3, 128, (2, 32)).astype(np.int32)
    bad = _f32_dots(pt.build(mt.make_model(cfg)),
                    {"ids": ids, "labels": np.roll(ids, -1, 1).astype(np.int32)},
                    allow_trailing=(cfg.num_experts, cfg.top_k))
    assert not bad, f"f32xf32 dots in MoE train step: {bad}"


def _jaxpr_dots(closed):
    """All dot_general eqns reachable from a jaxpr, descending into
    sub-jaxprs (pallas_call kernel bodies, scan/cond/custom-vjp)."""
    out = []
    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(tuple(str(v.aval.dtype) for v in eqn.invars)
                           + (str(eqn.outvars[0].aval.dtype),))
            for p in eqn.params.values():
                for cand in (p if isinstance(p, (list, tuple)) else (p,)):
                    if hasattr(cand, "eqns"):
                        walk(cand)
                    elif hasattr(cand, "jaxpr") and hasattr(cand.jaxpr, "eqns"):
                        walk(cand.jaxpr)

    walk(closed.jaxpr)
    return out


def test_flash_kernels_dot_operands_stay_bf16():
    """The pallas kernels' dots are invisible to the HLO pins (they
    lower as custom_call); pin their operand dtypes at the jaxpr level.
    A regression to the round-4 f32-operand upcast (every kernel matmul
    at ~1/8 MXU rate) must fail here."""
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_attention as fa

    q = jnp.ones((1, 2, 128, 32), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          block_q=64, block_k=64) ** 2)

    dots = _jaxpr_dots(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    # fwd kernel: s, pv; dq kernel: dp, dq; dkv kernel: dv, dp, dk
    assert len(dots) >= 7, f"expected fwd+dq+dkv kernel dots, got {dots}"
    bad = [d for d in dots if d[0] == "float32" and d[1] == "float32"]
    assert not bad, f"f32-operand dots inside flash kernels: {bad}"


def test_resnet_train_step_mxu_clean():
    from paddle_tpu.framework import layout_mode
    from paddle_tpu.models import resnet
    rng = np.random.RandomState(0)
    with layout_mode("NHWC"):
        model = pt.build(resnet.make_model(depth=50, class_num=10, image_size=32))
    feed = {"image": rng.randn(2, 32, 32, 3).astype(np.float32),
            "label": rng.randint(0, 10, (2, 1)).astype(np.int64)}
    bad = _f32_dots(model, feed, min_dots=2)
    assert not bad, f"f32xf32 dots/convs in ResNet train step: {bad}"


def test_bert_train_step_mxu_clean():
    """BERT pretrain step (attention + pooler + fused-CE MLM head +
    NSP head): the masked-LM gather and the two heads are paths the
    GPT pin does not cover."""
    from paddle_tpu.models import bert
    rng = np.random.RandomState(0)
    cfg = bert.base_config(vocab_size=128, d_model=64, d_inner=128,
                           num_heads=4, num_layers=1, max_len=32,
                           dropout=0.0, use_flash=False, fuse_qkv=True,
                           fused_ce=True, ce_chunk=64, dtype="bfloat16")
    ids = rng.randint(3, 128, (2, 16)).astype(np.int32)
    feed = {
        "input_ids": ids,
        "token_type_ids": np.zeros((2, 16), np.int32),
        "mlm_positions": rng.randint(0, 16, (2, 4)).astype(np.int32),
        "mlm_labels": rng.randint(0, 128, (2, 4, 1)).astype(np.int64),
        "nsp_label": rng.randint(0, 2, (2, 1)).astype(np.int64),
    }
    bad = _f32_dots(pt.build(bert.make_pretrain_model(cfg)), feed)
    assert not bad, f"f32xf32 dots in BERT train step: {bad}"


def test_lstm_train_step_mxu_clean():
    """Fused-gate LSTM backward runs through lax.scan: a f32 carry or
    cotangent upcast would put every per-step gate matmul on the slow
    MXU path — invisible to the transformer pins."""
    from paddle_tpu.models import lstm
    rng = np.random.RandomState(0)
    model = pt.build(lstm.make_model(vocab_size=64, emb_dim=32,
                                     hidden_dim=32, num_layers=2))
    feed = {"word_ids": rng.randint(0, 64, (2, 8)).astype(np.int64),
            "label": rng.randint(0, 2, (2, 1)).astype(np.int64),
            "sequence_length": np.full((2,), 8, np.int64)}
    bad = _f32_dots(model, feed, min_dots=2)
    assert not bad, f"f32xf32 dots in LSTM train step: {bad}"


def test_deepfm_train_step_mxu_clean():
    """DeepFM: FM pairwise interactions + the DNN tower. The FM part is
    einsum-heavy and was never covered by the transformer/conv pins."""
    from paddle_tpu.models import deepfm
    rng = np.random.RandomState(0)
    model = pt.build(deepfm.make_model(num_sparse_fields=5,
                                       sparse_feature_dim=64,
                                       embedding_size=8, num_dense=4,
                                       hidden_dims=(16, 16)))
    feed = {"dense": rng.randn(2, 4).astype(np.float32),
            "sparse_ids": rng.randint(0, 64, (2, 5)).astype(np.int32),
            "label": rng.randint(0, 2, (2, 1)).astype(np.int64)}
    bad = _f32_dots(model, feed, min_dots=2)
    assert not bad, f"f32xf32 dots in DeepFM train step: {bad}"


def test_seq2seq_train_step_mxu_clean():
    """GRU seq2seq with additive attention (the machine-translation
    bench config): the hand-rolled decoder scan cell casts its own
    weights, a path no other pin exercises. The attention-score
    softmax runs f32 by design but feeds no f32 dot (the cast-back
    sits between it and every matmul), so no whitelist is needed."""
    from paddle_tpu.models import seq2seq
    rng = np.random.RandomState(0)
    model = pt.build(seq2seq.make_model(src_vocab=64, trg_vocab=64,
                                        emb_dim=16, hidden=16))
    src = rng.randint(3, 64, (2, 6)).astype(np.int64)
    trg = np.zeros_like(src); trg[:, 0] = 1; trg[:, 1:] = src[:, :-1]
    labels = np.concatenate([trg[:, 1:], np.full((2, 1), 2)], 1).astype(np.int64)
    feed = {"src_ids": src, "trg_ids": trg, "labels": labels,
            "src_lengths": np.full((2,), 6, np.int64)}
    bad = _f32_dots(model, feed, min_dots=2)
    assert not bad, f"f32xf32 dots in seq2seq train step: {bad}"
