"""Chunked (logits-free) softmax CE (ops/fused_ce.py): value+grad
equivalence vs the dense path, with and without label smoothing, plus
the transformer integration flag."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ops.fused_ce import chunked_softmax_cross_entropy


@pytest.mark.parametrize("eps", [0.0, 0.1])
@pytest.mark.parametrize("with_bias", [True, False])
def test_fused_ce_matches_dense(eps, with_bias):
    rng = np.random.RandomState(0)
    n, d, v = 12, 16, 50           # v=50 with chunk=16 -> ragged last chunk
    h = jnp.asarray(rng.randn(n, d).astype(np.float32))
    w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(v).astype(np.float32) * 0.1) if with_bias else None
    lab = jnp.asarray(rng.randint(0, v, n))

    def dense(h, w, b):
        logits = h @ w + (b if b is not None else 0.0)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, lab[:, None], 1)[:, 0]
        return ((1 - eps) * nll - eps * jnp.mean(logp, -1)).sum()

    def fused(h, w, b):
        return chunked_softmax_cross_entropy(h, w, b, lab, eps, 16).sum()

    argnums = (0, 1, 2) if with_bias else (0, 1)
    v1, g1 = jax.value_and_grad(dense, argnums)(h, w, b)
    v2, g2 = jax.value_and_grad(fused, argnums)(h, w, b)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=2e-4, atol=1e-5)


def test_fused_ce_bf16_inputs_close_to_f32():
    rng = np.random.RandomState(1)
    n, d, v = 8, 16, 32
    h = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, v).astype(np.float32) * 0.1
    lab = jnp.asarray(rng.randint(0, v, n))
    f32 = chunked_softmax_cross_entropy(jnp.asarray(h), jnp.asarray(w), None, lab, 0.0, 16)
    bf = chunked_softmax_cross_entropy(jnp.asarray(h, jnp.bfloat16),
                                       jnp.asarray(w, jnp.bfloat16), None, lab, 0.0, 16)
    assert bf.dtype == jnp.float32  # loss always reduces in f32
    np.testing.assert_allclose(np.asarray(f32), np.asarray(bf), rtol=0.05, atol=0.05)


def test_transformer_fused_ce_equals_dense():
    rng = np.random.RandomState(0)
    from paddle_tpu.models import transformer
    feed = {"src_ids": rng.randint(3, 64, (2, 8)).astype(np.int64),
            "trg_ids": rng.randint(3, 64, (2, 8)).astype(np.int64),
            "labels": rng.randint(0, 64, (2, 8)).astype(np.int64)}
    losses, grads = {}, {}
    for fused in (False, True):
        cfg = transformer.base_config(
            src_vocab=64, trg_vocab=64, d_model=16, d_inner=32, num_heads=2,
            num_encoder_layers=1, num_decoder_layers=1, dropout=0.0,
            fused_ce=fused, ce_chunk=16)
        prog = pt.build(transformer.make_model(cfg))
        params, state = prog.init(jax.random.PRNGKey(0), **feed)

        def loss_fn(p):
            out, _ = prog.apply(p, state, **feed)
            return out["loss"]

        losses[fused], grads[fused] = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(float(losses[False]), float(losses[True]), rtol=1e-5)
    for k in grads[False]:
        np.testing.assert_allclose(np.asarray(grads[False][k]),
                                   np.asarray(grads[True][k]), rtol=5e-4, atol=1e-6)
