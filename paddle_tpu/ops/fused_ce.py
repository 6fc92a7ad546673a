"""Chunked (logits-free) softmax cross-entropy for large vocabularies.

New first-class TPU component (SURVEY §7's N16 analog — the kernel work
the reference did with xbyak JIT, applied to the modern hot spot): the
projection-to-vocab + softmax CE at the top of a language model. The
naive path materializes logits [tokens, vocab] (0.5–1 GB/step at
bs·seq=8K, V=32K) purely to reduce them to one scalar. Here the vocab
axis is processed in chunks under ``lax.scan`` with an online
log-sum-exp — peak activation is [tokens, chunk] — and the backward pass
recomputes each chunk's logits from the hidden states (flash-attention's
recompute trick applied to the LM head).

Supports label smoothing over the uniform prior (the Transformer
objective): loss = (1−eps)·nll + eps·(lse − mean_logits) and the exact
matching gradient dlogits = softmax − ((1−eps)·onehot + eps/V).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _chunks(weight, chunk: int):
    d, v = weight.shape
    n = -(-v // chunk)
    pad = n * chunk - v
    wp = jnp.pad(weight, ((0, 0), (0, pad)))
    return wp.reshape(d, n, chunk).transpose(1, 0, 2), n, pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def chunked_softmax_cross_entropy(hidden, weight, bias, labels,
                                  smooth_eps: float = 0.0,
                                  chunk: int = 4096,
                                  logit_dtype=jnp.float32):
    """Per-token CE of softmax(hidden @ weight + bias) vs labels.

    hidden: [n, d] (flatten batch/time first); weight: [d, V];
    bias: [V] or None; labels: [n] int. Returns nll [n] (f32).
    """
    nll, _ = _fwd_stats(hidden, weight, bias, labels, smooth_eps, chunk, logit_dtype)
    return nll


@jax.named_scope("ce")
def _fwd_stats(hidden, weight, bias, labels, smooth_eps, chunk, logit_dtype):
    n_tok, d = hidden.shape
    v = weight.shape[1]
    wc, n_chunks, pad = _chunks(weight, chunk)
    bc = (jnp.pad(bias, (0, pad)) if bias is not None else jnp.zeros(n_chunks * chunk,
          weight.dtype)).reshape(n_chunks, chunk)
    lab = labels.astype(jnp.int32)

    def body(carry, inp):
        m, s, tgt, logit_sum = carry
        w_i, b_i, idx = inp
        # [n, chunk] — the only live logits block. Matmul in the model
        # dtype (bf16 on the MXU) with f32 accumulation.
        logits = jax.lax.dot_general(
            hidden, w_i, (((1,), (0,)), ((), ())),
            preferred_element_type=logit_dtype) + b_i.astype(logit_dtype)[None, :]
        base = idx * chunk
        col = jnp.arange(chunk)[None, :] + base
        valid = col < v                                   # mask the pad tail
        logits = jnp.where(valid, logits, -jnp.inf)
        # online logsumexp
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=1)
        # target logit lives in exactly one chunk
        in_chunk = (lab >= base) & (lab < base + chunk)
        local = jnp.clip(lab - base, 0, chunk - 1)
        tgt = jnp.where(in_chunk, jnp.take_along_axis(logits, local[:, None], axis=1)[:, 0], tgt)
        logit_sum = logit_sum + jnp.sum(jnp.where(valid, logits, 0.0), axis=1)
        return (m_new, s, tgt, logit_sum), None

    m0 = jnp.full((n_tok,), -jnp.inf, logit_dtype)
    s0 = jnp.zeros((n_tok,), logit_dtype)
    t0 = jnp.zeros((n_tok,), logit_dtype)
    ls0 = jnp.zeros((n_tok,), logit_dtype)
    (m, s, tgt, logit_sum), _ = jax.lax.scan(
        body, (m0, s0, t0, ls0), (wc, bc, jnp.arange(n_chunks)))
    lse = m + jnp.log(s)
    nll = (1.0 - smooth_eps) * (lse - tgt) + smooth_eps * (lse - logit_sum / v)
    return nll.astype(jnp.float32), (lse, m)


def _fwd(hidden, weight, bias, labels, smooth_eps, chunk, logit_dtype):
    nll, (lse, _) = _fwd_stats(hidden, weight, bias, labels, smooth_eps, chunk, logit_dtype)
    return nll, (hidden, weight, bias, labels, lse)


@jax.named_scope("ce")  # traced apart from the forward: named apart too
def _bwd(smooth_eps, chunk, logit_dtype, res, g):
    hidden, weight, bias, labels, lse = res
    n_tok, d = hidden.shape
    v = weight.shape[1]
    wc, n_chunks, pad = _chunks(weight, chunk)
    bc = (jnp.pad(bias, (0, pad)) if bias is not None else jnp.zeros(n_chunks * chunk,
          weight.dtype)).reshape(n_chunks, chunk)
    lab = labels.astype(jnp.int32)
    g32 = g.astype(logit_dtype)
    mm_dtype = hidden.dtype   # bf16 matmuls, f32 accumulation

    def body(dh, inp):
        w_i, b_i, idx = inp
        logits = jax.lax.dot_general(
            hidden, w_i, (((1,), (0,)), ((), ())),
            preferred_element_type=logit_dtype) + b_i.astype(logit_dtype)[None, :]
        base = idx * chunk
        col = jnp.arange(chunk)[None, :] + base
        valid = col < v
        p = jnp.exp(jnp.where(valid, logits, -jnp.inf) - lse[:, None])   # softmax chunk
        onehot = (col == lab[:, None]).astype(logit_dtype)
        dlogits = ((p - (1.0 - smooth_eps) * onehot
                    - jnp.where(valid, smooth_eps / v, 0.0)) * g32[:, None]).astype(mm_dtype)
        dh = dh + jax.lax.dot_general(dlogits, w_i, (((1,), (1,)), ((), ())),
                                      preferred_element_type=logit_dtype)
        dw_i = jax.lax.dot_general(hidden, dlogits, (((0,), (0,)), ((), ())),
                                   preferred_element_type=logit_dtype)   # [d, chunk]
        db_i = jnp.sum(dlogits.astype(logit_dtype), axis=0)
        return dh, (dw_i, db_i)

    dh0 = jnp.zeros((n_tok, d), logit_dtype)
    dh, (dw_chunks, db_chunks) = jax.lax.scan(
        body, dh0, (wc, bc, jnp.arange(n_chunks)))
    dw = dw_chunks.transpose(1, 0, 2).reshape(d, n_chunks * chunk)[:, :v]
    db = db_chunks.reshape(-1)[:v]
    d_bias = db.astype(bias.dtype) if bias is not None else None
    return (dh.astype(hidden.dtype), dw.astype(weight.dtype), d_bias, None)


chunked_softmax_cross_entropy.defvjp(_fwd, _bwd)
