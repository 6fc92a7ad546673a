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

Two forms. ``softmax_cross_entropy_sum`` is the training path: it owns
the reduction to one weighted sum, so the cotangent that reaches it is
one scalar and the gradient of a row's logits is known the moment its
logits are. It walks the *rows* in chunks with the whole vocabulary a
chunk: three products a chunk (logits, dh, dW), none made twice, and the
backward pass is a scaling. ``chunked_softmax_cross_entropy`` returns the
per-token values, for a caller that weighs them under a cotangent the op
cannot know while it walks: that takes the recomputation, four products
over padded vocabulary chunks.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

LANES = 128   # the vocabulary is padded to whole lanes, the tail masked


def _chunks(weight, chunk: int):
    d, v = weight.shape
    n = -(-v // chunk)
    pad = n * chunk - v
    wp = jnp.pad(weight, ((0, 0), (0, pad)))
    return wp.reshape(d, n, chunk).transpose(1, 0, 2), n, pad


def _record_plan(**ids):
    """One zero-length span in the program's ring for each loss head
    traced: how it is walked, and where its rows live."""
    from ..core import profiler

    profiler.record_span("ce.plan", time.time_ns(), 0, **ids)


def chunked_softmax_cross_entropy(hidden, weight, bias, labels,
                                  smooth_eps: float = 0.0,
                                  chunk: int = 4096,
                                  logit_dtype=jnp.float32):
    """Per-token CE of softmax(hidden @ weight + bias) vs labels.

    hidden: [n, d] (flatten batch/time first); weight: [d, V];
    bias: [V] or None; labels: [n] int. Returns nll [n] (f32).

    For callers that need the per-token values under an arbitrary
    cotangent, which only recomputation can serve: a chunk's softmax
    needs the log-sum-exp over all ``chunk``-column chunks, so the
    backward pass makes every chunk's logits a second time. A loss that
    is one weighted sum of these values trains through
    ``softmax_cross_entropy_sum``.
    """
    n, v = hidden.shape[0], weight.shape[1]
    chunks = -(-v // chunk)
    _record_plan(rows=n, rows_per_chunk=n, chunks=chunks, vocab=v,
                 vocab_padded=chunks * chunk, products_per_chunk=4,
                 logits_block_bytes=n * chunk * jnp.dtype(logit_dtype).itemsize,
                 form="per_token", sharded_over="", why="")
    return _per_token(hidden, weight, bias, labels, smooth_eps, chunk,
                      logit_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _per_token(hidden, weight, bias, labels, smooth_eps, chunk, logit_dtype):
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


_per_token.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# the training form: the reduction is the op's, the gradients are made
# where the logits are


def softmax_cross_entropy_sum(hidden, weight, bias, labels, token_weights,
                              smooth_eps: float = 0.0,
                              rows_per_chunk: int = 4096):
    """``sum_n token_weights[n] * ce[n]`` (f32 scalar) of
    softmax(hidden @ weight + bias) vs labels, ``ce`` as
    ``chunked_softmax_cross_entropy`` gives it.

    hidden: [n, d]; weight: [d, V]; bias: [V] or None; labels: [n] int;
    token_weights: [n] (no gradient flows to them: 0 for a pad token, the
    ``1 / count`` of a mean). The rows are walked in chunks of at most
    ``rows_per_chunk`` with the whole vocabulary a chunk; under
    differentiation a chunk's logits serve the loss and both gradient
    products and are then dead, and the backward pass scales what the
    forward pass made by the scalar cotangent.

    Under a mesh whose data axes divide the rows the walk runs per shard
    inside ``shard_map`` over those axes, and the loss and the head's
    gradient cross them once, at its exit: left to the partitioner, a
    gradient carried through a scan over sharded rows is all-reduced in
    every iteration.
    """
    if jnp.float16 in (hidden.dtype, weight.dtype):
        # float16 has not the range to hold a gradient that the loss
        # scale has not reached yet: the casts' own transposes round
        # after the scaling
        hidden, weight, bias = (None if x is None else x.astype(jnp.float32)
                                for x in (hidden, weight, bias))
    mesh, axes, why = _row_shards(hidden.shape[0])
    rows = hidden.shape[0] // math.prod(mesh.shape[a] for a in axes)
    r, chunks = _row_chunks(rows, rows_per_chunk)
    v = weight.shape[1]
    v_pad = _whole_lanes(v)
    _record_plan(rows=rows, rows_per_chunk=r, chunks=chunks, vocab=v,
                 vocab_padded=v_pad, products_per_chunk=3,
                 logits_block_bytes=r * v_pad * 4, form="grad_in_forward",
                 sharded_over=",".join(axes), why=why)
    return _ce_sum(hidden, weight, bias, labels.astype(jnp.int32),
                   token_weights.astype(jnp.float32), float(smooth_eps),
                   int(rows_per_chunk), mesh, axes)


def _row_shards(n: int):
    """``(mesh, axes, why)``: the mesh and those of its axes that the
    rows are walked per shard over, or none and why the walk is whole."""
    from ..framework import active_mesh
    from ..parallel.mesh import DATA_AXES, SP, dividing_axes

    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return None, (), "one device"
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None, (), "already per shard"
    if mesh.shape.get(SP, 1) > 1:
        return None, (), "a sequence's rows are sharded over sp"
    axes = dividing_axes(mesh, n, DATA_AXES)
    if not axes:
        return None, (), "no data axis divides the rows"
    return mesh, axes, ""


def _whole_lanes(v: int) -> int:
    return -(-v // LANES) * LANES


def _row_chunks(n: int, rows_per_chunk: int):
    """``(rows a chunk, chunks)``: the fewest chunks of at most
    ``rows_per_chunk`` rows, of equal length so that the padding is under
    one row a chunk."""
    chunks = max(-(-n // rows_per_chunk), 1)
    return -(-n // chunks), chunks


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ce_sum(hidden, weight, bias, labels, token_weights, smooth_eps,
            rows_per_chunk, mesh, axes):
    return _head(hidden, weight, bias, labels, token_weights, smooth_eps,
                 rows_per_chunk, mesh, axes, grads=False)[0]


def _ce_sum_fwd(hidden, weight, bias, labels, token_weights, smooth_eps,
                rows_per_chunk, mesh, axes):
    (loss, dw, db), dh = _head(hidden, weight, bias, labels, token_weights,
                               smooth_eps, rows_per_chunk, mesh, axes,
                               grads=True)
    with jax.named_scope("ce"):
        return loss, (dh, dw.astype(weight.dtype),
                      None if db is None else db.astype(bias.dtype))


@jax.named_scope("ce")  # traced apart from the forward: named apart too
def _ce_sum_bwd(smooth_eps, rows_per_chunk, mesh, axes, grads, g):
    dh, dw, db = (None if x is None else
                  (g * x.astype(jnp.float32)).astype(x.dtype) for x in grads)
    return dh, dw, db, None, jnp.zeros(dh.shape[:1], jnp.float32)


_ce_sum.defvjp(_ce_sum_fwd, _ce_sum_bwd)


@jax.named_scope("ce")
def _head(hidden, weight, bias, labels, token_weights, smooth_eps,
          rows_per_chunk, mesh, axes, grads: bool):
    """``_walk`` where the rows live: per shard under ``shard_map`` over
    ``axes`` with one ``psum`` of its sums at the exit, or as it is."""
    walk = functools.partial(_walk, smooth_eps=smooth_eps,
                             rows_per_chunk=rows_per_chunk, grads=grads)
    if not axes:
        return walk(hidden, weight, bias, labels, token_weights)

    def per_shard(*a):
        sums, dh = walk(*a)
        return jax.lax.psum(sums, axes), dh

    rows, whole = P(axes), P()
    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axes, None), whole, whole, rows, rows),
        out_specs=(whole, P(axes, None) if grads else None),
        check_vma=False)(hidden, weight, bias, labels, token_weights)


def _walk(hidden, weight, bias, labels, token_weights, *, smooth_eps,
          rows_per_chunk, grads: bool):
    """The row-chunked head on the rows it is given: ``(sums, dh)``, the
    sums over its rows ``loss`` or, with ``grads``, ``(loss, dW, db)``,
    ``dW`` and ``db`` in float32 as accumulated (a sum over shards comes
    before the rounding), and ``dh`` in the hidden dtype (None without
    ``grads``)."""
    n, d = hidden.shape
    v = weight.shape[1]
    r, chunks = _row_chunks(n, rows_per_chunk)
    pad, v_pad = chunks * r - n, _whole_lanes(v)
    f32 = jnp.float32
    wp = jnp.pad(weight, ((0, 0), (0, v_pad - v)))
    bp = None if bias is None else jnp.pad(bias, (0, v_pad - v)).astype(f32)
    col = jnp.arange(v_pad)[None, :]
    valid = col < v                                       # mask the pad tail
    # a pad row weighs nothing
    xs = (jnp.pad(hidden, ((0, pad), (0, 0))).reshape(chunks, r, d),
          jnp.pad(labels, (0, pad)).reshape(chunks, r),
          jnp.pad(token_weights, (0, pad)).reshape(chunks, r))

    def body(carry, inp):
        h, lab, tw = inp
        # [r, v_pad]: the only live logits block, made once
        logits = jax.lax.dot_general(h, wp, (((1,), (0,)), ((), ())),
                                     preferred_element_type=f32)
        if bp is not None:
            logits = logits + bp[None, :]
        logits = jnp.where(valid, logits, -jnp.inf)
        m = jnp.max(logits, axis=1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=1))
        tgt = jnp.take_along_axis(logits, lab[:, None], axis=1)[:, 0]
        nll = (1.0 - smooth_eps) * (lse - tgt)
        if smooth_eps:
            logit_sum = jnp.sum(jnp.where(valid, logits, 0.0), axis=1)
            nll = nll + smooth_eps * (lse - logit_sum / v)
        loss = jnp.sum(tw * nll)
        if not grads:
            return carry + loss, None
        total, dw, db = carry
        dlogits = jnp.exp(logits - lse[:, None]) - (1.0 - smooth_eps) * (col == lab[:, None])
        if smooth_eps:
            dlogits = dlogits - jnp.where(valid, smooth_eps / v, 0.0)
        # rounded once: bf16 matmuls, f32 accumulation
        dlogits = (dlogits * tw[:, None]).astype(hidden.dtype)
        dh = jax.lax.dot_general(dlogits, wp, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        dw = dw + jax.lax.dot_general(h, dlogits, (((0,), (0,)), ((), ())),
                                      preferred_element_type=f32)
        if bp is not None:
            db = db + jnp.sum(dlogits.astype(f32), axis=0)
        return (total + loss, dw, db), dh.astype(hidden.dtype)

    zero = jnp.zeros((), f32)
    if not grads:
        return jax.lax.scan(body, zero, xs)[0], None
    db0 = None if bias is None else jnp.zeros((v_pad,), f32)
    (loss, dw, db), dh = jax.lax.scan(
        body, (zero, jnp.zeros((d, v_pad), f32), db0), xs)
    return ((loss, dw[:, :v], None if db is None else db[:v]),
            dh.reshape(chunks * r, d)[:n])
