"""The two mixers of a sparse / linear hybrid decoder (MiniCPM-SALA), each
in a prefill form over a chunk of the prompt, which reads and extends the
state the layer carries, and a one-token form. A sibling of
``layers/latent.py`` and written as it is: pure functions of ``(activation,
layer_params, carried state)``, parameter tables that take a stack's
leading axis or none (``models/minicpm_sala.py`` gives every layer its
own); the norm, the rotary map, the residual sum and the parameter maker
are ``layers/blocks.py``'s.

**Sparse mixer** (``minicpm4``, InfLLM-V2): ``heads`` query heads over
``kv_heads`` key/value heads (a *group* of ``heads / kv_heads`` shares one),
RMSNorm on every head of q and k, no rotary positions, an output gate. Up
to ``dense_len`` tokens of context it is causal softmax attention. Beyond,
a query reads a selection of key blocks: a scorer takes the softmax of the
query's heads against *compressed keys* (the mean of ``kernel_size`` keys
every ``kernel_stride``), sums a group's heads, pools the kernels that touch
a block into the block's score (the largest), and takes the highest blocks
beside those always read (the first ``init_blocks`` and the window ending
with the query's own) up to ``topk`` in all: :func:`select_blocks`, one
selection a group; a prefill's chunk takes it as ``ops/block_select.py``'s
kernel, which writes the indices alone, a step's one query a row in the
plain form. The attention over the selection is
``ops/sparse_attention.py``'s kernel in the prefill and a gather in a step.
The layer carries three lane-dense slabs: keys and values ``[rows, T,
kv_heads * 128]`` and the compressed keys ``[rows, T / stride, kv_heads *
128]``, the scorer's own cache, extended whenever ``stride`` more keys
are in.

Which form a call takes follows its context: a prefill's is the prompt's
length (every query of a prompt longer than ``dense_len`` selects, the
early ones among few blocks), a step's is its position + 1.

**Lightning mixer**: linear attention with a fixed decay a head
(``ops/lightning_attention.py``): q and k normed a head, rotated, q
scaled; the layer carries one float32 state ``[rows, heads, 128, 128]``,
whatever the context; an RMSNorm a head on the output, then the gate.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.errors import enforce
from ..framework import LayerHelper
from ..ops.block_select import block_select
from ..ops.flash_attention import NEG_INF, flash_attention
from ..ops.lightning_attention import lightning_attention
from ..ops.sparse_attention import record_plan as _record_sparse_plan
from ..ops.sparse_attention import sparse_attention
from .blocks import params, residual, rms_norm, rope


class SparseDims(NamedTuple):
    """One sparse mixer (published key names in brackets; the last seven
    are ``sparse_config``)."""
    d_model: int            # hidden_size
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int           # head_dim
    eps: float              # rms_norm_eps
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def n_sel(self) -> int:
        """Blocks a query chooses beside the forced ones."""
        return max(self.topk - self.init_blocks - self.window_blocks, 0)

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5

    def check(self):
        enforce(self.kernel_size == 2 * self.kernel_stride
                and self.block_size % self.kernel_stride == 0
                and self.window_size % self.block_size == 0
                and self.heads % self.kv_heads == 0,
                f"sparse mixer: kernels of two strides, blocks and windows "
                f"of whole strides and blocks, got {self}")


class LightningDims(NamedTuple):
    d_model: int            # hidden_size
    heads: int              # lightning_nh
    head_dim: int           # lightning_head_dim
    eps: float              # rms_norm_eps
    theta: float            # rope_theta

    @property
    def scale(self) -> float:   # lightning_scale "1/sqrt(d)"
        return self.head_dim ** -0.5


def lightning_log_decay(heads: int, layer: int, depth: int):
    """``log lambda_h`` of the layer with published index ``layer`` of
    ``depth``: ``-2^(-8 (h + 1) / heads) * (1 - layer / (depth - 1) +
    1e-5)`` (the Lightning Attention slopes, weaker in later layers)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -(2.0 ** (-8.0 * h / heads)) * (1.0 - layer / (depth - 1) + 1e-5)


# -- parameters ------------------------------------------------------------------


def sparse_params(dims: SparseDims, dtype, layers: Optional[int] = None,
                  name: str = "mixer") -> Dict[str, jax.Array]:
    d, hd = dims.d_model, dims.head_dim
    q, kv = dims.heads * hd, dims.kv_heads * hd
    return params(LayerHelper(name, name=name), {
        "attn_norm/g": ((d,), None),
        "qkv/w": ((q + 2 * kv, d), d),
        "q_norm/g": ((hd,), None), "k_norm/g": ((hd,), None),
        "gate/w": ((d, q), d), "o/w": ((q, d), q),
    }, layers, dtype)


def lightning_params(dims: LightningDims, dtype, layers: Optional[int] = None,
                     name: str = "mixer") -> Dict[str, jax.Array]:
    d, hd = dims.d_model, dims.head_dim
    w = dims.heads * hd
    return params(LayerHelper(name, name=name), {
        "attn_norm/g": ((d,), None),
        "qkv/w": ((3 * w, d), d),
        "q_norm/g": ((hd,), None), "k_norm/g": ((hd,), None),
        "o_norm/g": ((hd,), None),
        "gate/w": ((d, w), d), "o/w": ((w, d), w),
    }, layers, dtype)


def _project(u, w):
    """``u [b, s, d] @ w^T``: q, k and v of ``u`` side by side from the one
    matrix ``qkv/w``, stored ``[out, in]``."""
    return jnp.einsum("bsd,od->bso", u, w)


def _gated_out(o, u, p):
    """``W_o (sigmoid(W_g u) * o)``, the gate taken in float32."""
    gate = jax.nn.sigmoid(jnp.matmul(u, p["gate/w"],
                                     preferred_element_type=jnp.float32))
    return jnp.matmul((gate * o.astype(jnp.float32)).astype(u.dtype), p["o/w"])


# -- the sparse mixer ---------------------------------------------------------------


def _sparse_qkv(u, p, dims: SparseDims):
    """``(q [b, s, heads, hd], k [b, s, kv * hd], v [b, s, kv * hd])``,
    q and k normed a head."""
    b, s, _ = u.shape
    wide, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    qkv = _project(u, p["qkv/w"])
    q = rms_norm(qkv[..., :wide].reshape(b, s, dims.heads, dims.head_dim),
                   p["q_norm/g"], dims.eps)
    k = rms_norm(qkv[..., wide:wide + kv].reshape(b, s, dims.kv_heads,
                                                    dims.head_dim),
                   p["k_norm/g"], dims.eps)
    return q, k.reshape(b, s, -1), qkv[..., wide + kv:]


def _stride_means(k, stride: int):
    """Means of whole runs of ``stride`` keys: ``[b, s, w] -> [b, s /
    stride, w]`` float32. A compressed key is the mean of two of them."""
    b, s, w = k.shape
    return jnp.mean(k.astype(jnp.float32).reshape(b, s // stride, stride, w),
                    axis=2)


def select_blocks(q, ck_cache, positions, dims: SparseDims):
    """The scorer, in its plain form (a step's one query a row; a prefill's
    chunk takes ``ops/block_select.py``'s kernel, whose oracle this is).
    ``q [b, s, heads, hd]`` at ``positions [s]``, ``ck_cache [b, J, kv *
    hd]`` -> ``sel [b, kv, s, n_sel + 1]`` int32: a query's chosen blocks,
    highest score first, and how many of them count (fewer than ``n_sel``
    while fewer blocks lie before the window)."""
    top, idx = _highest(block_scores(q, ck_cache, positions, dims), dims.n_sel)
    count = jnp.sum(top >= 0.0, axis=-1, keepdims=True)
    return jnp.concatenate([idx, count], axis=-1).astype(jnp.int32)


def block_scores(q, ck_cache, positions, dims: SparseDims):
    """``[b, kv, s, blocks]`` float32: a block's score for a query, -1
    where the block is not the query's to choose (the first blocks, the
    window's, those after it). Compressed key ``j`` exists for a query at
    ``i`` when its last key does (``stride * j + kernel <= i + 1``)."""
    b, s, _, hd = q.shape
    J = ck_cache.shape[1]
    per = dims.block_size // dims.kernel_stride          # kernels a block starts
    n_blocks = J // per
    ck = ck_cache.reshape(b, J, dims.kv_heads, hd)
    qg = q.reshape(b, s, dims.kv_heads, dims.group, hd)
    scores = jnp.einsum("bscgd,bjcd->bcgsj", qg, ck,
                        preferred_element_type=jnp.float32) * dims.scale
    j = jnp.arange(J)
    exists = (dims.kernel_stride * j[None, :] + dims.kernel_size
              <= positions[:, None] + 1)                  # [s, J]
    probs = jax.nn.softmax(jnp.where(exists, scores, NEG_INF), axis=-1)
    summed = jnp.where(exists, jnp.sum(probs, axis=2), 0.0)   # [b, c, s, J]
    # block b's score: the largest of kernels per*b - 1 .. per*b + per - 1
    low = jnp.full(summed.shape[:-1] + (1,), -1.0)
    padded = jnp.concatenate([low, summed] + [low] * (per - 1), axis=-1)
    inside = padded[..., :J].reshape(summed.shape[:-1] + (n_blocks, per))
    after = padded[..., per:].reshape(summed.shape[:-1] + (n_blocks, per))
    block_score = jnp.maximum(jnp.max(inside, axis=-1), after[..., 0])
    own = positions // dims.block_size
    blocks = jnp.arange(n_blocks)
    free = ((blocks[None, :] >= dims.init_blocks)
            & (blocks[None, :] <= (own - dims.window_blocks)[:, None]))
    return jnp.where(free, block_score, -1.0)


def _highest(scores, k: int):
    """``lax.top_k``: the ``k`` highest of the last axis (none below -1),
    highest first, the lower index first among equals, as ``k`` passes of
    argmax. On the chip ``lax.top_k`` sorts every row: 41 ms for the 31
    highest of ``[2, 2, 4096, 514]`` where these passes take 7 (PERF.md
    section 6, PR 33)."""
    at = jnp.arange(scores.shape[-1])
    top, idx = [], []
    for _ in range(k):
        i = jnp.argmax(scores, axis=-1)
        top.append(jnp.max(scores, axis=-1))
        idx.append(i)
        scores = jnp.where(at == i[..., None], -2.0, scores)
    stack = lambda parts: (jnp.stack(parts, axis=-1) if parts else
                           jnp.zeros(scores.shape[:-1] + (0,), scores.dtype))
    return stack(top), stack(idx).astype(jnp.int32)


def sparse_prefill(x, p, dims: SparseDims, cache, p0, selected: bool, a: float):
    """A chunk of the prompt, ``x [b, s, d]`` at positions ``p0 ..``
    (``selected``: ``p0`` may be traced and is a multiple of the block;
    else the chunk is the whole prompt and ``p0`` is 0). ``cache = (k, v,
    ck)`` is written at the chunk's positions and, ``selected``, read up
    to them. Returns ``(x + a * mixer, cache)``."""
    dims.check()
    b, s, _ = x.shape
    k_cache, v_cache, ck_cache = cache
    stride = dims.kernel_stride
    with jax.named_scope("sparse"):
        u = rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _sparse_qkv(u, p, dims)
        k = k.astype(k_cache.dtype)
        # compressed keys: the one astride the chunk's start first (where
        # there is none, p0 = 0, it lands on entry 0 and is overwritten),
        # then those inside the chunk
        whole = s // stride * stride
        means = _stride_means(k[:, :whole], stride)
        before = _stride_means(jax.lax.dynamic_slice_in_dim(
            k_cache, jnp.maximum(p0 - stride, 0), stride, axis=1), stride)
        first = p0 // stride
        ck_cache = jax.lax.dynamic_update_slice_in_dim(
            ck_cache, (0.5 * (before + means[:, :1])).astype(ck_cache.dtype),
            jnp.maximum(first - 1, 0), axis=1)
        if whole >= 2 * stride:
            ck_cache = jax.lax.dynamic_update_slice_in_dim(
                ck_cache, (0.5 * (means[:, :-1] + means[:, 1:])
                           ).astype(ck_cache.dtype), first, axis=1)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, p0, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), p0, axis=1)
        if selected:
            enforce(s % dims.block_size == 0,
                    f"sparse_prefill: a chunk of {s} in blocks of "
                    f"{dims.block_size}")
            # a chunk is whole query tiles: the scorer is the kernel (a
            # step's one query a row takes select_blocks, its plain form)
            with jax.named_scope("sparse_select"):
                sel, scorer = block_select(
                    q.reshape(b, s, -1), ck_cache, p0, group=dims.group,
                    head_dim=dims.head_dim, kernel_size=dims.kernel_size,
                    stride=stride, block=dims.block_size,
                    init_blocks=dims.init_blocks,
                    window_blocks=dims.window_blocks, n_sel=dims.n_sel,
                    scale=dims.scale)
            qt = q.reshape(b, s, dims.kv_heads, dims.group, dims.head_dim)
            qt = qt.transpose(0, 2, 1, 3, 4).reshape(
                b, dims.kv_heads, s * dims.group, dims.head_dim)
            o = sparse_attention(
                qt, k_cache, v_cache, sel, p0, group=dims.group,
                block=dims.block_size, window_blocks=dims.window_blocks,
                init_blocks=dims.init_blocks, scale=dims.scale, scorer=scorer)
            o = o.reshape(b, dims.kv_heads, s, dims.group, dims.head_dim)
            o = o.transpose(0, 2, 1, 3, 4).reshape(b, s, -1)
        else:
            _record_sparse_plan(s, k_cache.shape[1], -(-s // dims.block_size),
                                dims.topk, dims.init_blocks + dims.window_blocks,
                                dims.block_size, (0, 0), "dense")
            rep = lambda t: jnp.repeat(
                t.reshape(b, s, dims.kv_heads, 1, dims.head_dim), dims.group,
                axis=3).reshape(b, s, -1)
            o = flash_attention(q.reshape(b, s, -1), rep(k), rep(v),
                                causal=True, scale=dims.scale,
                                num_heads=dims.heads)
        x = residual(x, _gated_out(o, u, p), a)
    return x, (k_cache, v_cache, ck_cache)


def _attend(q, keys, values, seen, dims: SparseDims):
    """``q [r, kv, g, hd]`` over ``keys, values [r, kv, n, hd]`` where
    ``seen [r, kv, n]`` -> ``[r, kv * g * hd]``."""
    s = jnp.einsum("rcgd,rcnd->rcgn", q, keys,
                   preferred_element_type=jnp.float32) * dims.scale
    probs = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, NEG_INF), axis=-1)
    o = jnp.einsum("rcgn,rcnd->rcgd", probs.astype(values.dtype), values)
    return o.reshape(q.shape[0], -1)


def _split_heads(slab, dims: SparseDims):
    """``[r, n, kv * hd] -> [r, kv, n, hd]``."""
    r, n, _ = slab.shape
    return slab.reshape(r, n, dims.kv_heads, dims.head_dim).transpose(0, 2, 1, 3)


def _step_selected(q, cache, index, dims: SparseDims):
    """One query a row at position ``index`` over its selection: the
    window as one slice of the cache, the first blocks as another, the
    chosen blocks gathered (both key heads' lanes of a block come with
    it; each group keeps its own)."""
    k_cache, v_cache, ck_cache = cache
    r, total, width = k_cache.shape
    blk, n_sel = dims.block_size, dims.n_sel
    with jax.named_scope("sparse_select"):
        sel = select_blocks(q[:, None], ck_cache, index[None], dims)[:, :, 0]
    first = jnp.maximum(index // blk - (dims.window_blocks - 1), 0) * blk
    span, lead = dims.window_blocks * blk, dims.init_blocks * blk
    qg = q.reshape(r, dims.kv_heads, dims.group, dims.head_dim)
    parts_k, parts_v, parts_seen = [], [], []

    def add(k_part, v_part, seen):
        parts_k.append(k_part)
        parts_v.append(v_part)
        parts_seen.append(jnp.broadcast_to(seen, k_part.shape[:3]))

    win = lambda slab: _split_heads(jax.lax.dynamic_slice_in_dim(
        slab, first, span, axis=1), dims)
    add(win(k_cache), win(v_cache), first + jnp.arange(span) <= index)
    add(_split_heads(k_cache[:, :lead], dims),
        _split_heads(v_cache[:, :lead], dims), jnp.arange(lead) < first)
    if n_sel:
        # the rows of the chosen blocks, as an embedding reads its rows (a
        # slab reshaped to blocks is another tiling, and the compiler then
        # carried it transposed)
        rows_of = (sel[..., :n_sel, None] * blk + jnp.arange(blk)).reshape(r, -1)

        def chosen(slab):
            got = jax.vmap(lambda rows, at: rows[at])(slab, rows_of)
            got = got.reshape(r, dims.kv_heads, n_sel * blk, dims.kv_heads,
                              dims.head_dim)
            return jnp.stack([got[:, c, :, c] for c in range(dims.kv_heads)],
                             axis=1)

        add(chosen(k_cache), chosen(v_cache),
            (jnp.arange(n_sel * blk) // blk)[None, None, :] < sel[..., n_sel:])
    cat = lambda parts: jnp.concatenate(parts, axis=2)
    return _attend(qg, cat(parts_k), cat(parts_v), cat(parts_seen), dims)


def sparse_decode(x, p, dims: SparseDims, cache, index, prompt_len: int,
                  a: float):
    """One token at position ``index`` (traced): ``x [rows, 1, d]``; the
    cache is written in place at the position (a compressed key whenever
    its last key is in), read dense while the context is within
    ``dense_len`` and by selection beyond. ``prompt_len`` is the least
    ``index`` this step can see: a cache that ends within ``dense_len``
    never selects, a prompt beyond it always does, and only a generator
    that crosses it holds both forms under a ``cond``.

    A step run twice at one ``index`` leaves what its second run writes
    and nothing of the first: position ``index`` of ``k`` and ``v``, and
    compressed key ``j`` from the rewritten ``k`` (the one that covers
    ``index`` itself where ``(index + 1) % stride == 0``). That is all
    ``decoding.step_with_write_switch`` asks of a first step, which stands
    at the prompt's length as the second does: no ``write`` switch here."""
    dims.check()
    k_cache, v_cache, ck_cache = cache
    r, total, _ = k_cache.shape
    stride = dims.kernel_stride
    _record_sparse_plan(prompt_len, total, total // dims.block_size, dims.topk,
                        dims.init_blocks + dims.window_blocks, dims.block_size,
                        (0, 0), "dense" if total <= dims.dense_len else
                        "selected" if prompt_len >= dims.dense_len
                        else "dense+selected")
    with jax.named_scope("sparse"):
        u = rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _sparse_qkv(u, p, dims)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), index, axis=1)
        # the compressed key whose last key is the newest whole stride's
        # (written again at each of the stride's steps: the same numbers)
        j = jnp.maximum((index + 1 - dims.kernel_size) // stride, 0)
        means = _stride_means(jax.lax.dynamic_slice_in_dim(
            k_cache, j * stride, dims.kernel_size, axis=1), stride)
        ck_cache = jax.lax.dynamic_update_slice_in_dim(
            ck_cache, (0.5 * (means[:, :1] + means[:, 1:])
                       ).astype(ck_cache.dtype), j, axis=1)
        cache = (k_cache, v_cache, ck_cache)
        q = q[:, 0]

        def dense(_):
            n = min(total, dims.dense_len)
            return _attend(
                q.reshape(r, dims.kv_heads, dims.group, dims.head_dim),
                _split_heads(k_cache[:, :n], dims),
                _split_heads(v_cache[:, :n], dims),
                jnp.broadcast_to(jnp.arange(n) <= index,
                                 (r, dims.kv_heads, n)), dims)

        def selected(_):
            return _step_selected(q, cache, index, dims)

        if total <= dims.dense_len:
            o = dense(None)
        elif prompt_len >= dims.dense_len:
            o = selected(None)
        else:
            o = jax.lax.cond(index + 1 <= dims.dense_len, dense, selected, None)
        x = residual(x, _gated_out(o[:, None, :], u, p), a)
    return x, cache


# -- the lightning mixer --------------------------------------------------------------


def _lightning_qkv(u, p, dims: LightningDims, positions):
    """``q, k, v [b, s, heads, hd]``: q and k normed a head and rotated, q
    scaled."""
    b, s, _ = u.shape
    qkv = _project(u, p["qkv/w"]).reshape(b, s, 3, dims.heads, dims.head_dim)
    i = jnp.arange(dims.head_dim // 2, dtype=jnp.float32)
    freqs = dims.theta ** (-2.0 * i / dims.head_dim)
    q = rope(rms_norm(qkv[:, :, 0], p["q_norm/g"], dims.eps), positions,
               freqs, head_axis=True)
    k = rope(rms_norm(qkv[:, :, 1], p["k_norm/g"], dims.eps), positions,
               freqs, head_axis=True)
    q = (q.astype(jnp.float32) * dims.scale).astype(q.dtype)
    return q, k, qkv[:, :, 2]


def lightning_prefill(x, p, dims: LightningDims, state, log_decay, p0, a: float):
    """A chunk ``x [b, s, d]`` at positions ``p0 ..`` through the chunked
    recurrence, from ``state [b, heads, hd, hd]`` float32. Returns ``(x +
    a * mixer, state)``."""
    b, s, _ = x.shape
    with jax.named_scope("lightning"):
        u = rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _lightning_qkv(u, p, dims, p0 + jnp.arange(s))
        flat = lambda t: t.reshape(b, s, -1)
        o, state = lightning_attention(flat(q), flat(k), flat(v), log_decay,
                                       state, dims.heads)
        o = rms_norm(o.reshape(b, s, dims.heads, dims.head_dim),
                       p["o_norm/g"], dims.eps)
        x = residual(x, _gated_out(flat(o), u, p), a)
    return x, state


def lightning_decode(x, p, dims: LightningDims, state, log_decay, index,
                     a: float, write=True):
    """One token at position ``index``: ``S <- lambda S + k^T v``, ``o = q
    S``, the state read and written once, in float32. ``write`` (a traced
    bool) false keeps the state handed in, bit for bit (a select inside the
    update, not a product with one and a sum with zero): the first step of
    ``decoding.step_with_write_switch``, whose ``x`` nothing reads."""
    f32 = jnp.float32
    with jax.named_scope("lightning"):
        u = rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _lightning_qkv(u, p, dims, index[None])
        state = jnp.where(
            write, state * jnp.exp(log_decay.astype(f32))[None, :, None, None]
            + jnp.einsum("rhd,rhe->rhde", k[:, 0].astype(f32),
                         v[:, 0].astype(f32)), state)
        o = jnp.einsum("rhd,rhde->rhe", q[:, 0].astype(f32), state)
        o = rms_norm(o.astype(x.dtype), p["o_norm/g"], dims.eps)
        x = residual(x, _gated_out(o.reshape(x.shape[0], 1, -1), u, p), a)
    return x, state


__all__ = ["LightningDims", "SparseDims", "lightning_decode",
           "lightning_log_decay", "lightning_params", "lightning_prefill",
           "select_blocks", "sparse_decode", "sparse_params", "sparse_prefill"]
