"""The plain reference for the ``minicpm_sala`` family: the forward pass
that ``configs/minicpm-sala.json`` writes down in words, in float32
``jax.numpy`` at ``jax.default_matmul_precision("highest")``. No kernel, no
cache, no scan and no chunked recurrence: lightning attention is the masked
quadratic form ``((Q K^T) * decay^(t - s)) V``; the sparse mixer is the
scorer, the block selection turned into a ``[queries, keys]`` mask, and one
dense masked softmax. Both walk the queries in blocks (``query_block``)
against all the keys, so that a context of 32k fits: a block of 256 queries
x 32 heads x 32,896 keys is 1.1 GB of float32 scores. It shares no code
with ``paddle_tpu/``.

One sequence at a time: ``x [s, d]``. Parameters are per layer, under the
reference's own names; matrices are ``[in, out]``:

- a ``minicpm4`` layer: ``attn_norm, q, k, v, q_norm, k_norm, gate, o``;
- a ``lightning-attn`` layer: ``attn_norm, q, k, v, q_norm, k_norm, o_norm,
  gate, o``;
- every layer's FFN: ``ffn_norm, ffn_gate, ffn_up, ffn_down``;
- the ends: ``emb, final_norm, head``.

**Which form a sparse layer's query takes.** The published code decides a
call at a time: a call whose context is at most ``dense_len`` is dense. A
served sequence is two kinds of call: the prefill of the prompt (context:
the prompt's length, for every query of it) and one call a generated token
(context: its position + 1). ``prompt_len`` says where the one ends and
the other begins; a forward over a whole sequence with ``prompt_len`` its
length is the published prefill.

Departures from the published code, as the configuration file lists them:
(a) the scorer's softmax is exact (the published kernels approximate its
normaliser from a four times coarser pooling);
(b) rotary pairs are ``(2i, 2i + 1)`` rotated in place (the published code
rotates halves: one fixed permutation of a head's dimensions of q and k
alike, every product unchanged);
(c) ``mixer_part`` and ``ffn_part`` are a layer's two halves, so that the
chip check holds one half's float32 weights at a time.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
NEG = -1e30


class Shape(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    l_heads: int
    l_head_dim: int
    eps: float
    theta: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    published_layers: int
    kernel_size: int
    kernel_stride: int
    block_size: int
    init_blocks: int
    window_size: int
    topk: int
    dense_len: int
    attn_use_rope: bool = False         # rotary positions in a sparse layer
    lightning_use_rope: bool = True     # ... in a lightning layer
    query_block: int = 256      # queries a block of either mixer
    token_block: int = 4096     # tokens a block of the FFN
    # never set by a configuration: the fault of tools/sala_sensitivity.py
    # that sums a group's scores before the softmax instead of after it
    scorer_sums_first: bool = False


def shape_of(config: Dict[str, Any], **kw) -> Shape:
    """From a configuration file: published keys, ``published`` for the
    depth the scalings refer to, ``assumed.sparse_config`` for the rest."""
    sc = config["assumed"]["sparse_config"]
    return Shape(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        l_heads=config["lightning_nh"], l_head_dim=config["lightning_head_dim"],
        eps=config["rms_norm_eps"], theta=config["rope_theta"],
        scale_emb=config["scale_emb"], scale_depth=config["scale_depth"],
        dim_model_base=config["dim_model_base"],
        published_layers=config["published"]["num_hidden_layers"],
        kernel_size=sc["kernel_size"], kernel_stride=sc["kernel_stride"],
        block_size=sc["block_size"], init_blocks=sc["init_blocks"],
        window_size=sc["window_size"], topk=sc["topk"],
        dense_len=sc["dense_len"], attn_use_rope=config["attn_use_rope"],
        lightning_use_rope=config["lightning_use_rope"], **kw)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def branch_scale(sh: Shape) -> float:
    return sh.scale_depth / math.sqrt(sh.published_layers)


def _in_blocks(fn, n: int, block: int):
    """``concat(fn(start) for start in 0, block, ...)[:n]``; ``fn`` gives
    ``[block, ...]`` for the rows ``start .. start + block`` (rows past
    ``n`` are its to ignore)."""
    starts = jnp.arange(-(-n // block)) * block
    out = jax.lax.map(fn, starts)
    return out.reshape((-1,) + out.shape[2:])[:n]


def _pad_rows(a, block: int):
    return jnp.pad(a, [(0, -a.shape[0] % block)] + [(0, 0)] * (a.ndim - 1))


# -- the sparse mixer -------------------------------------------------------------


def selected_blocks(q_rows, positions, kbar, n_blocks: int, sh: Shape):
    """``[kv, rows, n_blocks]`` booleans: the blocks the queries
    ``q_rows [rows, heads, hd]`` at ``positions [rows]`` read, by the
    scorer over the compressed keys ``kbar [J, kv, hd]``."""
    J = kbar.shape[0]
    group = sh.heads // sh.kv_heads
    qg = q_rows.reshape(-1, sh.kv_heads, group, sh.head_dim)
    s = jnp.einsum("qcgd,jcd->cgqj", qg, kbar) * sh.head_dim ** -0.5
    exists = (sh.kernel_stride * jnp.arange(J)[None, :] + sh.kernel_size
              <= positions[:, None] + 1)                        # [rows, J]
    if sh.scorer_sums_first:
        summed = group * jax.nn.softmax(
            jnp.where(exists, s.sum(axis=1), NEG), axis=-1)
    else:
        summed = jax.nn.softmax(jnp.where(exists, s, NEG), axis=-1).sum(axis=1)
    summed = jnp.where(exists, summed, NEG)                      # [kv, rows, J]
    # a block's score: the largest of the kernels that touch it and exist
    per = sh.block_size // sh.kernel_stride
    reach = sh.kernel_size // sh.kernel_stride                   # strides a kernel spans
    touching = (per * jnp.arange(n_blocks)[:, None]
                + jnp.arange(-(reach - 1), per)[None, :])         # [n_blocks, 5]
    valid = (touching >= 0) & (touching < J)
    score = jnp.where(valid, summed[..., jnp.clip(touching, 0, J - 1)], NEG)
    score = score.max(axis=-1)                                   # [kv, rows, n_blocks]
    own = positions // sh.block_size
    blocks = jnp.arange(n_blocks)[None, :]
    window = sh.window_size // sh.block_size
    forced = ((blocks < sh.init_blocks) | (blocks > own[:, None] - window)) & (
        blocks <= own[:, None])
    free = ~forced & (blocks <= own[:, None])
    room = sh.topk - forced.sum(axis=-1)                         # [rows]
    order = jnp.argsort(-jnp.where(free, score, NEG), axis=-1)   # stable
    rank = jnp.argsort(order, axis=-1)
    return forced[None] | (free[None] & (rank < room[None, :, None]))


def sparse_mixer(u, p, sh: Shape, prompt_len: int):
    """``u [s, d]`` (normed) -> the mixer's output ``[s, d]``."""
    s = u.shape[0]
    group = sh.heads // sh.kv_heads
    q = rms_norm((u @ p["q"]).reshape(s, sh.heads, sh.head_dim), p["q_norm"], sh.eps)
    k = rms_norm((u @ p["k"]).reshape(s, sh.kv_heads, sh.head_dim), p["k_norm"], sh.eps)
    v = (u @ p["v"]).reshape(s, sh.kv_heads, sh.head_dim)
    if sh.attn_use_rope:    # published false
        q, k = rope(q, jnp.arange(s), sh.theta), rope(k, jnp.arange(s), sh.theta)
    gate = jax.nn.sigmoid(u @ p["gate"])
    n_kernels = max((s - sh.kernel_size) // sh.kernel_stride + 1, 0)
    # kbar_j = mean(k[stride * j : stride * j + kernel]); one row of zeros
    # that no query sees where the sequence is shorter than a kernel
    rows_of = (sh.kernel_stride * jnp.arange(n_kernels)[:, None]
               + jnp.arange(sh.kernel_size)[None, :])
    kbar = (k[rows_of].mean(axis=1) if n_kernels
            else jnp.zeros((1, sh.kv_heads, sh.head_dim), k.dtype))
    n_blocks = -(-s // sh.block_size)
    key_pos = jnp.arange(s)
    qp = _pad_rows(q, sh.query_block)

    def block(start):
        positions = start + jnp.arange(sh.query_block)
        rows = jax.lax.dynamic_slice_in_dim(qp, start, sh.query_block, axis=0)
        # a prompt's queries take the prompt's form, a generated token its own
        context = jnp.where(positions < prompt_len, prompt_len, positions + 1)
        chosen = selected_blocks(rows, positions, kbar, n_blocks, sh)
        by_block = jnp.repeat(chosen, sh.block_size, axis=-1)[..., :s]
        seen = ((by_block | (context <= sh.dense_len)[None, :, None])
                & (key_pos[None, None, :] <= positions[None, :, None]))
        scores = jnp.einsum(
            "qcgd,tcd->cgqt", rows.reshape(-1, sh.kv_heads, group, sh.head_dim),
            k) * sh.head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[:, None], scores, NEG), axis=-1)
        return jnp.einsum("cgqt,tcd->qcgd", probs, v).reshape(sh.query_block, -1)

    o = _in_blocks(block, s, sh.query_block)
    return (gate * o) @ p["o"]


# -- the lightning mixer -------------------------------------------------------------


def rope(x, positions, theta: float):
    """Rotate the pairs ``(2i, 2i + 1)`` of ``x [s, heads, dim]`` by
    ``positions[s] * theta^(-2i / dim)``."""
    dim = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs[None, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def decay_rates(sh: Shape, layer_index: int):
    """``-log lambda_h`` of the layer with published index ``layer_index``."""
    h = jnp.arange(1, sh.l_heads + 1, dtype=jnp.float32)
    return (2.0 ** (-8.0 * h / sh.l_heads)
            * (1.0 - layer_index / (sh.published_layers - 1) + 1e-5))


def lightning_mixer(u, p, sh: Shape, layer_index: int):
    """``u [s, d]`` (normed) -> the mixer's output ``[s, d]``."""
    s = u.shape[0]
    heads = lambda w: (u @ w).reshape(s, sh.l_heads, sh.l_head_dim)
    q = rms_norm(heads(p["q"]), p["q_norm"], sh.eps)
    k = rms_norm(heads(p["k"]), p["k_norm"], sh.eps)
    v = heads(p["v"])
    if sh.lightning_use_rope:
        q, k = rope(q, jnp.arange(s), sh.theta), rope(k, jnp.arange(s), sh.theta)
    q = q * sh.l_head_dim ** -0.5
    rate = decay_rates(sh, layer_index)[:, None, None]
    key_pos = jnp.arange(s, dtype=jnp.float32)
    qp = _pad_rows(q, sh.query_block)

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(qp, start, sh.query_block, axis=0)
        apart = (start + jnp.arange(sh.query_block, dtype=jnp.float32)
                 )[:, None] - key_pos[None, :]                   # t - s
        decay = jnp.where(apart >= 0, jnp.exp(-rate * jnp.maximum(apart, 0.0)), 0.0)
        scores = jnp.einsum("qhd,thd->hqt", rows, k) * decay
        return jnp.einsum("hqt,thd->qhd", scores, v)

    o = _in_blocks(block, s, sh.query_block)
    o = rms_norm(o, p["o_norm"], sh.eps).reshape(s, -1)
    return (jax.nn.sigmoid(u @ p["gate"]) * o) @ p["o"]


# -- a layer's two halves, and the ends ---------------------------------------------


def mixer_part(x, p, sh: Shape, kind: str, layer_index: int, prompt_len: int):
    """``x + a * Mixer(RMSNorm(x))`` for one sequence ``x [s, d]``."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["attn_norm"], sh.eps)
        y = (sparse_mixer(u, p, sh, prompt_len) if kind == SPARSE
             else lightning_mixer(u, p, sh, layer_index))
        return x + branch_scale(sh) * y


def ffn_part(x, p, sh: Shape):
    """``x + a * FFN(RMSNorm(x))``, a block of tokens at a time."""
    with jax.default_matmul_precision("highest"):
        n = min(sh.token_block, x.shape[0])
        xp = _pad_rows(x, n)

        def block(start):
            h = rms_norm(jax.lax.dynamic_slice_in_dim(xp, start, n, axis=0),
                         p["ffn_norm"], sh.eps)
            return (jax.nn.silu(h @ p["ffn_gate"]) * (h @ p["ffn_up"])) @ p["ffn_down"]

        return x + branch_scale(sh) * _in_blocks(block, x.shape[0], n)


def embed(emb, ids, sh: Shape):
    return sh.scale_emb * emb[ids]


def head_logits(x, final_norm, head, sh: Shape):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, final_norm, sh.eps) / (sh.hidden / sh.dim_model_base)
        return h @ head


def forward(params, ids, sh: Shape, kinds, layer_indices, prompt_len: int):
    """Logits ``[s, vocab]`` of one sequence ``ids [s]``: the layers
    ``params["layers"]`` of ``kinds`` at the published ``layer_indices``."""
    x = embed(params["emb"], ids, sh)
    for lp, kind, index in zip(params["layers"], kinds, layer_indices):
        x = mixer_part(x, lp, sh, kind, index, prompt_len)
        x = ffn_part(x, lp, sh)
    return head_logits(x, params["final_norm"], params["head"], sh)
