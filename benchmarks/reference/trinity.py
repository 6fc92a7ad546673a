"""The plain reference for the ``trinity`` family: the afmoe decoder's
forward pass as the configuration file describes it
(``configs/trinity-large-ep8.json``: the published keys, and under
``assumed`` what they leave open), in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``. No kernel, cache, ring, chunk,
sort or grouped product: a Python loop over layers, attention one dense
masked softmax over the whole sequence (the window and the causal rule as a
``[queries, keys]`` mask), the key/value head of query head ``h`` indexed ``h
// group`` and never repeated, the routed experts a Python loop over the
experts held with a mask. It imports nothing from ``paddle_tpu``.

It computes one rank's share, as the program does: ``held`` experts from
``rank * held`` of the ``routed`` the router scores, and logits over the rows
of the vocabulary it is given. What the absent experts would add is left out
and the partial result goes on to the next layer.

One layer, ``x [rows, s, d]`` (every RMSNorm with its own gain)::

    a = rms(x; attn_norm)
    q = rms_head(a q -> [.., heads, hd]; q_norm)   k = rms_head(a k -> [.., kv_heads, hd]; k_norm)
    v = a v -> [.., kv_heads, hd]                   g = a gate -> [.., heads * hd]
    sliding layer: q, k rotated, all hd dims; query i sees keys j, 0 <= i - j < window
    full layer:    no rotation;                query i sees keys j <= i
    o = softmax(q_h . k_(h // group) / sqrt(hd)) v_(h // group)
    x = x + rms((o * sigmoid(g)) o_proj; attn_post_norm)
    m = rms(x; ffn_norm)
    f = dense FFN(m), or shared(m) + sum over the selected held experts
    x = x + rms(f; ffn_post_norm)

Parameters are per layer, under the reference's own names: ``attn_norm, q,
k, v, gate, q_norm, k_norm, o, attn_post_norm, ffn_norm, ffn_post_norm`` and
either ``ffn_gate, ffn_up, ffn_down`` (a dense layer) or ``router,
select_bias, shared_gate, shared_up, shared_down, experts_gate, experts_up,
experts_down`` (an expert layer; the banks ``[held, ...]``). Matrices are
``[in, out]``.

Departures from the published description, each marked where it is made:
(a) rotary pairs are ``(2i, 2i + 1)`` rotated in place, where the published
code rotates halves ``(i, i + hd / 2)``: one fixed permutation of a head's
dimensions of q and k alike, every score unchanged;
(b) the RMSNorm gain multiplies in float32 before the result is rounded (the
published code rounds to the input dtype first; the same in float32);
(c) the queries are walked in blocks (``query_block``, ``jax.lax.map``): a
``[heads, s, s]`` array of scores at 33k tokens does not exist on any chip;
each block is the same dense masked softmax over all keys;
(d) ``attention_part`` and ``ffn_part`` are a layer's two halves, and
``ffn_part`` is made of :func:`routed_setup`, :func:`add_expert` and
:func:`ffn_close`, so that the chip check can hold one half's (or one
expert's) float32 weights at a time beside the served weights.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"


class Shape(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    theta: float
    eps: float
    top_k: int
    route_scale: float
    routed: int          # experts the router scores (published)
    held: int            # experts held here
    rank: int            # which block of ``held`` experts
    mup: bool            # the embedding times sqrt(hidden_size)
    query_block: int = 256


def shape_of(config: Dict[str, Any], **kw) -> Shape:
    """From a configuration file: published keys, the ``published`` group
    for what was cut, the ``deployment`` group for the rank."""
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], theta=config["rope_theta"],
        eps=config["rms_norm_eps"], top_k=config["num_experts_per_tok"],
        route_scale=config["route_scale"],
        routed=config["published"]["num_experts"], held=config["num_experts"],
        rank=config["deployment"]["expert_rank"],
        mup=config["mup_enabled"], **kw)


def layers_of(config: Dict[str, Any]):
    """``[(published index, kind, dense)]`` of the layers held."""
    dense_below = config["published"]["num_dense_layers"]
    return [(i, config["layer_types"][i], i < dense_below)
            for i in config["layer_indices"]]


def rms_norm(x, g, eps):
    # departure (b): gain applied before rounding
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def ffn(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def rotate(x, positions, theta: float):
    """``x [rows, s, heads, hd]`` by ``positions [s]``: each pair ``(2i, 2i +
    1)`` as one complex number times ``exp(i pos theta^(-2i / hd))``
    (departure (a): the pairs stay where they are)."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    turn = jax.lax.complex(jnp.cos(ang), jnp.sin(ang))[:, None, :]
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * turn
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


# -- attention ---------------------------------------------------------------------


def attention(a, lp, sh: Shape, kind: str):
    """``a [rows, s, d]`` (normed) -> the gated attention through its output
    projection, ``[rows, s, d]``, before the norm that follows it."""
    rows, s, _ = a.shape
    H, K, hd = sh.heads, sh.kv_heads, sh.head_dim
    group = H // K
    q = rms_norm((a @ lp["q"]).reshape(rows, s, H, hd), lp["q_norm"], sh.eps)
    k = rms_norm((a @ lp["k"]).reshape(rows, s, K, hd), lp["k_norm"], sh.eps)
    v = (a @ lp["v"]).reshape(rows, s, K, hd)
    gate = a @ lp["gate"]
    pos = jnp.arange(s)
    if kind == SLIDING:
        q, k = rotate(q, pos, sh.theta), rotate(k, pos, sh.theta)
    # query head h = c * group + g reads key head c: q as [.., K, group, hd]
    q = q.reshape(rows, s, K, group, hd)

    # departure (c): the queries a block at a time, each against all keys
    block = min(sh.query_block, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0), (0, 0)))

    def one(start):
        rows_q = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        i = start + jnp.arange(block)[:, None]
        j = pos[None, :]
        seen = j <= i
        if kind == SLIDING:
            seen = seen & (i - j < sh.window)
        scores = jnp.einsum("rqcgd,rkcd->rcgqk", rows_q, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        # a padded query row (beyond s) sees keys too: it is cut off below
        return jnp.einsum("rcgqk,rkcd->rqcgd", probs, v)

    o = jax.lax.map(one, jnp.arange(n) * block)          # [n, rows, block, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, n * block, H * hd)[:, :s]
    return (o * jax.nn.sigmoid(gate)) @ lp["o"]


def attention_part(x, lp, sh: Shape, kind: str):
    a = rms_norm(x, lp["attn_norm"], sh.eps)
    return x + rms_norm(attention(a, lp, sh, kind), lp["attn_post_norm"],
                        sh.eps)


# -- the routed layer ----------------------------------------------------------------


def route(h, lp, sh: Shape):
    """``(selected experts [t, top_k], their weights [t, top_k], the
    scores [t, routed])``: the top ``top_k`` of ``sigmoid + bias``,
    weighted by the unbiased scores, normalised, scaled."""
    scores = jax.nn.sigmoid(h @ lp["router"])
    _, idx = jax.lax.top_k(scores + lp["select_bias"], sh.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * sh.route_scale
    return idx, w, scores


def routed_setup(x, lp, sh: Shape):
    """``(m, selection, weights, what the shared expert gives)`` for ``x
    [rows, s, d]``: ``m`` the normed input, the selection over all
    ``routed`` experts."""
    m = rms_norm(x, lp["ffn_norm"], sh.eps)
    idx, w, _ = route(m, lp, sh)
    return m, idx, w, ffn(m, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])


def add_expert(acc, m, idx, w, e: int, gate, up, down):
    """``acc`` plus what expert ``e`` (its published index) adds: applied to
    every token and kept where the token selected it."""
    weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)    # 0 if unselected
    return acc + weight[..., None] * ffn(m, gate, up, down)


def ffn_close(x, f, lp, sh: Shape):
    return x + rms_norm(f, lp["ffn_post_norm"], sh.eps)


def ffn_part(x, lp, sh: Shape):
    """``x`` plus the normed dense FFN, or the normed sum of the shared
    expert and the held experts' part where ``lp`` has a router."""
    if "router" not in lp:
        m = rms_norm(x, lp["ffn_norm"], sh.eps)
        return ffn_close(x, ffn(m, lp["ffn_gate"], lp["ffn_up"],
                                lp["ffn_down"]), lp, sh)
    m, idx, w, f = routed_setup(x, lp, sh)
    for j in range(sh.held):
        f = add_expert(f, m, idx, w, sh.rank * sh.held + j,
                       lp["experts_gate"][j], lp["experts_up"][j],
                       lp["experts_down"][j])
    return ffn_close(x, f, lp, sh)


def layer(x, lp, sh: Shape, kind: str):
    """One decoder layer on ``x [rows, s, d]``."""
    return ffn_part(attention_part(x, lp, sh, kind), lp, sh)


def embed(emb, ids, sh: Shape):
    x = emb[ids]
    return x * math.sqrt(emb.shape[-1]) if sh.mup else x


def head_logits(x, final_norm, head, sh: Shape):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, sh.eps) @ head


def logits(params: Dict[str, Any], ids, sh: Shape, kinds, first: int = 0):
    """Logits ``[rows, s - first, vocab]`` of the whole forward pass:
    ``params`` holds ``emb``, ``layers`` (a list of per-layer dicts),
    ``final_norm`` and ``head``, all float32; ``kinds`` each layer's
    ``layer_types`` entry."""
    x = embed(params["emb"], ids, sh)
    with jax.default_matmul_precision("highest"):
        for lp, kind in zip(params["layers"], kinds):
            x = layer(x, lp, sh, kind)
    return head_logits(x[:, first:], params["final_norm"], params["head"], sh)
