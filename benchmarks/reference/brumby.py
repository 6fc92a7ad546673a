"""The plain reference for the ``brumby`` family: the forward pass that
``configs/brumby-14b-pp5.json`` writes down in words, in float32
``jax.numpy`` at ``jax.default_matmul_precision("highest")``. Power retention
is computed in its attention form and in no other: a ``[queries, keys]``
weight matrix a head from the squared scaled products and the cumulative
log-gates, divided by its row sum. No kernel, no scan, no cache, no state,
no feature map and no chunking; a Python loop over the layers. It shares no
code with ``paddle_tpu/``.

One sequence at a time: ``x [s, d]``. Parameters are per layer, under the
reference's own names; matrices are ``[in, out]``:

- a layer's mixer: ``attn_norm, q, k, v, q_norm, k_norm, gate, gate_bias, o``;
- its FFN: ``ffn_norm, ffn_gate, ffn_up, ffn_down``;
- the ends: ``emb, final_norm, head``.

Departures from the published code, as the configuration file lists them:
(a) the published inference keeps keys and values up to a switch-over length
and folds them into a state after it; this is the function both compute;
(b) rotary pairs are ``(2i, 2i + 1)`` rotated in place (the published code
rotates halves: one fixed permutation of a head's dimensions of q and k
alike, every product unchanged);
(c) ``mixer_part`` and ``ffn_part`` are a layer's two halves, so that the
chip check holds one half's float32 weights at a time.

The recurrent form appears once, as a definition and not as a way to
compute: :func:`carried_sums`, what a state holds after a sequence's last
token, written as the sum it stands for, in float64 numpy on the host. The
chip check holds a served request's own state to it.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Shape(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    eps_n: float
    token_block: int = 4096     # tokens a block of the FFN


def shape_of(config: Dict[str, Any], **kw) -> Shape:
    """From a configuration file: published keys, ``assumed.eps_n``."""
    return Shape(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        eps=config["rms_norm_eps"], theta=config["rope_theta"],
        eps_n=config["assumed"]["eps_n"], **kw)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta: float):
    """``x [s, heads, dim]``: pairs ``(2i, 2i + 1)`` turned by ``positions *
    theta^(-2i / dim)``."""
    dim = x.shape[-1]
    freqs = theta ** (-2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def mixer_inputs(u, p, sh: Shape):
    """``u [s, d] -> (q [s, heads, hd] scaled, k, v [s, kv, hd], log gamma [s,
    kv])``: what the mixer computes from a layer's normed input before any
    token meets another."""
    s = u.shape[0]
    pos = jnp.arange(s)
    q = (u @ p["q"]).reshape(s, sh.heads, sh.head_dim)
    k = (u @ p["k"]).reshape(s, sh.kv_heads, sh.head_dim)
    v = (u @ p["v"]).reshape(s, sh.kv_heads, sh.head_dim)
    q = rope(rms_norm(q, p["q_norm"], sh.eps), pos, sh.theta) / sh.head_dim ** 0.5
    k = rope(rms_norm(k, p["k_norm"], sh.eps), pos, sh.theta)
    return q, k, v, jax.nn.log_sigmoid(u @ p["gate"] + p["gate_bias"])


def retention_mixer(u, p, sh: Shape):
    """``u [s, d] -> [s, d]``: the attention form, every head's whole ``[s,
    s]`` weight matrix at once."""
    s = u.shape[0]
    group = sh.heads // sh.kv_heads
    pos = jnp.arange(s)
    q, k, v, log_gamma = mixer_inputs(u, p, sh)
    cum = jnp.cumsum(log_gamma, axis=0)
    # sum_{s=j+1..t} log gamma_s, for j <= t
    span = cum[:, None, :] - cum[None, :, :]                        # [t, j, kv]
    decay = jnp.where((pos[:, None] >= pos[None, :])[..., None],
                      jnp.exp(jnp.minimum(span, 0.0)), 0.0)
    qg = q.reshape(s, sh.kv_heads, group, sh.head_dim)
    a = jnp.einsum("tcgd,jcd->cgtj", qg, k) ** 2 * decay.transpose(2, 0, 1)[:, None]
    o = jnp.einsum("cgtj,jcd->tcgd", a, v) / (
        jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None] + sh.eps_n)
    return o.reshape(s, -1) @ p["o"]


def carried_sums(k, v, log_gamma, dim: int = 0):
    """What the recurrent form holds of one key head after the last of ``t``
    tokens, for the feature-map entries that are products of dimension
    ``dim``: ``k, v [t, hd]``, ``log_gamma [t]`` -> ``[hd + 1, hd]``, row
    ``e < hd`` the state's ``sum_j D_j k_j[dim] k_j[c] v_j[e]`` and row
    ``hd`` the key sum's ``sum_j D_j k_j[dim] k_j[c]``, ``D_j = exp(sum_{s >
    j} log_gamma_s)``. float64 numpy: the sum as written, whatever its
    inputs' precision."""
    k, v, log_gamma = (np.asarray(a, np.float64) for a in (k, v, log_gamma))
    after = np.cumsum(log_gamma[::-1])[::-1] - log_gamma
    ve = np.concatenate([v, np.ones((v.shape[0], 1))], axis=1)
    return (ve * (np.exp(after) * k[:, dim])[:, None]).T @ k


def mixer_part(x, p, sh: Shape):
    """``x + Mixer(RMSNorm(x))`` for one sequence ``x [s, d]``."""
    with jax.default_matmul_precision("highest"):
        return x + retention_mixer(rms_norm(x, p["attn_norm"], sh.eps), p, sh)


def ffn_part(x, p, sh: Shape):
    """``x + FFN(RMSNorm(x))``, a block of tokens at a time."""
    with jax.default_matmul_precision("highest"):
        out = []
        for start in range(0, x.shape[0], sh.token_block):
            h = rms_norm(x[start:start + sh.token_block], p["ffn_norm"], sh.eps)
            out.append((jax.nn.silu(h @ p["ffn_gate"]) * (h @ p["ffn_up"]))
                       @ p["ffn_down"])
        return x + jnp.concatenate(out, axis=0)


def embed(emb, ids):
    return emb[ids]


def head_logits(x, final_norm, head, sh: Shape):
    """``[s, d] -> [s, columns of head]``: ``head`` may be a block of the
    vocabulary's columns."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, sh.eps) @ head


def forward(params, ids, sh: Shape):
    """Logits ``[s, vocab]`` of one sequence ``ids [s]`` through the layers
    ``params["layers"]``."""
    x = embed(params["emb"], ids)
    for lp in params["layers"]:
        x = mixer_part(x, lp, sh)
        x = ffn_part(x, lp, sh)
    return head_logits(x, params["final_norm"], params["head"], sh)
