"""The plain reference for the ``phi4_flash`` family: the forward pass that
``configs/phi4-mini-flash.json`` writes down under ``equations``, in float32
``jax.numpy`` at ``jax.default_matmul_precision("highest")``. The Mamba
recurrence is a token-by-token ``lax.scan``, attention a dense masked
softmax a key pair at a time; no cache, no ring, no chunk, no kernel, and the
cross-decoder runs over **every** position, so the program's prefill, which
runs it at the last prompt position alone, is held to the mathematics it
skips. It shares no code with ``paddle_tpu/``.

One sequence at a time: ``x [s, d]``. Parameters are per layer, under the
reference's own names; matrices are ``[in, out]``:

- every layer: ``norm_g, norm_b`` (the mixer's LayerNorm), ``ffn_norm_g,
  ffn_norm_b, gate_up [d, 2 f], down``;
- a Mamba layer: ``in_proj [d, 2 di], conv_w [4, di], conv_b, x_proj [di, r +
  2 n], dt_proj [r, di], dt_bias, a_log [di, n], d_skip, out_proj``;
- an attention layer: ``qkv [d, (h + 2 kv) hd], qkv_b, lambdas [4, hd],
  sub_norm, o, o_b``; a cross layer the same with ``q [d, h hd], q_b`` for
  ``qkv``;
- a gated memory unit: ``in_proj [d, di], out_proj``;
- the ends: ``emb, final_g, final_b`` (the head is ``emb``).

A layer's two halves are apart (:func:`mixer_part`, :func:`ffn_part`) so
that the chip check holds one half's float32 weights at a time; what the
cross-decoder reads of the self-decoder travels between them as ``memory``
(the last Mamba layer's ``y`` before its gate) and ``kv`` (the
full-attention layer's keys and values).

The recurrent state appears once more, as a definition and not as a way to
compute: :func:`carried_state`, what a Mamba channel's state holds after a
sequence's last token, written as the sum it stands for, in float64 numpy.
The chip check holds a served request's own state to it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Shape(NamedTuple):
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    window: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    eps: float
    token_block: int = 4096     # tokens a block of the FFN

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def shape_of(config: Dict[str, Any], **kw) -> Shape:
    """From a configuration file: published keys and ``assumed.mamba``."""
    m = config["assumed"]["mamba"]
    d = config["hidden_size"]
    return Shape(
        hidden=d, layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        window=config["sliding_window"], d_inner=m["expand"] * d,
        d_state=m["d_state"], d_conv=m["d_conv"],
        dt_rank=-(-d // 16) if m["dt_rank"] == "auto" else m["dt_rank"],
        eps=config["layer_norm_eps"], **kw)


def kind_of(layer: int, sh: Shape) -> str:
    """``mamba``, ``window``, ``full``, ``gmu`` or ``cross`` by the layer's
    index: the self-decoder is layers ``0 .. L / 2 + 1``."""
    half = sh.layers // 2
    if layer <= half:
        return "mamba" if layer % 2 == 0 else "window"
    if layer == half + 1:
        return "full"
    return "gmu" if layer % 2 == 0 else "cross"


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def mamba_inputs(u, p, sh: Shape):
    """``u [s, d] -> (c [s, di], delta [s, di], B, C [s, n], z [s, di])``:
    what the recurrence is given, before any state."""
    s, di, n, r = u.shape[0], sh.d_inner, sh.d_state, sh.dt_rank
    az = u @ p["in_proj"]
    a = jnp.concatenate([jnp.zeros((sh.d_conv - 1, di)), az[:, :di]], axis=0)
    c = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][i] * a[i:i + s] for i in range(sh.d_conv)))
    low = c @ p["x_proj"]
    delta = jax.nn.softplus(low[:, :r] @ p["dt_proj"] + p["dt_bias"])
    return c, delta, low[:, r:r + n], low[:, r + n:], az[:, di:]


def mamba_mixer(u, p, sh: Shape):
    """``u [s, d] -> (mixer output [s, d], y [s, di] before the gate)``, the
    recurrence a token at a time from a zero state."""
    c, delta, b, cm, z = mamba_inputs(u, p, sh)
    a = -jnp.exp(p["a_log"])                                    # [di, n]

    def token(state, xs):
        delta_t, c_t, b_t, c_out = xs
        state = (jnp.exp(delta_t[:, None] * a) * state
                 + (delta_t * c_t)[:, None] * b_t[None, :])
        return state, state @ c_out

    _, y = jax.lax.scan(token, jnp.zeros_like(a), (delta, c, b, cm))
    y = y + p["d_skip"] * c
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def lambda_init(layer):
    """``0.8 - 0.6 exp(-0.3 l)``; ``layer`` an int or a traced scalar."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer)


def diff_attention(q, k, v, p, sh: Shape, layer: int, window=None):
    """Differential attention: ``q [s, heads * hd]``, ``k, v [s, kv_heads *
    hd]``, causal, over the last ``window`` keys where one is given. Query
    pair ``(2p, 2p + 1)`` reads key pair ``c = p // 2`` and its two values
    side by side. Dense ``[s, s]`` weights, a key pair's four query heads at
    a time."""
    s, hd = q.shape[0], sh.head_dim
    pairs = sh.kv_heads // 2
    t, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= t if window is None else (j <= t) & (j > t - window)
    lam = p["lambdas"]
    lam = (jnp.exp(lam[0] @ lam[1]) - jnp.exp(lam[2] @ lam[3])
           + lambda_init(layer))

    def key_pair(xs):
        qc, kc, vc = xs           # [s, 2, 2, hd], [s, 2, hd], [s, 2 hd]
        scores = jnp.einsum("tpid,jid->pitj", qc, kc) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("pitj,je->pite", a, vc)
        return o[:, 0] - lam * o[:, 1]                          # [2, s, 2 hd]

    o = jax.lax.map(key_pair, (
        q.reshape(s, pairs, 2, 2, hd).transpose(1, 0, 2, 3, 4),
        k.reshape(s, pairs, 2, hd).transpose(1, 0, 2, 3),
        v.reshape(s, pairs, 2 * hd).transpose(1, 0, 2)))        # [c, 2, s, 2hd]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + sh.eps)
    o = o * p["sub_norm"] * (1.0 - lambda_init(layer))
    return o.transpose(2, 0, 1, 3).reshape(s, -1) @ p["o"] + p["o_b"]


def mixer_part(x, p, sh: Shape, layer, memory=None, kv=None, kind=None):
    """``x + Mixer_l(LayerNorm(x))`` for one sequence ``x [s, d]``. Returns
    ``(x', memory, kv)``: a Mamba layer hands on its ``y`` as ``memory``,
    the full-attention layer its keys and values as ``kv``; the others pass
    on what they were given. ``layer`` may be a traced scalar where the
    layer's ``kind`` is given (one compiled function a kind: a layer's
    index then only sets ``lambda_init``)."""
    kind = kind or kind_of(layer, sh)
    wide, kvw = sh.heads * sh.head_dim, sh.kv_heads * sh.head_dim
    with jax.default_matmul_precision("highest"):
        u = layer_norm(x, p["norm_g"], p["norm_b"], sh.eps)
        if kind == "mamba":
            out, memory = mamba_mixer(u, p, sh)
        elif kind == "gmu":
            out = (memory * jax.nn.silu(u @ p["in_proj"])) @ p["out_proj"]
        elif kind == "cross":
            out = diff_attention(u @ p["q"] + p["q_b"], *kv, p, sh, layer)
        else:
            qkv = u @ p["qkv"] + p["qkv_b"]
            q, k, v = (qkv[:, :wide], qkv[:, wide:wide + kvw],
                       qkv[:, wide + kvw:])
            if kind == "full":
                kv = (k, v)
            out = diff_attention(q, k, v, p, sh, layer,
                                 sh.window if kind == "window" else None)
        return x + out, memory, kv


def ffn_part(x, p, sh: Shape):
    """``x + FFN(LayerNorm(x))``, a block of tokens at a time."""
    with jax.default_matmul_precision("highest"):
        out = []
        for start in range(0, x.shape[0], sh.token_block):
            h = layer_norm(x[start:start + sh.token_block], p["ffn_norm_g"],
                           p["ffn_norm_b"], sh.eps) @ p["gate_up"]
            f = h.shape[-1] // 2
            out.append((jax.nn.silu(h[:, :f]) * h[:, f:]) @ p["down"])
        return x + jnp.concatenate(out, axis=0)


def head_logits(x, final_g, final_b, emb, sh: Shape):
    """``[s, d] -> [s, rows of emb]``: the head is the embedding; ``emb`` may
    be a block of the vocabulary's rows."""
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, final_g, final_b, sh.eps) @ emb.T


def forward(params, ids, sh: Shape):
    """Logits ``[s, vocab]`` of one sequence ``ids [s]``: every layer over
    every position."""
    x = params["emb"][ids]
    memory = kv = None
    for layer, lp in enumerate(params["layers"]):
        x, memory, kv = mixer_part(x, lp, sh, layer, memory, kv)
        x = ffn_part(x, lp, sh)
    return head_logits(x, params["final_g"], params["final_b"], params["emb"],
                       sh)


def carried_state(delta, u, b, a):
    """What the recurrence holds of some channels after the last of ``t``
    tokens: ``delta, u [t, c]``, ``b [t, n]``, ``a [n, c]`` (negative) ->
    ``[n, c]``, ``sum_j exp(a[n, c] sum_{s > j} delta_s[c]) u_j[c] b_j[n]``.
    float64 numpy: the sum as written, whatever its inputs' precision."""
    delta, u, b, a = (np.asarray(x, np.float64) for x in (delta, u, b, a))
    after = np.cumsum(delta[::-1], axis=0)[::-1] - delta        # [t, c]
    return np.einsum("tnc,tc,tn->nc", np.exp(a[None] * after[:, None, :]), u, b)
