"""The plain reference for the ``granite_hybrid`` family: the
``granitemoehybrid`` decoder's forward pass as the configuration file
describes it (``configs/granite-4.0-h-small-ep2.json``: the published keys,
and under ``assumed`` what they leave open), in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``. No kernel, cache, chunk, sort or
grouped product: a Python loop over layers, the Mamba-2 recurrence a
token-by-token ``lax.scan`` from a zero state on states ``[heads, head_dim,
d_state]`` as published, attention one dense masked softmax over the whole
sequence with the key/value head of query head ``h`` indexed ``h // group``
and never repeated, the routed experts a Python loop over the experts held
with a mask. It imports nothing from ``paddle_tpu``.

It computes one rank's share, as the program does: ``held`` experts from
``rank * held`` of the ``routed`` the router scores, and logits over the rows
of the (tied) embedding it is given. What the absent experts would add is
left out and the partial result goes on to the next layer.

One layer, ``h [rows, s, d]``::

    h = h + r * Mixer(rms(h; norm))            r = residual_multiplier
    u = rms(h; ffn_norm)
    h = h + r * (shared(u) + sum over the selected held experts e of w_e E_e(u))

    Mamba-2:   [z | xBC | dt] = u W_in;  xBC = silu(conv4(xBC) + b_conv);  [x | B | C] = xBC
               dt = softplus(dt + dt_bias);  a = -exp(A_log)      (a head's)
               S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
               Mixer = W_out rms(y * silu(z); gate_norm)           (gate before norm)
    attention: q = u W_q [.., heads, hd];  k, v = u W_k, u W_v [.., kv_heads, hd];  no rotation
               o = softmax(m * q_h . k_(h // group), keys <= query) v_(h // group)
               Mixer = W_o o                                      m = attention_multiplier
    router:    the top_k largest of u W_r; w = softmax over those top_k logits

In: ``h = embedding_multiplier * E[ids]``. Out: ``rms(h; final_norm) E^T /
logits_scaling``.

Parameters are per layer, under the reference's own names: ``norm`` and
either ``in_proj, conv_w [taps, channels], conv_b, dt_bias, a_log, d_skip,
gate_norm, out_proj`` or ``q, k, v, o``; then ``ffn_norm, router,
shared_gate, shared_up, shared_down, experts_gate, experts_up, experts_down``
(the banks ``[held, ...]``). Matrices are ``[in, out]``.

Departures from the published description, each marked where it is made:
(a) the published fused expert input matrix ``[2 f, d]`` is taken as its two
halves, gate then up, each ``[d, f]``;
(b) the RMSNorm gain multiplies in float32 before the result is rounded (the
same in float32);
(c) the queries are walked in blocks (``query_block``, ``jax.lax.map``) where
a sequence is longer than one; each block is the same dense masked softmax
over all keys;
(d) ``mixer_part`` and ``ffn_part`` are a layer's two halves, and
``ffn_part`` is made of :func:`routed_setup`, :func:`add_expert` and
:func:`ffn_close`, so that the chip check can hold one half's (or one
expert's) float32 weights at a time beside the served weights.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, ATTENTION = "mamba", "attention"


class Shape(NamedTuple):
    heads: int           # attention
    kv_heads: int
    head_dim: int
    m_heads: int         # Mamba-2
    m_head_dim: int
    d_state: int
    d_conv: int
    eps: float
    top_k: int
    routed: int          # experts the router scores (published)
    held: int            # experts held here
    rank: int            # which block of ``held`` experts
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    query_block: int = 4096

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim


def shape_of(config: Dict[str, Any], **kw) -> Shape:
    """From a configuration file: published keys, the ``published`` group
    for what was cut, the ``deployment`` group for the rank, ``assumed`` for
    the head size the file does not state."""
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["assumed"]["head_dim"],
        m_heads=config["mamba_n_heads"], m_head_dim=config["mamba_d_head"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        eps=config["rms_norm_eps"], top_k=config["num_experts_per_tok"],
        routed=config["published"]["num_local_experts"],
        held=config["num_local_experts"],
        rank=config["deployment"]["expert_rank"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"], **kw)


def layers_of(config: Dict[str, Any]):
    """``[(published index, kind)]`` of the layers held."""
    return [(i, config["layer_types"][i]) for i in config["layer_indices"]]


def rms_norm(x, g, eps):
    # departure (b): gain applied before rounding
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def ffn(x, gate, up, down):
    # departure (a): the fused input matrix as gate, then up
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- Mamba-2 -------------------------------------------------------------------------


def mamba_inputs(u, lp, sh: Shape):
    """``u [rows, s, d]`` (normed) -> ``(x [rows, s, heads, hd], B, C [rows,
    s, n], dt [rows, s, heads], z [rows, s, d_inner])``: the projection's
    columns are gate, ``xBC``, ``dt``; the causal depthwise convolution sees
    zeros before the sequence."""
    rows, s, _ = u.shape
    di, n = sh.d_inner, sh.d_state
    zxd = u @ lp["in_proj"]
    z, xbc, dt = zxd[..., :di], zxd[..., di:2 * di + 2 * n], zxd[..., 2 * di + 2 * n:]
    padded = jnp.pad(xbc, ((0, 0), (sh.d_conv - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * lp["conv_w"][i] for i in range(sh.d_conv))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    x = xbc[..., :di].reshape(rows, s, sh.m_heads, sh.m_head_dim)
    return (x, xbc[..., di:di + n], xbc[..., di + n:],
            jax.nn.softplus(dt + lp["dt_bias"]), z)


def recurrence(x, b, c, dt, a, d_skip, keep=lambda s: s):
    """Token by token from a zero state: ``x [rows, s, heads, hd]``, ``b, c
    [rows, s, n]``, ``dt [rows, s, heads]``, ``a, d_skip [heads]`` -> ``(y
    [rows, s, heads, hd], the last state [rows, heads, hd, n])``. ``keep``
    is applied to the state a token leaves (a sensitivity run rounds it)."""
    rows, _, heads, hd = x.shape

    def step(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = keep(jnp.exp(dt_t * a)[..., None, None] * state
                     + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("rhpn,rn->rhp", state, c_t) + d_skip[:, None] * x_t
        return state, y

    state, y = jax.lax.scan(
        step, jnp.zeros((rows, heads, hd, b.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt)))
    return jnp.moveaxis(y, 0, 1), state


def mamba(u, lp, sh: Shape):
    rows, s, _ = u.shape
    x, b, c, dt, z = mamba_inputs(u, lp, sh)
    y, _ = recurrence(x, b, c, dt, -jnp.exp(lp["a_log"]), lp["d_skip"])
    y = y.reshape(rows, s, sh.d_inner) * jax.nn.silu(z)     # gate, then norm
    return rms_norm(y, lp["gate_norm"], sh.eps) @ lp["out_proj"]


# -- attention ---------------------------------------------------------------------


def attention(a, lp, sh: Shape):
    """``a [rows, s, d]`` (normed) -> plain causal grouped-query attention
    through its output projection: no positions, the model's own scale."""
    rows, s, _ = a.shape
    H, K, hd = sh.heads, sh.kv_heads, sh.head_dim
    group = H // K
    # query head h = c * group + g reads key head c: q as [.., K, group, hd]
    q = (a @ lp["q"]).reshape(rows, s, K, group, hd)
    k = (a @ lp["k"]).reshape(rows, s, K, hd)
    v = (a @ lp["v"]).reshape(rows, s, K, hd)
    pos = jnp.arange(s)

    # departure (c): the queries a block at a time, each against all keys
    block = min(sh.query_block, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0), (0, 0)))

    def one(start):
        rows_q = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seen = pos[None, :] <= start + jnp.arange(block)[:, None]
        scores = (jnp.einsum("rqcgd,rkcd->rcgqk", rows_q, k)
                  * sh.attention_multiplier)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rcgqk,rkcd->rqcgd", probs, v)

    o = jax.lax.map(one, jnp.arange(n) * block)          # [n, rows, block, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, n * block, H * hd)[:, :s]
    return o @ lp["o"]


def mixer_part(x, lp, sh: Shape, kind: str):
    u = rms_norm(x, lp["norm"], sh.eps)
    mixed = mamba(u, lp, sh) if kind == MAMBA else attention(u, lp, sh)
    return x + sh.residual_multiplier * mixed


# -- the routed layer ----------------------------------------------------------------


def route(h, lp, sh: Shape):
    """``(selected experts [.., top_k], their weights [.., top_k])``: the
    ``top_k`` largest logits, weighted by the softmax over those alone."""
    picked, idx = jax.lax.top_k(h @ lp["router"], sh.top_k)
    return idx, jax.nn.softmax(picked, axis=-1)


def routed_setup(x, lp, sh: Shape):
    """``(m, selection, weights, what the shared expert gives)`` for ``x
    [rows, s, d]``: ``m`` the normed input, the selection over all ``routed``
    experts."""
    m = rms_norm(x, lp["ffn_norm"], sh.eps)
    idx, w = route(m, lp, sh)
    return m, idx, w, ffn(m, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])


def add_expert(acc, m, idx, w, e: int, gate, up, down):
    """``acc`` plus what expert ``e`` (its published index) adds: applied to
    every token and kept where the token selected it."""
    weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)    # 0 if unselected
    return acc + weight[..., None] * ffn(m, gate, up, down)


def ffn_close(x, f, sh: Shape):
    return x + sh.residual_multiplier * f


def ffn_part(x, lp, sh: Shape):
    m, idx, w, f = routed_setup(x, lp, sh)
    for j in range(sh.held):
        f = add_expert(f, m, idx, w, sh.rank * sh.held + j,
                       lp["experts_gate"][j], lp["experts_up"][j],
                       lp["experts_down"][j])
    return ffn_close(x, f, sh)


def layer(x, lp, sh: Shape, kind: str):
    """One decoder layer on ``x [rows, s, d]``."""
    return ffn_part(mixer_part(x, lp, sh, kind), lp, sh)


def embed(emb, ids, sh: Shape):
    return emb[ids] * sh.embedding_multiplier


def head_logits(x, final_norm, emb, sh: Shape):
    """Over the rows of the tied embedding given."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, sh.eps) @ emb.T / sh.logits_scaling


def logits(params: Dict[str, Any], ids, sh: Shape, kinds, first: int = 0):
    """Logits ``[rows, s - first, vocab]`` of the whole forward pass:
    ``params`` holds ``emb``, ``layers`` (a list of per-layer dicts) and
    ``final_norm``, all float32; ``kinds`` each layer's ``layer_types``
    entry."""
    x = embed(params["emb"], ids, sh)
    with jax.default_matmul_precision("highest"):
        for lp, kind in zip(params["layers"], kinds):
            x = layer(x, lp, sh, kind)
    return head_logits(x[:, first:], params["final_norm"], params["emb"], sh)


# -- a carried state by its definition, float64 --------------------------------------


def carried_state(dt, x, b, a, head_dim: int) -> np.ndarray:
    """The state some channels' recurrence leaves, from what it was handed:
    ``dt [t, heads]`` (all heads'), ``x [t, lanes]`` (the first ``lanes /
    head_dim`` heads'), ``b [t, n]``, ``a [heads]`` (negative) -> ``[n,
    lanes]``, ``S[n, c] = sum_t exp(sum_{r > t} dt_r a) dt_t x_t[c] b_t[n]``,
    token by token in float64 from zeros."""
    dt, x, b = (np.asarray(v, np.float64) for v in (dt, x, b))
    lanes = x.shape[1]
    head = np.arange(lanes) // head_dim
    step = dt[:, head]                                               # [t, lanes]
    decay = np.exp(step * np.asarray(a, np.float64)[head])
    state = np.zeros((b.shape[1], lanes))
    for t in range(x.shape[0]):
        state = decay[t][None, :] * state + b[t][:, None] * (step[t] * x[t])[None, :]
    return state
