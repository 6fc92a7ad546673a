"""The plain reference for the ``kimi_k2`` family: the DeepseekV3 decoder's
forward pass as published (``modeling_deepseek.py`` beside the config named
in ``configs/kimi-k2.5-ep32.json``), in float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``. No kernel, cache, scan, sort
or grouped product: attention is the expanded form over the whole sequence,
the routed experts are a Python loop over the experts held with a mask.

It computes one rank's share, as the program does: ``held`` experts from
``rank * held`` of the ``routed`` the router scores, and logits over the
rows of the vocabulary it is given. What the absent experts would add is
left out and the partial result goes on to the next layer.

Parameters are per layer, under the reference's own names (the published
module names, shortened): ``attn_norm, q_a, q_norm, q_b, kv_a, kv_norm,
kv_b, o, ffn_norm`` and either ``gate, up, down`` (a dense layer) or
``router, select_bias, shared_gate, shared_up, shared_down, experts_gate,
experts_up, experts_down`` (an expert layer; the banks ``[held, ...]``).
Matrices are ``[in, out]``; ``kv_b`` is ``[kv_lora, H * (nope + v)]`` with
each head's ``[k_nope | v]`` side by side, as published.

Departures from the published code, each marked where it is made:
(a) rotary pairs are ``(2i, 2i + 1)`` rotated in place, where the published
code first de-interleaves and then rotates halves: one fixed permutation
of the rotary dimensions of q and k alike, every score unchanged;
(b) the RMSNorm scale multiplies in float32 before the result is rounded
(the published code rounds to the input dtype first; the same in float32);
(c) ``attention_part`` and ``ffn_part`` are a layer's two halves, so that
the chip check can hold one half's float32 weights at a time and apply it
to a row at a time (rows are independent) beside the served weights.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp


class Shape(NamedTuple):
    heads: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    eps: float
    top_k: int
    routed_scaling: float
    routed: int          # experts the router scores (published)
    held: int            # experts held here
    rank: int            # which block of ``held`` experts
    theta: float         # rope_theta, then the rope_scaling group
    factor: float
    beta_fast: float
    beta_slow: float
    original_max_position: int
    mscale: float
    mscale_all_dim: float


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a configuration file: published keys, the ``published`` group
    for what was cut, the ``deployment`` group for the rank."""
    sc = config["rope_scaling"]
    return Shape(
        heads=config["num_attention_heads"], kv_lora=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"], eps=config["rms_norm_eps"],
        top_k=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        routed=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"],
        rank=config["deployment"]["expert_rank"],
        theta=config["rope_theta"], factor=sc["factor"],
        beta_fast=sc["beta_fast"], beta_slow=sc["beta_slow"],
        original_max_position=sc["original_max_position_embeddings"],
        mscale=sc["mscale"], mscale_all_dim=sc.get("mscale_all_dim", 0))


def rms_norm(x, g, eps):
    # departure (b): scale applied before rounding
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def ffn(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


# -- rotary (modeling_deepseek.py: DeepseekV3YarnRotaryEmbedding) ---------------


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_find_correction_dim(rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base, max_pos))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(dim: int, sh: Shape):
    freq_extra = 1.0 / sh.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freq_inter = freq_extra / sh.factor
    low, high = yarn_find_correction_range(
        sh.beta_fast, sh.beta_slow, dim, sh.theta, sh.original_max_position)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def rotate(x, positions, inv_freq, mscale):
    """``x [..., s, dim]`` by ``positions [s]``: each pair ``(2i, 2i + 1)``
    as one complex number times ``mscale * exp(i pos f_i)`` (departure (a):
    the pairs stay where they are)."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    turn = mscale * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * turn
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


# -- attention (DeepseekV3Attention, the expanded form) --------------------------


def attention(x, lp, sh: Shape):
    """``x [rows, s, d]`` -> the attention's output, before the residual."""
    rows, s, _ = x.shape
    H, qk = sh.heads, sh.nope + sh.rope
    inv_freq = yarn_inv_freq(sh.rope, sh)
    mscale = (yarn_get_mscale(sh.factor, sh.mscale)
              / yarn_get_mscale(sh.factor, sh.mscale_all_dim))
    softmax_scale = qk ** -0.5
    if sh.mscale_all_dim:
        m = yarn_get_mscale(sh.factor, sh.mscale_all_dim)
        softmax_scale = softmax_scale * m * m
    pos = jnp.arange(s)

    q = rms_norm(x @ lp["q_a"], lp["q_norm"], sh.eps) @ lp["q_b"]
    q = q.reshape(rows, s, H, qk).transpose(0, 2, 1, 3)        # [rows, H, s, qk]
    q_nope, q_pe = q[..., :sh.nope], q[..., sh.nope:]
    ckv = x @ lp["kv_a"]
    c_kv, k_pe = ckv[..., :sh.kv_lora], ckv[..., sh.kv_lora:]
    kv = rms_norm(c_kv, lp["kv_norm"], sh.eps) @ lp["kv_b"]
    kv = kv.reshape(rows, s, H, sh.nope + sh.v).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :sh.nope], kv[..., sh.nope:]
    q_pe = rotate(q_pe, pos, inv_freq, mscale)
    k_pe = rotate(k_pe, pos, inv_freq, mscale)[:, None]         # one for all heads

    scores = (jnp.einsum("rhqd,rhkd->rhqk", q_nope, k_nope)
              + jnp.einsum("rhqd,rzkd->rhqk", q_pe, k_pe)) * softmax_scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("rhqk,rhkd->rhqd", probs, v)
    return out.transpose(0, 2, 1, 3).reshape(rows, s, H * sh.v) @ lp["o"]


# -- the routed layer (DeepseekV3MoE with MoEGate, noaux_tc, one group) -----------


def route(h, lp, sh: Shape):
    """``(selected experts [t, top_k], their weights [t, top_k], the
    scores [t, routed])``: the top ``top_k`` of ``sigmoid + bias``,
    weighted by the unbiased scores, normalised, scaled."""
    scores = jax.nn.sigmoid(h @ lp["router"])
    _, idx = jax.lax.top_k(scores + lp["select_bias"], sh.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * sh.routed_scaling
    return idx, w, scores


def routed_part(h, lp, sh: Shape, rank: int, held: int):
    """What experts ``rank * held .. rank * held + held`` add for tokens
    ``h [t, d]``: a loop over those experts, each applied to every token
    and kept where the token selected it."""
    idx, w, _ = route(h, lp, sh)
    out = jnp.zeros_like(h)
    for j in range(held):
        e = rank * held + j
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # 0 if unselected
        out = out + weight[:, None] * ffn(h, lp["experts_gate"][j],
                                          lp["experts_up"][j],
                                          lp["experts_down"][j])
    return out


def attention_part(x, lp, sh: Shape):
    return x + attention(rms_norm(x, lp["attn_norm"], sh.eps), lp, sh)


def ffn_part(x, lp, sh: Shape):
    """``x`` plus the dense FFN, or plus the shared expert and the held
    experts' part where ``lp`` has a router."""
    h = rms_norm(x, lp["ffn_norm"], sh.eps)
    if "router" not in lp:
        return x + ffn(h, lp["gate"], lp["up"], lp["down"])
    flat = h.reshape(-1, h.shape[-1])
    routed = routed_part(flat, lp, sh, sh.rank, sh.held).reshape(h.shape)
    shared = ffn(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return x + shared + routed


def layer(x, lp, sh: Shape):
    """One decoder layer on ``x [rows, s, d]``."""
    return ffn_part(attention_part(x, lp, sh), lp, sh)


def embed(emb, ids):
    return emb[ids]


def head_logits(x, final_norm, head, sh: Shape):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, sh.eps) @ head


def logits(params: Dict[str, Any], ids, sh: Shape, first: int = 0):
    """Logits ``[rows, s - first, vocab]`` of the whole forward pass:
    ``params`` holds ``emb``, ``layers`` (a list of per-layer dicts),
    ``final_norm`` and ``head``, all float32."""
    x = embed(params["emb"], ids)
    with jax.default_matmul_precision("highest"):
        for lp in params["layers"]:
            x = layer(x, lp, sh)
    return head_logits(x[:, first:], params["final_norm"], params["head"], sh)
