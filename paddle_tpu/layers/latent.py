"""Latent attention (MLA) blocks of the DeepseekV3 decoder and YaRN's
frequency blend for their rotary positions; the norm, the rotary map and the
FFN that stand round them are ``layers/blocks.py``'s. A sibling of
``layers/stacked.py``, written once in the stacked form: parameters carry a
leading ``[num_layers, ...]`` axis, blocks are pure functions of
``(activation, layer_params)`` with no ``LayerHelper`` call inside, so they
trace under ``lax.scan``.

Latent attention projects a token to one 512-wide latent ``c_kv`` and one
64-wide rotary key ``k_rope`` shared by all heads; keys and values are
linear in the latent. That gives the block two forms, which agree
(``tests/test_kimi_k2.py``):

- **expanded** (:func:`mla_prefill`): per head ``[k_nope | v] = c_kv W_kvb``,
  scores ``q_nope . k_nope + q_rope . k_rope`` (128 + 64 wide), values 128
  wide, through the flash kernel. The two parts of a score travel apart
  from the projection to the softmax: ``q_nope``, ``k_nope``, ``v`` and the
  output are ``[b, s, H * 128]`` as their matmuls leave and take them,
  ``q_rope`` is ``[b, s, H * 64]`` and ``k_rope`` ``[b, s, 64]`` once, the
  kernel's second score operand (``ops/flash_attention.py``, ``q_rot`` /
  ``k_rot``). No array is 192 wide: a head of 192 lies astride the
  128-lane groups the TPU tiles a minor dimension in, so every array that
  held one was kept at 256 lanes and copied to get there. The kernel puts
  a tile's two parts side by side in VMEM, where that costs nothing.
- **absorbed** (:func:`mla_decode`): ``W_kvb`` moves onto the query and the
  output (``q' = q_nope W_k^T``, 512 a head; ``o = (P c_kv) W_v``), so a
  cached step reads 576 numbers a token and layer, whatever the head count,
  and never builds a key or a value.

The cache is what the absorbed form reads, stored so that the TPU's
(8, 128) tiles pad nothing: the latents as ``[rows, T, 512]`` (512 = 4 x
128 lanes) and the rotary keys transposed, ``[rows, 64, T]`` (positions on
lanes; ``q_rope @ that`` is the rotary part of every score with no
transposition). One ``[rows, T, 576]`` slab would be held at 640 lanes.

The parameter table keeps the published layouts (``q_b/w [q_lora, H *
(nope + rope)]``, a head's 128 and 64 side by side). :func:`regrouped`
makes of it what both forms read, once, outside a generator's scans and
its loop: ``q_b``'s columns as every head's ``nope`` and every head's
``rope``, and ``kv_b``'s halves flat for the prefill's products.

Rotary pairs are ``(2i, 2i + 1)``; the published code de-interleaves and
rotates halves, the same map up to one fixed permutation of the 64 rotary
dimensions applied to q and k alike, which leaves every score unchanged.
"""

from __future__ import annotations

import math
import time
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..framework import LayerHelper
from ..ops.flash_attention import flash_attention, padded_rows
from .blocks import params, rms_norm, rope
from .stacked import NEG_INF


class MLADims(NamedTuple):
    """The widths of one latent-attention block (published key names in
    brackets)."""
    d_model: int          # hidden_size
    heads: int            # num_attention_heads
    q_lora: int           # q_lora_rank
    kv_lora: int          # kv_lora_rank
    nope: int             # qk_nope_head_dim
    rope: int             # qk_rope_head_dim
    v: int                # v_head_dim
    eps: float = 1e-5     # rms_norm_eps

    @property
    def qk(self) -> int:
        return self.nope + self.rope


class Yarn(NamedTuple):
    """``rope_theta`` and the ``rope_scaling`` group of the config."""
    theta: float = 10000.0
    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


# -- rotary frequencies --------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, y: Yarn):
    """The ``dim // 2`` rotary frequencies, float32: ``theta^(-2i/dim)``,
    divided by ``factor`` for the slow dimensions, left alone for the fast
    ones, blended linearly between the two correction dimensions."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = y.theta ** (-2.0 * i / dim)
    if y.factor <= 1.0:
        return f

    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(y.theta)))

    lo = max(math.floor(correction_dim(y.beta_fast)), 0)
    hi = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    ramp = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / y.factor * ramp + f * (1.0 - ramp)


def yarn_cos_sin_scale(y: Yarn) -> float:
    """What YaRN multiplies cos and sin by (1 where ``mscale`` equals
    ``mscale_all_dim``, as in Kimi-K2.5)."""
    return (_yarn_mscale(y.factor, y.mscale)
            / _yarn_mscale(y.factor, y.mscale_all_dim))


def softmax_scale(dims: MLADims, y: Yarn) -> float:
    """``qk^-0.5``, times ``yarn_mscale(factor, mscale_all_dim)^2`` where
    that is set."""
    m = _yarn_mscale(y.factor, y.mscale_all_dim) if y.mscale_all_dim else 1.0
    return dims.qk ** -0.5 * m * m


# -- parameters ------------------------------------------------------------------


def mla_params(dims: MLADims, dtype, layers: Optional[int] = None,
               name: str = "mla") -> Dict[str, jax.Array]:
    """One block's attention parameters (or ``layers`` of them, stacked),
    every matrix in ``dtype``. The published ``kv_b_proj`` is held as its
    two halves, heads leading: ``kv_b_k [H, nope, kv_lora]`` (so that
    ``q_nope @ kv_b_k`` is the absorbed query) and ``kv_b_v [H, kv_lora,
    v]``."""
    d, h = dims.d_model, dims.heads
    return params(LayerHelper(name, name=name), {
        "attn_norm/g": ((d,), None),
        "q_a/w": ((d, dims.q_lora), d),
        "q_norm/g": ((dims.q_lora,), None),
        "q_b/w": ((dims.q_lora, h * dims.qk), dims.q_lora),
        "kv_a/w": ((d, dims.kv_lora + dims.rope), d),
        "kv_norm/g": ((dims.kv_lora,), None),
        "kv_b_k/w": ((h, dims.nope, dims.kv_lora), dims.kv_lora),
        "kv_b_v/w": ((h, dims.kv_lora, dims.v), dims.kv_lora),
        "o/w": ((h * dims.v, d), h * dims.v),
    }, layers, dtype)


# -- the block's two forms ----------------------------------------------------------


def regrouped(p: Dict[str, jax.Array], dims: MLADims) -> Dict[str, jax.Array]:
    """``p`` (one block's parameters, or a stack's: any leading axes) as
    both forms read it. ``q_b/w [.., q_lora, H * (nope + rope)]`` becomes
    ``q_b/w_nope [.., q_lora, H * nope]`` and ``q_b/w_rope [.., q_lora, H *
    rope]``, so that each product's result splits into heads on whole lane
    groups and a one-token step reads the weight as it is held (a view of
    the published columns a head at a time made the compiler lay the whole
    weight out anew, every layer of every step). ``kv_b_k/w`` and
    ``kv_b_v/w`` gain their flat forms ``[.., kv_lora, H * nope]`` and
    ``[.., kv_lora, H * v]`` beside them, for the prefill's keys and values
    as ``[b, s, H * 128]``. A copy, so a generator makes it once a request,
    outside its scans and its loop; a ``p`` that has been through here comes
    back as it is."""
    if "q_b/w" not in p:
        return p
    h = dims.heads
    p = dict(p)
    w = p.pop("q_b/w")
    lead = w.shape[:-1]
    w = w.reshape(lead + (h, dims.qk))
    p["q_b/w_nope"] = w[..., :dims.nope].reshape(lead + (h * dims.nope,))
    p["q_b/w_rope"] = w[..., dims.nope:].reshape(lead + (h * dims.rope,))
    k, v = p["kv_b_k/w"], p["kv_b_v/w"]     # [.., H, nope, c], [.., H, c, v]
    lead = k.shape[:-3]
    p["kv_b_k/w_flat"] = jnp.moveaxis(k, -1, -3).reshape(
        lead + (dims.kv_lora, h * dims.nope))
    p["kv_b_v/w_flat"] = jnp.moveaxis(v, -3, -2).reshape(
        lead + (dims.kv_lora, h * dims.v))
    return p


def _record_plan(dims: MLADims, form: str, seq: int, scale: float):
    """One zero-length span in the program's ring for each attention
    traced: its widths and which form it took. ``score_parts``: the widths
    a score's products contract over, ``[nope, rope]`` apart in both forms
    (``[nope + rope]`` would say one head is built; no caller does)."""
    from ..core import profiler

    profiler.record_span(
        "mla.plan", time.time_ns(), 0, heads=dims.heads, q_lora=dims.q_lora,
        kv_lora=dims.kv_lora, rope_dim=dims.rope, nope_dim=dims.nope,
        v_dim=dims.v, form=form, seq=seq, softmax_scale=scale,
        score_parts=[dims.nope, dims.rope])


def _queries(c_q, p, dims: MLADims, positions, freqs, cs_scale):
    """``(q_nope [b, s, H * nope], q_rope [b, s, H * rope])`` of the query
    latents ``c_q [b, s, q_lora]`` (:func:`_query_latents`), heads side by
    side as the two products leave them, the rotary part rotated; ``p`` as
    :func:`regrouped` leaves it."""
    b, s, _ = c_q.shape
    # the flat product as it is, behind a barrier: left free, the compiler
    # moves the views below (a head's 64, a pair's 2) onto the weight, and a
    # step copies every layer's ``[H, rope / 2, 2, q_lora]`` to get them
    q_rope = jax.lax.optimization_barrier(jnp.matmul(c_q, p["q_b/w_rope"]))
    q_rope = rope(q_rope.reshape(b, s, dims.heads, dims.rope), positions,
                  freqs, cs_scale, head_axis=True)
    return (jnp.matmul(c_q, p["q_b/w_nope"]),
            q_rope.reshape(b, s, dims.heads * dims.rope))


def _query_latents(h, p, dims: MLADims):
    """``c_q [b, s, q_lora]`` of the normed input ``h``, after its norm."""
    return rms_norm(jnp.matmul(h, p["q_a/w"]), p["q_norm/g"], dims.eps)


def _latents(h, p, dims: MLADims, positions, freqs, cs_scale):
    """What the cache holds of ``h [b, s, d]``: ``c_kv [b, s, kv_lora]``
    after its norm and ``k_rope [b, s, rope]`` after RoPE."""
    kv = jnp.matmul(h, p["kv_a/w"])
    c_kv = rms_norm(kv[..., :dims.kv_lora], p["kv_norm/g"], dims.eps)
    return c_kv, rope(kv[..., dims.kv_lora:], positions, freqs, cs_scale)


def mla_prefill(x, p, dims: MLADims, y: Yarn):
    """The expanded form over a whole prompt: ``x [b, s, d]`` ->
    ``(x + attention, (c_kv [b, s, kv_lora], k_rope [b, rope, s]))``, the
    pair being this layer's cache entries in the cache's own layout."""
    s = x.shape[1]
    # the rows the kernel pads a call of s to. The latents are padded to
    # them here, 2,112 numbers a token, and the products leave q, k and v
    # that long: padded inside the call they were 20,544 a token, copied
    rows = padded_rows(s)
    positions, freqs = jnp.arange(rows), yarn_frequencies(dims.rope, y)
    scale, cs = softmax_scale(dims, y), yarn_cos_sin_scale(y)
    _record_plan(dims, "expanded", s, scale)
    p = regrouped(p, dims)

    def grown(a):
        return jnp.pad(a, ((0, 0), (0, rows - s), (0, 0)))

    with jax.named_scope("mla"):
        h = rms_norm(x, p["attn_norm/g"], dims.eps)
        c_kv, k_rope = _latents(h, p, dims, positions[:s], freqs, cs)
        q_nope, q_rope = _queries(grown(_query_latents(h, p, dims)), p, dims,
                                  positions, freqs, cs)
        c_long = grown(c_kv)
        o = flash_attention(
            q_nope, jnp.matmul(c_long, p["kv_b_k/w_flat"]),
            jnp.matmul(c_long, p["kv_b_v/w_flat"]), num_heads=dims.heads,
            causal=True, scale=scale, q_rot=q_rope, k_rot=grown(k_rope))
        x = x + jnp.matmul(o[:, :s], p["o/w"])
    return x, (c_kv, k_rope.transpose(0, 2, 1))


def mla_decode(x, p, c_cache, r_cache, index, dims: MLADims, y: Yarn):
    """The absorbed form for one token at position ``index``: ``x [rows,
    1, d]``; this layer's ``c_cache [rows, T, kv_lora]`` and ``r_cache
    [rows, rope, T]`` written in place at ``index`` and read as stored;
    positions ``<= index`` attended. Returns ``(x + attention, c_cache,
    r_cache)``."""
    rows, T = x.shape[0], c_cache.shape[1]
    positions, freqs = index[None], yarn_frequencies(dims.rope, y)
    scale, cs = softmax_scale(dims, y), yarn_cos_sin_scale(y)
    _record_plan(dims, "absorbed", T, scale)
    p = regrouped(p, dims)
    with jax.named_scope("mla"):
        h = rms_norm(x, p["attn_norm/g"], dims.eps)
        q_nope, q_rope = (
            q.reshape(rows, dims.heads, -1)
            for q in _queries(_query_latents(h, p, dims), p, dims, positions,
                              freqs, cs))
        c_new, r_new = _latents(h, p, dims, positions, freqs, cs)
        c_cache = jax.lax.dynamic_update_slice(
            c_cache, c_new.astype(c_cache.dtype), (0, index, 0))
        r_cache = jax.lax.dynamic_update_slice(
            r_cache, r_new.transpose(0, 2, 1).astype(r_cache.dtype),
            (0, 0, index))
        q_lat = jnp.einsum("rhn,hnc->rhc", q_nope, p["kv_b_k/w"])
        s = (jnp.einsum("rhc,rtc->rht", q_lat, c_cache,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("rhe,ret->rht", q_rope, r_cache,
                          preferred_element_type=jnp.float32)) * scale
        live = jnp.arange(T)[None, None, :] <= index
        probs = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
        o_lat = jnp.einsum("rht,rtc->rhc", probs.astype(x.dtype), c_cache)
        o = jnp.einsum("rhc,hcv->rhv", o_lat, p["kv_b_v/w"])
        x = x + jnp.matmul(o.reshape(rows, 1, dims.heads * dims.v), p["o/w"])
    return x, c_cache, r_cache


__all__ = ["MLADims", "Yarn", "mla_decode", "mla_params", "mla_prefill",
           "regrouped", "softmax_scale", "yarn_cos_sin_scale",
           "yarn_frequencies"]
