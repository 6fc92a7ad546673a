"""Gated grouped-query attention (``model_type: afmoe``, Arcee Trinity): the
mixer of a decoder whose layers alternate a sliding window with full
attention. A sibling of ``layers/sambay.py`` and ``layers/latent.py`` and
written as they are: pure functions of ``(activation, layer_params, carried
state)``, parameters created by :func:`attention_params` under ``mixer/`` in
the caller's scope, the norms and the rotary map from ``layers/blocks.py``.

One layer, ``x [.., d]``::

    a = rms(x; g_in)
    q = rms_head(a W_q; g_q)   [.., heads, hd]      k = rms_head(a W_k; g_k)   [.., kv_heads, hd]
    v = a W_v                  [.., kv_heads, hd]   g = a W_g                  [.., heads * hd]
    window layer: q, k rotated (all hd dims, pairs (2i, 2i + 1)); query i sees keys j, 0 <= i - j < window
    full layer:   not rotated at all;                             query i sees keys j <= i
    o = softmax(q_h . k_(h // group) / sqrt(hd)) v_(h // group)
    x + rms((o * sigmoid(g)) W_o; g_post)                        # the norm AFTER the mixer

``group = heads // kv_heads`` query heads read one key/value head. The
prefill gives the flash kernel the key heads as they are (``kv_heads=``): no
head is repeated in HBM. What a layer carries, lane-dense ``[rows, T,
kv_heads * hd]`` as the projections leave them:

- a **window layer** the last ``window`` keys and values (``layers/kv_ring.py``):
  in order of position through the prefill (:func:`window_prefill`: a piece
  attends to them and to its own through ``flash_attention(window=)``, whose
  walk skips the key tiles behind the window), as a ring in the steps
  (:func:`window_decode`);
- a **full layer** every key and value, ``T`` the request's length padded to
  the kernel's key blocks (``ops/flash_attention.padded_keys``):
  :func:`full_prefill` writes a piece's into it and hands the kernel the
  cache whole with the piece's position (``q_offset=``), :func:`full_decode`
  writes one row.

A one-token step reads a cache in place, heads separated on the MXU as
``layers/stacked._cache_attention`` separates them (:func:`cache_attention`:
the query row laid out block-diagonally against the cache's lanes, ``group``
columns to a key head).

Every attention traced leaves one ``attn.plan`` span: ``kind``, ``heads``,
``kv_heads``, ``head_dim``, ``window``, ``rotary``, ``form`` and the ``keys``
a call reads.
"""

from __future__ import annotations

import time
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..framework import LayerHelper
from ..ops.flash_attention import flash_attention
from . import blocks, kv_ring
from .blocks import params, rms_norm, rope
from .stacked import NEG_INF

WINDOW, FULL = "window", "full"


class GQADims(NamedTuple):
    """One decoder's attention widths (published key names in brackets)."""
    d_model: int            # hidden_size
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int           # head_dim
    window: int             # sliding_window
    theta: float            # rope_theta
    eps: float              # rms_norm_eps

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads


def attention_params(dims: GQADims, dtype) -> Dict[str, jax.Array]:
    """One attention layer's, window or full: four projections of the
    normed input (no bias), the two per-head norms, the output projection
    and the norm after it."""
    d, qw, kvw, hd = dims.d_model, dims.q_width, dims.kv_width, dims.head_dim
    return params(LayerHelper("mixer", name="mixer"), {
        "attn_norm/g": ((d,), None), "q/w": ((d, qw), d), "k/w": ((d, kvw), d),
        "v/w": ((d, kvw), d), "gate/w": ((d, qw), d), "q_norm/g": ((hd,), None),
        "k_norm/g": ((hd,), None), "o/w": ((qw, d), qw),
        "post_norm/g": ((d,), None)}, None, dtype)


def _record_plan(kind: str, dims: GQADims, form: str, keys: int):
    from ..core import profiler

    profiler.record_span(
        "attn.plan", time.time_ns(), 0, kind=kind, heads=dims.heads,
        kv_heads=dims.kv_heads, head_dim=dims.head_dim, window=dims.window,
        rotary=kind == WINDOW, form=form, keys=keys)


def _project(x, p, dims: GQADims, positions, rotary: bool):
    """``x [b, s, d]`` at ``positions [s]`` -> ``(q [b, s, heads * hd], k, v
    [b, s, kv_heads * hd], gate [b, s, heads * hd])``, q and k normed a head
    and, with ``rotary``, rotated."""
    b, s, _ = x.shape
    hd = dims.head_dim
    a = rms_norm(x, p["attn_norm/g"], dims.eps)
    freqs = dims.theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)

    def heads_of(w, g, n):
        y = rms_norm(jnp.matmul(a, w).reshape(b, s, n, hd), g, dims.eps)
        if rotary:
            y = rope(y, positions, freqs, head_axis=True)
        return y.reshape(b, s, n * hd)

    return (heads_of(p["q/w"], p["q_norm/g"], dims.heads),
            heads_of(p["k/w"], p["k_norm/g"], dims.kv_heads),
            jnp.matmul(a, p["v/w"]), jnp.matmul(a, p["gate/w"]))


def _out(x, p, o, gate, dims: GQADims):
    """``x + rms((o * sigmoid(gate)) W_o; g_post)``: the gate in float32,
    rounded once."""
    gated = (o.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
    return x + rms_norm(jnp.matmul(gated, p["o/w"]), p["post_norm/g"],
                        dims.eps)


# -- a piece of a prompt -------------------------------------------------------------


def window_prefill(x, p, dims: GQADims, held, p0):
    """A piece ``x [b, s, d]`` at positions ``p0 ..`` (traced) over what the
    window ``held = (k, v) [b, window, kv_heads * hd]`` had of the positions
    before, in order. Returns ``(x + mixer, held)``."""
    b, s, _ = x.shape
    with jax.named_scope("swa"):
        q, k, v, gate = _project(x, p, dims, p0 + jnp.arange(s), True)
        k, v = kv_ring.joined(held[0], k), kv_ring.joined(held[1], v)
        _record_plan(WINDOW, dims, "prefill", k.shape[1])
        bias = kv_ring.empty_bias(k.shape[1], dims.window - p0)
        o = flash_attention(
            q, k, v, causal=True, num_heads=dims.heads, kv_heads=dims.kv_heads,
            window=dims.window,
            key_bias=jnp.broadcast_to(bias[None], (b, k.shape[1])))
        x = _out(x, p, o, gate, dims)
    return x, (kv_ring.kept(k, dims.window), kv_ring.kept(v, dims.window))


def full_prefill(x, p, dims: GQADims, cache, p0):
    """A piece ``x [b, s, d]`` at positions ``p0 ..`` (traced): its keys and
    values written into ``cache = (K, V) [b, T, kv_heads * hd]`` there, its
    queries against the cache whole (the kernel walks the keys up to each
    query's own). Returns ``(x + mixer, cache)``."""
    with jax.named_scope("full_attn"):
        q, k, v, gate = _project(x, p, dims, None, False)
        put = lambda held, new: jax.lax.dynamic_update_slice_in_dim(
            held, new, p0, axis=1)
        cache = (put(cache[0], k), put(cache[1], v))
        _record_plan(FULL, dims, "prefill", cache[0].shape[1])
        o = flash_attention(q, *cache, causal=True, num_heads=dims.heads,
                            kv_heads=dims.kv_heads, q_offset=p0)
        x = _out(x, p, o, gate, dims)
    return x, cache


# -- one token -----------------------------------------------------------------------


def cache_attention(q, k_cache, v_cache, live, dims: GQADims, scale=None):
    """One token's attention against a cache read in place: ``q [rows,
    heads * hd]``, caches ``[rows, T, kv_heads * hd]``, ``live [T]`` the
    slots attended; ``scale`` the softmax scale (None: ``hd ** -0.5``).
    Returns ``[rows, heads * hd]`` float32."""
    rows, hd, kvh, grp = q.shape[0], dims.head_dim, dims.kv_heads, dims.group
    # [rows, kv_heads * hd, heads]: column h holds head h's query in the
    # lanes of the key head it reads, zeros elsewhere, so that ``cache @
    # that`` is every head's scores
    lane = jnp.arange(dims.kv_width)[:, None] // hd
    reads = (lane == (jnp.arange(dims.heads) // grp)[None, :]).astype(q.dtype)
    blocks = jnp.tile(q.reshape(rows, dims.heads, hd).transpose(0, 2, 1),
                      (1, kvh, 1)) * reads
    logits = jnp.einsum("rtc,rch->rth", k_cache, blocks,
                        preferred_element_type=jnp.float32) * (
                            hd ** -0.5 if scale is None else scale)
    probs = jax.nn.softmax(jnp.where(live[None, :, None], logits, NEG_INF),
                           axis=1).astype(q.dtype)
    o = jnp.einsum("rth,rtc->rhc", probs, v_cache,
                   preferred_element_type=jnp.float32)
    # head h reads the values of key head h // group: its 128 lanes of the row
    o = o.reshape(rows, kvh, grp, kvh, hd)
    own = jnp.eye(kvh, dtype=o.dtype)[None, :, None, :, None]
    return jnp.sum(o * own, axis=3).reshape(rows, dims.q_width)


def _decode(x, p, dims: GQADims, cache, index, slot, live, kind: str):
    q, k, v, gate = _project(x, p, dims, index[None], kind == WINDOW)
    cache = (kv_ring.write(cache[0], k[:, 0], slot),
             kv_ring.write(cache[1], v[:, 0], slot))
    _record_plan(kind, dims, "step", cache[0].shape[1])
    o = cache_attention(q[:, 0], *cache, live, dims)
    return _out(x, p, o[:, None], gate, dims), cache


def window_decode(x, p, dims: GQADims, ring, index):
    """One token at position ``index`` (traced): ``x [rows, 1, d]``; its key
    and value go to slot ``index % window`` of ``ring``, in place, over the
    key that has just left the window. Returns ``(x + mixer, ring)``."""
    with jax.named_scope("swa"):
        return _decode(x, p, dims, ring, index, index % dims.window,
                       kv_ring.live(dims.window, index), WINDOW)


def full_decode(x, p, dims: GQADims, cache, index):
    """One token at position ``index`` (traced): its key and value written
    into ``cache`` at ``index``, positions ``<= index`` attended. Returns
    ``(x + mixer, cache)``."""
    with jax.named_scope("full_attn"):
        return _decode(x, p, dims, cache, index, index,
                       jnp.arange(cache[0].shape[1]) <= index, FULL)


# -- plain grouped-query attention (no gate, no head norm, no rotation) --------------
#
# ``model_type: granitemoehybrid``'s attention layers: four projections of
# the normed input, a softmax scale of the model's own (``attention_multiplier``,
# not ``1 / sqrt(hd)``), no positions at all, every key seen, and the output
# summed into the stream times ``residual``. The cache is a full layer's.


def plain_params(dims: GQADims, dtype) -> Dict[str, jax.Array]:
    d, qw, kvw = dims.d_model, dims.q_width, dims.kv_width
    return params(LayerHelper("mixer", name="mixer"), {
        "attn_norm/g": ((d,), None), "q/w": ((d, qw), d), "k/w": ((d, kvw), d),
        "v/w": ((d, kvw), d), "o/w": ((qw, d), qw)}, None, dtype)


def _plain_project(x, p, dims: GQADims):
    a = rms_norm(x, p["attn_norm/g"], dims.eps)
    return (jnp.matmul(a, p["q/w"]), jnp.matmul(a, p["k/w"]),
            jnp.matmul(a, p["v/w"]))


def plain_prefill(x, p, dims: GQADims, cache, p0, scale: float,
                  residual: float):
    """:func:`full_prefill` for the plain layer: ``x + residual * W_o
    softmax(scale q k^T) v``, the piece's keys and values written into
    ``cache`` at ``p0`` (traced) and the cache handed to the kernel whole."""
    with jax.named_scope("attn"):
        q, k, v = _plain_project(x, p, dims)
        put = lambda held, new: jax.lax.dynamic_update_slice_in_dim(
            held, new, p0, axis=1)
        cache = (put(cache[0], k), put(cache[1], v))
        _record_plan(FULL, dims, "prefill", cache[0].shape[1])
        o = flash_attention(q, *cache, causal=True, num_heads=dims.heads,
                            kv_heads=dims.kv_heads, q_offset=p0, scale=scale)
        x = blocks.residual(x, jnp.matmul(o, p["o/w"]), residual)
    return x, cache


def plain_decode(x, p, dims: GQADims, cache, index, scale: float,
                 residual: float):
    """:func:`full_decode` for the plain layer: one token at position
    ``index`` (traced), its key and value written into ``cache`` there."""
    with jax.named_scope("attn"):
        q, k, v = _plain_project(x, p, dims)
        cache = (kv_ring.write(cache[0], k[:, 0], index),
                 kv_ring.write(cache[1], v[:, 0], index))
        _record_plan(FULL, dims, "step", cache[0].shape[1])
        o = cache_attention(q[:, 0], *cache,
                            jnp.arange(cache[0].shape[1]) <= index, dims, scale)
        x = blocks.residual(x, jnp.matmul(o.astype(x.dtype)[:, None], p["o/w"]),
                            residual)
    return x, cache


__all__ = ["FULL", "GQADims", "WINDOW", "attention_params", "cache_attention",
           "full_decode", "full_prefill", "plain_decode", "plain_params",
           "plain_prefill", "window_decode", "window_prefill"]
