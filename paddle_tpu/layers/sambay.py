"""The mixers of a SambaY decoder-hybrid-decoder (arXiv:2507.06607,
``model_type: phi4flash``): a Mamba-1 mixer, differential attention over a
sliding window or over everything, the gated memory unit, and the
cross-attention that computes a query only. A sibling of ``layers/sala.py``
and ``layers/retention.py`` and written as they are: pure functions of
``(activation, layer_params, carried state)``, parameters created by
``*_params`` under ``mixer/`` in the caller's scope.

Every block is ``x + Mixer(LayerNorm(x))`` (``layers/blocks.layer_norm``:
scale and bias, statistics in float32). What a mixer carries:

- **Mamba** (:func:`mamba_prefill`, :func:`mamba_decode`): the last ``d_conv
  - 1`` inputs of the causal convolution ``[rows, 3, d_inner]`` and the
  float32 state ``[rows, d_state, d_inner]`` (``ops/selective_scan.py``).
  Both also return ``y`` before the gate: the last Mamba layer's is the
  *memory* the gated memory units read.
- **window attention** (:func:`window_prefill`, :func:`window_decode`): the
  last ``window`` keys and values, ``[rows, window, kv_heads * head_dim]``
  each, lane-dense as a projection leaves them. A prefill holds them in
  order of position (a piece attends to them and to its own keys through
  ``flash_attention(window=)``); ``layers/kv_ring.ring_of`` turns that into
  the ring the steps write, a key at slot ``position % window``.
- **full attention** (:func:`shared_kv`, :func:`shared_decode`): its keys
  and values ``[rows, max_len, kv_heads * head_dim]``, which its own query
  and every cross layer's read (:func:`cross_decode`): stored once.
- the **gated memory unit** (:func:`gmu`) and the cross layers carry
  nothing.

Differential attention (arXiv:2410.05258) pairs the heads in order: query
pair ``p`` is heads ``(2p, 2p + 1)``, it reads key pair ``c = p // 2`` (key
heads ``2c, 2c + 1``) and that pair's two values side by side, 128 wide::

    o_p = (softmax(q1 k1^T / sqrt(hd)) - lambda softmax(q2 k2^T / sqrt(hd))) [v1; v2]
    o_p <- RMSNorm_128(o_p) * (1 - lambda_init)

so every one of the 40 query heads is an ordinary head with 64-wide scores
and a 128-wide value, which is what the flash kernel is given (``[b, h, s,
d]`` with ``dv = 2 d``), and the pair's difference is taken of its outputs.
A one-token step reads a cache in place, heads separated on the MXU as
``layers/stacked._cache_attention`` separates them: the query row laid out
block-diagonally against the cache's lanes, the difference taken of the
probabilities (one value product a pair).
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import initializer as init
from ..framework import LayerHelper
from ..ops.flash_attention import flash_attention
from ..ops.selective_scan import mamba_step, selective_scan
from . import conv_tail, kv_ring
from .blocks import gated_ffn_params, layer_norm, params
from .stacked import NEG_INF


class SambaDims(NamedTuple):
    """One decoder's widths (published key names in brackets)."""
    d_model: int            # hidden_size
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int           # hidden_size // num_attention_heads
    window: int             # sliding_window
    d_inner: int            # mamba_expand * hidden_size
    d_state: int            # mamba_d_state
    d_conv: int             # mamba_d_conv
    dt_rank: int            # mamba_dt_rank ("auto": ceil(hidden_size / 16))
    eps: float              # layer_norm_eps

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim


def lambda_init(layer: int) -> float:
    """``0.8 - 0.6 exp(-0.3 l)``, ``l`` the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# -- parameters ------------------------------------------------------------------


def _create(shapes, dtype) -> Dict[str, jax.Array]:
    """``layers/blocks.params`` under ``mixer/`` in the caller's scope."""
    return params(LayerHelper("mixer", name="mixer"), shapes, None, dtype)


def _norm(d):
    return {"norm/g": ((d,), 1.0), "norm/b": ((d,), 0.0)}


def mamba_params(dims: SambaDims, dtype) -> Dict[str, jax.Array]:
    """``a_log`` and what goes with the state are float32 and lie ``[d_state,
    d_inner]``, channels on lanes (the published ``A_log`` is its
    transpose). Created as Mamba is published to start: ``A = -(1 ..
    d_state)`` a channel, a step of 0.01, ``D = 1``, taps U(-1/2, 1/2)."""
    d, di, n, r = dims.d_model, dims.d_inner, dims.d_state, dims.dt_rank
    a_log = np.broadcast_to(np.log(np.arange(1.0, n + 1))[:, None], (n, di))
    return _create({
        **_norm(d), "in/w": ((d, 2 * di), d),
        "conv/w": ((dims.d_conv, di), init.Uniform(-0.5, 0.5)),
        "conv/b": ((di,), 0.0), "x/w": ((di, r + 2 * n), di),
        "dt/w": ((r, di), r), "dt/b": ((di,), math.log(math.expm1(0.01))),
        "a_log": ((n, di), init.NumpyArrayInitializer(a_log)),
        "d": ((di,), 1.0), "out/w": ((di, d), di)}, dtype)


def _diff_params(dims: SambaDims):
    wide = dims.heads * dims.head_dim
    return {"lambda": ((4, dims.head_dim), 0.0),        # lq1, lk1, lq2, lk2
            "sub_norm/g": ((2 * dims.head_dim,), 1.0),
            "o/w": ((wide, dims.d_model), wide), "o/b": ((dims.d_model,), 0.0)}


def attention_params(dims: SambaDims, dtype) -> Dict[str, jax.Array]:
    """A self-attention layer's (window or full): q, k and v one matrix,
    q's columns first."""
    d, out = dims.d_model, dims.heads * dims.head_dim + 2 * dims.kv_width
    return _create({**_norm(d), "qkv/w": ((d, out), d), "qkv/b": ((out,), 0.0),
                    **_diff_params(dims)}, dtype)


def cross_params(dims: SambaDims, dtype) -> Dict[str, jax.Array]:
    d, wide = dims.d_model, dims.heads * dims.head_dim
    return _create({**_norm(d), "q/w": ((d, wide), d), "q/b": ((wide,), 0.0),
                    **_diff_params(dims)}, dtype)


def gmu_params(dims: SambaDims, dtype) -> Dict[str, jax.Array]:
    d, di = dims.d_model, dims.d_inner
    return _create({**_norm(d), "in/w": ((d, di), d), "out/w": ((di, d), di)},
                   dtype)


def ffn_params(dims: SambaDims, width: int, dtype) -> Dict[str, jax.Array]:
    """The published ``gate_up_proj`` as its two halves."""
    p = gated_ffn_params(dims.d_model, width, dtype)
    p["ffn_norm/b"] = LayerHelper("ffn", name="ffn").create_parameter(
        "ffn_norm/b", (dims.d_model,), jnp.float32,
        initializer=init.Constant(0.0))
    return p


# -- Mamba ---------------------------------------------------------------------------


def _mamba_inputs(x, p, dims: SambaDims, tail):
    """``x [b, s, d]`` and the last ``d_conv - 1`` inputs before it -> what
    the recurrence is given, float32: ``(c [b, s, d_inner]`` after the
    convolution and its SiLU, ``delta``, ``B, C [b, s, d_state]``, the gate
    ``z`` in ``x``'s dtype, the new tail)``."""
    f32 = jnp.float32
    di, n, r = dims.d_inner, dims.d_state, dims.dt_rank
    u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
    az = jnp.matmul(u, p["in/w"])
    c, tail = conv_tail.conv_silu(az[..., :di], tail, p["conv/w"],
                                  p["conv/b"])
    low = jnp.matmul(c.astype(x.dtype), p["x/w"], preferred_element_type=f32)
    delta = jax.nn.softplus(jnp.matmul(
        low[..., :r].astype(x.dtype), p["dt/w"], preferred_element_type=f32)
        + p["dt/b"])
    return c, delta, low[..., r:r + n], low[..., r + n:], az[..., di:], tail


def _mamba_out(x, p, y, c, z):
    """``y`` with the skip, and the block's output: ``(x + W_out(y *
    silu(z)), y)``."""
    y = y + p["d"] * c
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return x + jnp.matmul(gated, p["out/w"]), y.astype(x.dtype)


def mamba_prefill(x, p, dims: SambaDims, carried):
    """A piece ``x [b, s, d]`` through the scan from ``carried = (tail,
    state)``. Returns ``(x + mixer, carried, y [b, s, d_inner], given)``,
    ``given = (delta, u [b, s, d_inner], B [b, s, d_state])`` what the
    recurrence was handed, for a caller that audits the state."""
    tail, state = carried
    with jax.named_scope("mamba"):
        c, delta, b, cm, z, tail = _mamba_inputs(x, p, dims, tail)
        u = delta * c
        y, state = selective_scan(delta, u, b, cm, -jnp.exp(p["a_log"]), state)
        x, y = _mamba_out(x, p, y, c, z)
    return x, (tail, state), y, (delta, u, b)


def mamba_decode(x, p, dims: SambaDims, carried, write):
    """One token ``x [rows, 1, d]``. Where ``write`` (a traced bool) is
    false the token leaves the tail and the state as they were. Returns
    what :func:`mamba_prefill` does, ``s = 1``."""
    tail, state = carried
    with jax.named_scope("mamba"):
        c, delta, b, cm, z, new_tail = _mamba_inputs(x, p, dims, tail)
        u = delta * c
        y, new_state = mamba_step(delta[:, 0], u[:, 0], b[:, 0], cm[:, 0],
                                  -jnp.exp(p["a_log"]), state)
        x, y = _mamba_out(x, p, y[:, None, :], c, z)
    return x, (jnp.where(write, new_tail, tail),
               jnp.where(write, new_state, state)), y, (delta, u, b)


# -- the gated memory unit ----------------------------------------------------------


def gmu(x, p, dims: SambaDims, memory):
    """``x + W_2(memory * silu(W_1 LayerNorm(x)))``; ``memory [.., d_inner]``
    is the same tokens' ``y`` of the last Mamba layer."""
    with jax.named_scope("gmu"):
        u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
        gate = jax.nn.silu(jnp.matmul(u, p["in/w"]).astype(jnp.float32))
        return x + jnp.matmul((memory.astype(jnp.float32) * gate
                               ).astype(x.dtype), p["out/w"])


# -- differential attention ---------------------------------------------------------


def _lambda(p, layer: int):
    lam = p["lambda"]
    return (jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3]))
            + lambda_init(layer))


def _attn_out(x, p, o, dims: SambaDims, layer: int):
    """``o [.., pairs, 2 hd]`` float32, a pair's difference -> the block's
    output: the norm over 128, ``(1 - lambda_init)``, ``W_o`` and its
    bias."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + dims.eps)
    o = o * p["sub_norm/g"] * (1.0 - lambda_init(layer))
    o = o.reshape(o.shape[:-2] + (-1,)).astype(x.dtype)
    return x + _biased(o, p["o/w"], p["o/b"])


def _biased(u, w, b):
    """``W u + b`` in ``u``'s dtype: the product's result taken in that
    dtype (a one-row step's product with a float32 result is walked in
    narrow strided strips, PERF.md section 6, PR 39), the float32 bias added
    in float32."""
    return (jnp.matmul(u, w).astype(jnp.float32) + b).astype(u.dtype)


def _qkv(u, p, dims: SambaDims):
    wide = dims.heads * dims.head_dim
    qkv = _biased(u, p["qkv/w"], p["qkv/b"])
    return (qkv[..., :wide], qkv[..., wide:wide + dims.kv_width],
            qkv[..., wide + dims.kv_width:])


def _query_blocks(q, dims: SambaDims):
    """One token's queries ``[rows, heads * hd]`` against a lane-dense cache
    of keys: ``[rows, kv_heads * hd, heads]``, column ``h`` holding head
    ``h``'s query in the lanes of the key head it reads (``2 (h // 4) + h %
    2``) and zeros elsewhere, so that ``cache @ that`` is every head's
    scores."""
    hd, h = dims.head_dim, jnp.arange(dims.heads)
    lane = jnp.arange(dims.kv_width)[:, None] // hd
    reads = (lane == (2 * (h // 4) + h % 2)[None, :]).astype(q.dtype)
    q = q.reshape(q.shape[0], dims.heads, hd).transpose(0, 2, 1)
    return jnp.tile(q, (1, dims.kv_heads, 1)) * reads


def cache_attention(q, k_cache, v_cache, live, dims: SambaDims, lam):
    """One token's differential attention against a cache read in place:
    ``q [rows, heads * hd]``, caches ``[rows, T, kv_heads * hd]``, ``live
    [T]`` the slots attended. Returns ``[rows, pairs, 2 hd]`` float32."""
    rows, pairs, hd = q.shape[0], dims.heads // 2, dims.head_dim
    logits = jnp.einsum("rtc,rch->rth", k_cache, _query_blocks(q, dims),
                        preferred_element_type=jnp.float32) * hd ** -0.5
    probs = jax.nn.softmax(jnp.where(live[None, :, None], logits, NEG_INF),
                           axis=1).reshape(rows, -1, pairs, 2)
    diff = (probs[..., 0] - lam * probs[..., 1]).astype(q.dtype)
    o = jnp.einsum("rtp,rtc->rpc", diff, v_cache,
                   preferred_element_type=jnp.float32)
    # pair p reads the values of key pair p // 2: its 128 lanes of the row
    o = o.reshape(rows, pairs // 2, 2, pairs // 2, 2 * hd)
    own = jnp.eye(pairs // 2, dtype=o.dtype)[None, :, None, :, None]
    return jnp.sum(o * own, axis=3).reshape(rows, pairs, 2 * hd)


def _flash_window(q, k, v, valid_from, dims: SambaDims, lam):
    """A piece's differential attention over a window through the flash
    kernel: ``q [b, s, heads * hd]``, ``k, v [b, window + s, kv_heads *
    hd]`` (what the window held before the piece, then the piece's own:
    ``layers/kv_ring.joined``); keys before index ``valid_from`` (traced)
    hold nothing yet. Returns
    ``[b, s, pairs, 2 hd]`` float32."""
    b, s, _ = q.shape
    hd, h, sk = dims.head_dim, dims.heads, k.shape[1]
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    # a key head is read by two query heads, a value pair by four
    k = jnp.broadcast_to(k.reshape(b, sk, h // 4, 1, 2, hd),
                         (b, sk, h // 4, 2, 2, hd))
    v = jnp.broadcast_to(v.reshape(b, sk, h // 4, 1, 2 * hd),
                         (b, sk, h // 4, 4, 2 * hd))
    bias = kv_ring.empty_bias(sk, valid_from)
    o = flash_attention(
        q, k.reshape(b, sk, h, hd).transpose(0, 2, 1, 3),
        v.reshape(b, sk, h, 2 * hd).transpose(0, 2, 1, 3), causal=True,
        key_bias=jnp.broadcast_to(bias[None], (b, sk)), window=dims.window)
    o = o.astype(jnp.float32).reshape(b, h // 2, 2, s, 2 * hd)
    return (o[:, :, 0] - lam * o[:, :, 1]).transpose(0, 2, 1, 3)


def window_prefill(x, p, dims: SambaDims, held, p0, layer: int):
    """A piece ``x [b, s, d]`` at positions ``p0 ..`` (traced) over what the
    window ``held = (k, v) [b, window, kv_heads * hd]`` had of the
    positions before, in order. Returns ``(x + mixer, held)``."""
    with jax.named_scope("swa"):
        u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
        q, k, v = _qkv(u, p, dims)
        k, v = kv_ring.joined(held[0], k), kv_ring.joined(held[1], v)
        o = _flash_window(q, k, v, dims.window - p0, dims, _lambda(p, layer))
        x = _attn_out(x, p, o, dims, layer)
    return x, (kv_ring.kept(k, dims.window), kv_ring.kept(v, dims.window))


def window_decode(x, p, dims: SambaDims, ring, index, layer: int):
    """One token at position ``index`` (traced): ``x [rows, 1, d]``; its key
    and value go to slot ``index % window`` of ``ring``, in place, over the
    key that has just left the window; slots ``<= index`` are attended (all
    of them from position ``window - 1`` on). Returns ``(x + mixer,
    ring)``."""
    with jax.named_scope("swa"):
        u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
        q, k, v = _qkv(u[:, 0], p, dims)
        slot = index % dims.window
        ring = (kv_ring.write(ring[0], k, slot),
                kv_ring.write(ring[1], v, slot))
        o = cache_attention(q, *ring, kv_ring.live(dims.window, index), dims,
                            _lambda(p, layer))
        x = _attn_out(x, p, o[:, None], dims, layer)
    return x, ring


def shared_kv(x, p, dims: SambaDims, shared, p0):
    """What the full-attention layer keeps of a piece ``x [b, s, d]`` at
    positions ``p0 ..``: its keys and values, written into ``shared = (K*,
    V*) [b, max_len, kv_heads * hd]``. The piece's queries are not computed:
    nothing reads this layer's output at a prompt position but the last
    (:func:`shared_decode` computes that one)."""
    with jax.named_scope("full_attn"):
        u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
        wide = dims.heads * dims.head_dim
        kv = _biased(u, p["qkv/w"][:, wide:], p["qkv/b"][wide:])
        put = lambda cache, new: jax.lax.dynamic_update_slice_in_dim(
            cache, new, p0, axis=1)
        return (put(shared[0], kv[..., :dims.kv_width]),
                put(shared[1], kv[..., dims.kv_width:]))


def shared_decode(x, p, dims: SambaDims, shared, index, layer: int):
    """The full-attention layer for one token at position ``index``: its key
    and value written into ``shared`` at ``index``, positions ``<= index``
    attended. Returns ``(x + mixer, shared)``."""
    with jax.named_scope("full_attn"):
        u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
        q, k, v = _qkv(u[:, 0], p, dims)
        shared = (kv_ring.write(shared[0], k, index),
                  kv_ring.write(shared[1], v, index))
        o = cache_attention(q, *shared,
                            jnp.arange(shared[0].shape[1]) <= index, dims,
                            _lambda(p, layer))
        x = _attn_out(x, p, o[:, None], dims, layer)
    return x, shared


def cross_decode(x, p, dims: SambaDims, shared, index, layer: int):
    """A cross layer for one token at position ``index``: a query of its
    own against ``shared``, positions ``<= index``; no key, no value, and
    nothing written."""
    with jax.named_scope("cross_attn"):
        u = layer_norm(x, p["norm/g"], p["norm/b"], dims.eps)
        q = _biased(u[:, 0], p["q/w"], p["q/b"])
        o = cache_attention(q, *shared,
                            jnp.arange(shared[0].shape[1]) <= index, dims,
                            _lambda(p, layer))
        return _attn_out(x, p, o[:, None], dims, layer)


__all__ = ["SambaDims", "attention_params", "cache_attention", "cross_decode",
           "cross_params", "ffn_params", "gmu", "gmu_params",
           "lambda_init", "mamba_decode", "mamba_params",
           "mamba_prefill", "shared_decode", "shared_kv",
           "window_decode", "window_prefill"]
