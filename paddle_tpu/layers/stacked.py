"""Stacked transformer blocks — the pipeline-parallel layer representation.

Gap-fill component (SURVEY §2.2: PP absent in the reference; the closest
machinery is the multi-device SSA replication of
framework/details/multi_devices_graph_pass.cc, which replicates ops per
device — here we *partition layers* per device instead).

TPU-native design: per-layer parameters live STACKED on a leading
``[num_layers, ...]`` axis, created once through the normal LayerHelper
scope (so save/load, sharding rules, and optimizers see ordinary named
params). The stack is applied either

- sequentially with ``lax.scan`` (single chip, or dp/fsdp meshes where
  GSPMD partitions the scanned matmuls; under a ``tp`` axis the scan runs
  per shard and the block's exchanges go under its own matmuls, see
  :func:`apply_stacked`), or
- pipelined with ``parallel.pipeline.pipeline_apply`` when the Trainer
  has entered :func:`framework.pipeline_mode` (``DistStrategy.pp_microbatches``),
  each pp rank owning a contiguous span of layers.

Blocks are pure functions of ``(activation, layer_params, extra)`` — no
LayerHelper calls inside, so they trace safely under scan and shard_map.
Dropout IS supported on the scan path: the naive scan-traced rng would
reuse one key across every layer (the per-call counter is a Python int
fixed at trace time), so ``apply_stacked`` folds the traced layer index
into the ambient rng stream per iteration (:func:`framework.rng_fold`),
giving each layer independent masks at the same four sites as the
unrolled transformer layer (attention softmax, two residuals, ffn
inner). The pipeline path supports dropout too: a per-step key is
threaded into the schedule and folded per (layer, microbatch,
data-shard) inside the shard_map body (parallel/pipeline.py module doc
covers the tp-axis caveat).
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.errors import enforce
from ..framework import (LayerHelper, active_mesh, cast_compute,
                         in_training as _in_training, maybe_remat,
                         pipeline_config, remat_enabled, rng_fold, sp_config)
from .. import initializer as init
from ..parallel.collective_matmul import (BatchSharded, gather_matmul,
                                          matmul_scatter, ring_order)
from .attention import flash_applies, flash_sdpa, tp_partitioned

NEG_INF = -1e9


class StackedInit:
    """Apply a base initializer per layer over the leading stack axis, so
    a ``[L, d, k]`` leaf gets L independent ``[d, k]`` inits (fan-in/out
    computed per layer, matching the unstacked model exactly)."""

    def __init__(self, base):
        self.base = base

    def __call__(self, key, shape, dtype):
        keys = jax.random.split(key, shape[0])
        return jnp.stack([self.base(k, shape[1:], dtype) for k in keys])


@jax.named_scope("ln")
def _ln(x, scale, bias, eps: float = 1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return out * scale + bias


def _drop(x, rate: float):
    """Residual/inner dropout (upscale_in_train, matching the unrolled
    transformer layer); no-op at rate 0 or outside training."""
    if rate == 0.0:
        return x
    from .nn import dropout
    return dropout(x, rate, dropout_implementation="upscale_in_train")


def _sdpa(q, k, v, key_bias, causal: bool, use_flash: bool, sp_cfg=None,
          dropout_rate: float = 0.0):
    """[b,h,s,hd] attention with an additive [b,s_k] key bias. With an
    active sequence-parallel context, self-attention runs as ring
    attention over the mesh's sp axis. The layout comes from the sp
    context ("natural" unless the MODEL set "zigzag" after permuting its
    own activations, as models/gpt.py does) — natural-order callers get
    the numerically-safe per-call gathers, never a silent mismatch."""
    if sp_cfg is not None:
        enforce(key_bias is None,
                "sequence-parallel attention does not take a padding bias "
                "(pack full sequences; pad-free is the long-context contract)")
        enforce(dropout_rate == 0.0 or not _in_training(),
                "sequence-parallel attention has no softmax-dropout site "
                "(ring/ulysses kernels); train sp stacks with dropout 0")
        if sp_cfg.get("impl", "ring") == "ulysses":
            from ..parallel.ulysses import ulysses_attention

            def inner(qh, kh, vh, caus):
                if use_flash:
                    from ..ops.flash_attention import flash_attention
                    return flash_attention(qh, kh, vh, causal=caus)
                return _sdpa(qh, kh, vh, None, caus, False)

            return ulysses_attention(q, k, v, sp_cfg["mesh"],
                                     axis_name=sp_cfg["axis"], causal=causal,
                                     attn_fn=inner)
        from ..parallel.ring_attention import ring_attention
        layout = sp_cfg.get("layout", "natural")
        return ring_attention(q, k, v, sp_cfg["mesh"], axis_name=sp_cfg["axis"],
                              causal=causal,
                              schedule="zigzag" if (causal and layout == "zigzag")
                              else "auto",
                              layout=layout)
    if flash_applies(use_flash, dropout_rate):
        return flash_sdpa(q, k, v, causal, key_bias=key_bias)
    from ..ops.attention_scores import scores_mxu
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = scores_mxu(q, k, scale)
    if key_bias is not None:
        logits = logits + key_bias[:, None, None, :]
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(cm, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    probs = _drop(probs, dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _split_heads(x, head_dim):
    # split by head_dim, not head count: under tensor parallelism the
    # projection output is a tp-local slice holding num_heads/tp whole
    # heads, so the local head count falls out of the shape
    b, s, d = x.shape
    return x.reshape(b, s, d // head_dim, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


# -- parameter stacks --------------------------------------------------------


def encoder_stack_params(num_layers: int, d_model: int, d_inner: int,
                         name: str = "encoder_stack") -> Dict[str, jax.Array]:
    """Create the stacked params of ``num_layers`` pre-LN self-attention
    blocks. The fused qkv weight is [L, d, 3, d_model] (q/k/v on their own
    axis, so a tensor-parallel shard of the LAST dim keeps whole heads —
    the Megatron fused-qkv layout)."""
    helper = LayerHelper(name, name=name)
    xavier = StackedInit(init.Xavier())
    zeros = init.Constant(0.0)
    ones = init.Constant(1.0)
    L, d, di = num_layers, d_model, d_inner
    p = {
        "ln1/scale": helper.create_parameter("ln1/scale", (L, d), jnp.float32, initializer=ones),
        "ln1/bias": helper.create_parameter("ln1/bias", (L, d), jnp.float32, initializer=zeros),
        "qkv/w": helper.create_parameter("qkv/w", (L, d, 3, d), jnp.float32, initializer=xavier),
        "qkv/b": helper.create_parameter("qkv/b", (L, 3, d), jnp.float32, initializer=zeros),
        "out/w": helper.create_parameter("out/w", (L, d, d), jnp.float32, initializer=xavier),
        "out/b": helper.create_parameter("out/b", (L, d), jnp.float32, initializer=zeros),
        "ln2/scale": helper.create_parameter("ln2/scale", (L, d), jnp.float32, initializer=ones),
        "ln2/bias": helper.create_parameter("ln2/bias", (L, d), jnp.float32, initializer=zeros),
        "ffn_in/w": helper.create_parameter("ffn_in/w", (L, d, di), jnp.float32, initializer=xavier),
        "ffn_in/b": helper.create_parameter("ffn_in/b", (L, di), jnp.float32, initializer=zeros),
        "ffn_out/w": helper.create_parameter("ffn_out/w", (L, di, d), jnp.float32, initializer=xavier),
        "ffn_out/b": helper.create_parameter("ffn_out/b", (L, d), jnp.float32, initializer=zeros),
    }
    return p


def decoder_stack_params(num_layers: int, d_model: int, d_inner: int,
                         name: str = "decoder_stack") -> Dict[str, jax.Array]:
    """Stacked pre-LN decoder blocks: causal self-attention + cross
    attention (encoder-decoder capability of the reference's transformer
    benchmark) + FFN."""
    p = encoder_stack_params(num_layers, d_model, d_inner, name=name)
    helper = LayerHelper(name, name=name)
    xavier = StackedInit(init.Xavier())
    zeros = init.Constant(0.0)
    ones = init.Constant(1.0)
    L, d = num_layers, d_model
    p.update({
        "lnx/scale": helper.create_parameter("lnx/scale", (L, d), jnp.float32, initializer=ones),
        "lnx/bias": helper.create_parameter("lnx/bias", (L, d), jnp.float32, initializer=zeros),
        "xq/w": helper.create_parameter("xq/w", (L, d, d), jnp.float32, initializer=xavier),
        "xq/b": helper.create_parameter("xq/b", (L, d), jnp.float32, initializer=zeros),
        "xkv/w": helper.create_parameter("xkv/w", (L, d, 2, d), jnp.float32, initializer=xavier),
        "xkv/b": helper.create_parameter("xkv/b", (L, 2, d), jnp.float32, initializer=zeros),
        "xout/w": helper.create_parameter("xout/w", (L, d, d), jnp.float32, initializer=xavier),
        "xout/b": helper.create_parameter("xout/b", (L, d), jnp.float32, initializer=zeros),
    })
    return p


# -- block functions ---------------------------------------------------------
#
# ``tp_axis`` says how a block's projections meet tensor parallelism:
# None, under GSPMD or on one chip, where they are plain matmuls; the
# name of a manual axis, in a pipeline stage, where the activation is
# whole and a row-parallel matmul's partial sums are closed by ``psum``;
# a ``BatchSharded`` name, where the activation is ``[b/tp, s, d]`` between
# matmuls, the exchanges run in chunks under the matmuls themselves, and
# what lies between a column- and a row-parallel matmul holds the tp
# group's rows in ring order (parallel/collective_matmul.py).


def _column_parallel(h, proj, tp_axis):
    """``proj`` (a matmul with tp-local columns) of every row."""
    if isinstance(tp_axis, BatchSharded):
        return jnp.concatenate(gather_matmul(h, proj, tp_axis))
    return proj(h)


def _row_parallel(o, w, tp_axis):
    """``o @ w`` with tp-local rows of ``w``, partial sums closed."""
    if isinstance(tp_axis, BatchSharded):
        chunks = jnp.split(o, jax.lax.axis_size(tp_axis))
        return matmul_scatter(chunks, lambda c: jnp.matmul(c, w), tp_axis)
    o = jnp.matmul(o, w)
    return jax.lax.psum(o, tp_axis) if tp_axis else o


def _attend(q, k, v, head_dim, key_bias, causal: bool, use_flash: bool,
            dropout_rate: float = 0.0):
    """Attention over the projections' own layout: q, k and v are each
    ``[b, s, h*hd]`` with their heads side by side, and so is the result,
    what the output projection takes. The flash kernels read and write
    that layout in place (no head is transposed, no 64-wide minor
    dimension padded to 128 lanes: PERF.md, PR 32); the dense path works
    on ``[b, h, s, hd]``."""
    if flash_applies(use_flash, dropout_rate):
        return flash_sdpa(q, k, v, causal, key_bias=key_bias,
                          num_heads=q.shape[-1] // head_dim)
    q, k, v = (_split_heads(t, head_dim) for t in (q, k, v))
    return _merge_heads(_sdpa(q, k, v, key_bias, causal, False,
                              dropout_rate=dropout_rate))


def _self_attend(x, p, head_dim, key_bias, causal, use_flash, tp_axis=None,
                 sp_cfg=None, dropout_rate: float = 0.0):
    """Layer norm, the fused projection and self-attention over it:
    ``(o, qkv)``, ``o`` ``[b, s, h*hd]`` and ``qkv`` as :func:`_qkv` gave
    it (:func:`_part` picks k and v out of either form)."""
    if sp_cfg is None and flash_applies(use_flash, dropout_rate):
        # one flat matmul's output, q, k and v side by side, read as it
        # lies; under a ``tp`` axis the partitioner splits, the einsum it
        # partitions (see :func:`_qkv`), and the kernels take its parts
        flat = not tp_partitioned()
        qkv = _qkv(x, p, tp_axis, flat=flat)
        parts = (qkv, None, None) if flat else tuple(
            _part(qkv, i) for i in range(3))
        o = flash_sdpa(*parts, causal, key_bias=key_bias,
                       num_heads=_part(qkv, 0).shape[-1] // head_dim)
        return o, qkv
    qkv = _qkv(x, p, tp_axis)
    q, k, v = (_split_heads(qkv[:, :, i], head_dim) for i in range(3))
    # flash_applies has spoken (and warned, if it had to) for this call:
    # only the sequence-parallel paths still ask for the kernel
    o = _sdpa(q, k, v, key_bias, causal, use_flash and sp_cfg is not None,
              sp_cfg, dropout_rate=dropout_rate)
    return _merge_heads(o), qkv


@jax.named_scope("attn")
def _self_attention(x, p, num_heads, causal, use_flash, key_bias, tp_axis,
                    sp_cfg=None, dropout_rate: float = 0.0):
    o, _ = _self_attend(x, p, x.shape[-1] // num_heads, key_bias, causal,
                        use_flash, tp_axis, sp_cfg, dropout_rate)
    return _attn_out(x, p, o, tp_axis, dropout_rate=dropout_rate)


@jax.named_scope("ffn")
def _ffn(x, p, tp_axis, dropout_rate: float = 0.0):
    h = _ln(x, p["ln2/scale"], p["ln2/bias"])
    h, w1, w2 = cast_compute(h, p["ffn_in/w"], p["ffn_out/w"])

    def inner(c):
        c = jax.nn.relu(jnp.matmul(c, w1) + p["ffn_in/b"].astype(c.dtype))
        return _drop(c, dropout_rate)

    if isinstance(tp_axis, BatchSharded):
        # the inner activation is made and used chunk by chunk
        h = matmul_scatter(gather_matmul(h, inner, tp_axis),
                           lambda c: jnp.matmul(c, w2), tp_axis)
    else:
        h = _row_parallel(inner(h), w2, tp_axis)
    return x + _drop(h + p["ffn_out/b"].astype(h.dtype), dropout_rate)


def make_encoder_block(num_heads: int, use_flash: bool = False,
                       causal: bool = False,
                       tp_axis: Optional[str] = None,
                       sp_cfg: Optional[dict] = None,
                       dropout_rate: float = 0.0) -> Callable:
    """layer_fn(x, layer_params, key_bias) for pipeline_apply/scan. When
    ``tp_axis`` is set, attention/ffn heads are tp-local and the output
    projections psum partial sums (Megatron pattern inside a stage).
    ``sp_cfg`` routes self-attention through zigzag ring attention.
    ``dropout_rate`` mirrors the unrolled layer's four dropout sites;
    the scan path decorrelates layers via rng_fold (see module doc)."""

    def block(x, p, key_bias=None):
        x = _self_attention(x, p, num_heads, causal, use_flash,
                            key_bias, tp_axis, sp_cfg,
                            dropout_rate=dropout_rate)
        return _ffn(x, p, tp_axis, dropout_rate=dropout_rate)

    return block


def make_decoder_block(num_heads: int, use_flash: bool = False,
                       causal: bool = True,
                       tp_axis: Optional[str] = None,
                       sp_cfg: Optional[dict] = None,
                       dropout_rate: float = 0.0) -> Callable:
    """layer_fn(x, layer_params, extra) with extra = {"enc": encoder
    output [b,s,d], "enc_bias": additive [b,s] padding bias}. Causal
    self-attention + cross attention + FFN."""
    enforce(sp_cfg is None,
            "sequence parallelism is wired for the self-attention-only "
            "stack (models/gpt.py); the encoder-decoder cross-attention "
            "path does not support it")

    def block(x, p, extra):
        head_dim = x.shape[-1] // num_heads
        x = _self_attention(x, p, num_heads, causal, use_flash, None, tp_axis,
                            dropout_rate=dropout_rate)
        with jax.named_scope("attn"):
            h = _ln(x, p["lnx/scale"], p["lnx/bias"])
            h, wq, wkv, enc = cast_compute(h, p["xq/w"], p["xkv/w"],
                                           extra["enc"])
            q = _column_parallel(
                h, lambda c: jnp.matmul(c, wq) + p["xq/b"].astype(c.dtype),
                tp_axis)
            # the encoder's output is whole on every tp rank
            kv = jnp.einsum("bsd,dke->bske", enc, wkv) \
                + p["xkv/b"].astype(h.dtype)
            o = _attend(q, kv[:, :, 0], kv[:, :, 1], head_dim,
                        extra.get("enc_bias"), False, use_flash,
                        dropout_rate)
            o, ow = cast_compute(o, p["xout/w"])
            o = _row_parallel(o, ow, tp_axis)
            x = x + _drop(o + p["xout/b"].astype(o.dtype), dropout_rate)
        return _ffn(x, p, tp_axis, dropout_rate=dropout_rate)

    return block


# -- incremental decoding (KV cache over stacked params) ---------------------
#
# The cache of one layer is LANE-DENSE: ``[rows, T, h*hd]``, every head's
# vector side by side in the minor dimension, the form a position's k and v
# have when they leave the fused projection. The TPU tiles an array's two
# minor dimensions (8, 128); a ``[rows, h, T, hd]`` cache with hd = 64
# fills 64 lanes of every 128 and is stored, and read each step, at twice
# its size (PERF.md, PR 28). ``h*hd`` is the model width, dense for every
# head_dim. Rows lead, as ``beam_search._gather_beams`` needs of a state
# leaf. The one-row attention below reads a slab as stored: nothing in the
# decode loop reshapes a slab's minor dimension, which the compiler would
# answer with a relayout of the whole slab.


def _qkv(x, p, tp_axis=None, flat: bool = False):
    """LayerNorm and the fused projection: ``[b, s, 3, h*hd]``, q, k and
    v on axis 2, each with its heads side by side in the last; ``flat``,
    ``[b, s, 3 * h*hd]``, the three side by side: one plain matmul, whose
    output the flash kernels read as it lies (the compiler gives a
    ``[b, s, 3, e]`` result a layout of its own choosing, and three
    slices of it are three copies). Who needs the first form: the dense
    and sequence-parallel paths, which split heads out of each part
    anyway, and a stack whose ``tp`` axis the partitioner splits
    (:func:`_batch_sharded_why_not`): ``qkv/w`` is then sharded over its
    last axis, and merging that axis with the 3 would have the weight
    gathered every layer."""
    h = _ln(x, p["ln1/scale"], p["ln1/bias"])
    h, w = cast_compute(h, p["qkv/w"])
    b = p["qkv/b"]
    if flat:
        w, b = w.reshape(w.shape[0], -1), b.reshape(-1)
        return _column_parallel(
            h, lambda c: jnp.matmul(c, w) + b.astype(c.dtype), tp_axis)
    return _column_parallel(
        h, lambda c: jnp.einsum("bsd,dke->bske", c, w) + b.astype(c.dtype),
        tp_axis)


def _part(qkv, i: int):
    """q, k or v (``i`` = 0, 1, 2) of :func:`_qkv`'s result, ``[b, s, h*hd]``."""
    if qkv.ndim == 4:
        return qkv[:, :, i]
    e = qkv.shape[-1] // 3
    return qkv[:, :, i * e:(i + 1) * e]


def _attn_out(x, p, o, tp_axis=None, dropout_rate: float = 0.0):
    """Output projection and residual; ``o`` is ``[b, s, h*hd]``."""
    o, ow = cast_compute(o, p["out/w"])
    o = _row_parallel(o, ow, tp_axis)
    return x + _drop(o + p["out/b"].astype(o.dtype), dropout_rate)


def prefill_block(x, p, num_heads: int, use_flash: bool = False):
    """Causal block that also returns its (k, v) for cache seeding, each
    ``[b, s, h*hd]``: the cache's own layout, so seeding it transposes
    nothing back (the stacked-layer analog of the transformer decoder's
    cache path, models/transformer.py make_decoder)."""
    head_dim = x.shape[-1] // num_heads
    with jax.named_scope("attn"):
        o, qkv = _self_attend(x, p, head_dim, None, True, use_flash)
        x = _attn_out(x, p, o)
    return _ffn(x, p, None), (_part(qkv, 1), _part(qkv, 2))


def quantize_kv(x, num_heads: int):
    """Symmetric int8 quantization of cache entries ``[..., h*hd]``, one
    scale for each head's vector: int8 ``[..., h*hd]`` and float32 scales
    ``[..., h]``. One quantizer for the whole repo: delegates to
    quantize._quant_dynamic and converts its absmax scale convention
    (dequant = q/qmax·scale) to the multiply-direct one the decode
    matmuls factor out (dequant = q·scale), so the two can never
    drift. Zero vectors dequantize to exact 0."""
    from ..quantize import _quant_dynamic

    heads = x.reshape(x.shape[:-1] + (num_heads, x.shape[-1] // num_heads))
    q, scale = _quant_dynamic(heads, axes=(-1,))
    return q.reshape(x.shape), scale[..., 0] / 127.0


def _head_blocks(width: int, num_heads: int, dtype):
    """``[h*hd, h]`` of 0/1: column ``j`` selects head ``j``'s lanes."""
    lane = jnp.arange(width)[:, None] // (width // num_heads)
    return (lane == jnp.arange(num_heads)[None, :]).astype(dtype)


def _cache_attention(q, k_cache, v_cache, index, num_heads: int,
                     k_scale=None, v_scale=None):
    """One query row of every head against a lane-dense cache, read in
    place. q ``[rows, 1, h*hd]``; caches ``[rows, T, h*hd]``; positions
    ``<= index`` attended; returns ``[rows, 1, h*hd]``, heads merged.

    Heads are separated on the MXU, not by a reshape: the query row is
    laid block-diagonally in a ``[h*hd, h]`` operand, so ``cache @ that``
    is every head's score ``[T, h]`` (float32 accumulation), and
    ``probs^T @ cache`` gives ``[h, h*hd]`` whose block diagonal is the
    output. An int8 cache's scales ``[rows, T, h]`` factor out of both
    matmuls (score[t] ∝ k_scale[t], out ∝ probs∘v_scale), so no
    dequantized cache is ever materialized."""
    _, T, width = k_cache.shape
    blocks = _head_blocks(width, num_heads, q.dtype)
    q_blocks = q[:, 0, :, None] * blocks                        # [rows, c, h]
    scale = 1.0 / math.sqrt(width // num_heads)
    logits = jnp.einsum("rtc,rch->rth", k_cache.astype(q.dtype), q_blocks,
                        preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        logits = logits * k_scale
    live = jnp.arange(T)[None, :, None] <= index
    probs = jax.nn.softmax(jnp.where(live, logits, NEG_INF), axis=1)
    if v_scale is not None:
        probs = probs * v_scale
    o = jnp.einsum("rth,rtc->rhc", probs.astype(q.dtype),
                   v_cache.astype(q.dtype))
    return jnp.einsum("rhc,ch->rc", o, blocks)[:, None, :]


def _write_row(cache, row, index):
    return jax.lax.dynamic_update_slice(cache, row.astype(cache.dtype),
                                        (0, index, 0))


def decode_block_q8(x, p, k_q, k_s, v_q, v_s, index, num_heads: int):
    """decode_block with an int8 KV cache: k_q/v_q int8 ``[rows, T,
    h*hd]`` plus one scale a head and position, k_s/v_s ``[rows, T, h]``.
    Decode is HBM-bound — the cache read dominates — so halving (vs
    bf16) or quartering (vs f32) the cache bytes is direct serving
    throughput. Returns (x, k_q, k_s, v_q, v_s)."""
    with jax.named_scope("attn"):
        qkv = _qkv(x, p)
        q, k1, v1 = (qkv[:, :, i] for i in range(3))
        k1q, k1s = quantize_kv(k1, num_heads)
        v1q, v1s = quantize_kv(v1, num_heads)
        k_q, k_s = _write_row(k_q, k1q, index), _write_row(k_s, k1s, index)
        v_q, v_s = _write_row(v_q, v1q, index), _write_row(v_s, v1s, index)
        o = _cache_attention(q, k_q, v_q, index, num_heads, k_s, v_s)
        x = _attn_out(x, p, o)
    return _ffn(x, p, None), k_q, k_s, v_q, v_s


def decode_block(x, p, k_cache, v_cache, index, num_heads: int):
    """One-token step: x ``[rows, 1, d]``; caches ``[rows, T, h*hd]``
    (lane-dense, see above), written in place at ``index``; attends to
    cache positions <= index. Returns (x, new_k, new_v)."""
    with jax.named_scope("attn"):
        qkv = _qkv(x, p)
        q, k1, v1 = (qkv[:, :, i] for i in range(3))
        k_cache = _write_row(k_cache, k1, index)
        v_cache = _write_row(v_cache, v1, index)
        o = _cache_attention(q, k_cache, v_cache, index, num_heads)
        x = _attn_out(x, p, o)
    return _ffn(x, p, None), k_cache, v_cache


# -- tensor-parallel specs (non-layer dims, pipeline_apply param_specs) ------

_ENCODER_TP_SPECS = {
    "ln1/scale": P(), "ln1/bias": P(),
    "qkv/w": P(None, None, "tp"), "qkv/b": P(None, "tp"),
    "out/w": P("tp"), "out/b": P(),
    "ln2/scale": P(), "ln2/bias": P(),
    "ffn_in/w": P(None, "tp"), "ffn_in/b": P("tp"),
    "ffn_out/w": P("tp"), "ffn_out/b": P(),
}

_DECODER_TP_SPECS = dict(_ENCODER_TP_SPECS, **{
    "lnx/scale": P(), "lnx/bias": P(),
    "xq/w": P(None, "tp"), "xq/b": P("tp"),
    "xkv/w": P(None, None, "tp"), "xkv/b": P(None, "tp"),
    "xout/w": P("tp"), "xout/b": P(),
})


def stack_tp_specs(stacked: Dict[str, Any]) -> Dict[str, Any]:
    table = _DECODER_TP_SPECS if "xq/w" in stacked else _ENCODER_TP_SPECS
    return {k: table[k] for k in stacked}


# -- apply -------------------------------------------------------------------


def _local_batch(x, mesh) -> int:
    """Rows of ``x`` on one data shard (a batch the data axes do not
    divide stays whole, as GSPMD would leave it)."""
    from ..parallel.mesh import DATA_AXES, dividing_axes

    return x.shape[0] // math.prod(
        mesh.shape[a] for a in dividing_axes(mesh, x.shape[0], DATA_AXES))


def _batch_sharded_why_not(x, stacked, mesh, tp: int, num_heads: int, sp,
                           dropout_rate: float) -> Optional[str]:
    """Why this stack cannot run with its activation sharded over the
    mesh's ``tp`` axis between matmuls (it then keeps the GSPMD form),
    or None if it can."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return "already inside a shard_map"
    if sp is not None:
        return "sequence parallelism has its own shard_map"
    if any(k not in _DECODER_TP_SPECS for k in stacked):
        return "not the encoder or decoder stack: no tp specs for it"
    if x.ndim != 3 or _local_batch(x, mesh) % tp:
        return f"tp={tp} does not divide the rows of a data shard"
    inner = stacked["ffn_in/w"].shape[-1]
    if num_heads % tp or inner % tp:
        return f"tp={tp} does not divide {num_heads} heads and inner {inner}"
    if dropout_rate > 0.0 and _in_training():
        return "dropout masks are not folded per shard"
    return None


def _record_tp_plan(x, mesh, tp: int, stacked, why_not: Optional[str],
                    remat: bool):
    """One zero-length span in the program's ring for each stack traced
    under a ``tp`` axis: the exchanges a layer makes and their size on
    one chip. ``form`` is ``windowed`` (chunks of ``chunk_rows`` batch
    rows, sent under the block's matmuls; an exchange is ``tp - 1`` hops
    of ``bytes_per_exchange``) or ``all_reduce`` (the whole activation,
    closed by the partitioner or a ``psum``), with ``why`` naming what
    kept the stack from the first."""
    from ..core import profiler

    windowed = why_not is None
    # windowed: a gather before each column-parallel matmul and a scatter
    # after each row-parallel one; all_reduce: one after each row-parallel
    # matmul, and in the backward pass one for each column-parallel dx.
    # Remat's second forward needs all but the last result again.
    rows = 3 if "xq/w" in stacked else 2      # row-parallel matmuls a layer
    each_pass = 2 * rows if windowed else rows
    train = _in_training()
    batch = _local_batch(x, mesh)
    chunk_rows = batch // tp if windowed else batch
    profiler.record_span(
        "tp.plan", time.time_ns(), 0, tp=tp,
        seq=x.shape[1] if x.ndim == 3 else 0, chunk_rows=chunk_rows,
        form="windowed" if windowed else "all_reduce", why=why_not or "",
        exchanges_per_layer={
            "forward": each_pass,
            "remat": each_pass - 1 if train and remat else 0,
            "backward": each_pass if train else 0},
        bytes_per_exchange=chunk_rows * math.prod(x.shape[1:])
        * jnp.dtype(cast_compute(x).dtype).itemsize)


def _batch_sharded(run, mesh, x, stacked, extras):
    """``run`` per shard under ``shard_map`` over the whole mesh, ``tp``
    manual: the activation is ``[b/dp/tp, s, d]`` from its slice at entry
    (no traffic) to one gather at exit, and the side inputs, whole on
    every tp rank, are put in the rank's ring order once, outside the
    layer loop. The parameters enter in float32 and are cast inside, so
    their cotangents cross the boundary, and are reduced over ``dp``, in
    float32."""
    from ..parallel.mesh import DATA_AXES, TP, dividing_axes

    data = dividing_axes(mesh, x.shape[0], DATA_AXES)
    specs = stack_tp_specs(stacked)

    def mapped(x_, stacked_, extras_):
        extras_ = jax.tree.map(lambda e: ring_order(e, TP), extras_)
        out = run(x_, stacked_, extras_)
        return jax.lax.all_gather(out, TP, axis=0, tiled=True)

    return jax.shard_map(
        mapped, mesh=mesh,
        in_specs=(P(data + (TP,)),
                  {k: P(None, *specs[k]) for k in stacked},
                  jax.tree.map(lambda e: P(data or None), extras)),
        out_specs=P(data or None), check_vma=False)(x, stacked, extras)


def apply_stacked(x, stacked: Dict[str, jax.Array], make_block: Callable,
                  extras=None, num_heads: int = 8, use_flash: bool = False,
                  causal: bool = False, remat: bool = False,
                  dropout_rate: float = 0.0):
    """Run a parameter stack over ``x``: pipelined across the ``pp`` mesh
    axis when the Trainer has entered :func:`framework.pipeline_mode`
    (DistStrategy.pp_microbatches — the BuildStrategy-knob analog),
    sequential ``lax.scan`` otherwise. Under a mesh whose ``tp`` axis is
    larger than 1 the scan runs per shard with the activation sharded
    over ``tp`` between matmuls (:func:`_batch_sharded`); where that form
    cannot apply (:func:`_batch_sharded_why_not`) GSPMD tp/fsdp-shards
    the scanned matmuls from the rule-table shardings, as it does for
    every other axis.

    ``make_block(num_heads=…, use_flash=…, causal=…, tp_axis=…)`` builds
    the layer fn — tp_axis is set when the pipeline mesh also has a
    ``tp`` axis, making dp×tp×pp one call.
    """
    from ..parallel.mesh import TP

    cfg = pipeline_config()
    sp = sp_config()
    enforce(not (cfg is not None and sp is not None),
            "pipeline and sequence parallelism cannot wrap the same stack "
            "(ring attention's shard_map cannot nest inside the pipeline's)")
    mesh = cfg["mesh"] if cfg is not None else active_mesh()
    tp_size = mesh.shape.get(TP, 1) if mesh is not None else 1
    if cfg is None:
        why_not = _batch_sharded_why_not(
            x, stacked, mesh, tp_size, num_heads, sp,
            dropout_rate) if tp_size > 1 else "no tp axis"
        block = make_block(num_heads=num_heads, use_flash=use_flash,
                           causal=causal,
                           tp_axis=None if why_not else BatchSharded(TP),
                           sp_cfg=sp, dropout_rate=dropout_rate)
        num_layers = next(iter(stacked.values())).shape[0]

        def run(x, stacked, extras):
            def scan_body(a, xs):
                lp, idx = xs

                def fn(a_, lp_):
                    # per-layer rng: the traced layer index folds into the
                    # ambient stream so dropout masks decorrelate across
                    # scan iterations (the body is traced ONCE)
                    with rng_fold(idx):
                        return block(a_, lp_, extras) if extras is not None \
                            else block(a_, lp_)
                # remat=True forces per-layer checkpointing (cfg.remat);
                # False defers to the ambient strategy.remat switch
                return maybe_remat(fn, enabled=remat or None)(a, lp), None
            out, _ = jax.lax.scan(scan_body, x,
                                  (stacked, jnp.arange(num_layers)))
            return out

        if tp_size > 1:
            _record_tp_plan(x, mesh, tp_size, stacked, why_not,
                            remat or remat_enabled())
        if why_not:
            return run(x, stacked, extras)
        return _batch_sharded(run, mesh, x, stacked, extras)

    from ..framework import next_rng_key
    from ..parallel.pipeline import pipeline_apply
    tp = TP if tp_size > 1 else None
    if tp:
        enforce(num_heads % tp_size == 0,
                f"stacked blocks with tp={tp_size} need num_heads "
                f"({num_heads}) divisible by tp")
        _record_tp_plan(x, mesh, tp_size, stacked,
                        "pipeline stages close partial sums with psum",
                        remat or remat_enabled())
    block = make_block(num_heads=num_heads, use_flash=use_flash,
                       causal=causal, tp_axis=tp, sp_cfg=None,
                       dropout_rate=dropout_rate)
    layer_fn = block if extras is not None else (lambda a, lp: block(a, lp))
    # dropout in the pipeline: thread one per-step key into the schedule
    # (the body runs under shard_map, where the ambient stream is not
    # addressable); pipeline_apply folds it per (layer, microbatch,
    # data-shard). Eval traces pass None — dropout is a no-op there.
    rng_key = (next_rng_key()
               if dropout_rate > 0.0 and _in_training() else None)
    return pipeline_apply(
        x, stacked, layer_fn, mesh, axis_name=cfg["axis"],
        microbatches=cfg["microbatches"],
        interleave=cfg.get("interleave", 1),
        param_specs=stack_tp_specs(stacked) if tp else None,
        extras=extras,
        param_layout=cfg.get("param_layout", "stacked"),
        rng_key=rng_key)
