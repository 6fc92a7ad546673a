"""Attention layers.

The reference has NO attention kernels — attention exists only as
composed ops in models (SURVEY §5 "long-context": e.g. benchmark
machine_translation.py builds dot-product attention from mul/softmax).
Per SURVEY §7 these are new first-class components for the TPU build:
a fused scaled-dot-product core (XLA-fused by default, pallas flash
kernel via ``paddle_tpu.ops.flash_attention`` for long sequences) and a
multi-head layer whose parameter names line up with the tensor-parallel
sharding rules (parallel.sharding.transformer_tp_rules).
"""

from __future__ import annotations


import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework import LayerHelper, active_mesh, cast_compute, in_training
from .. import initializer as init
from .nn import dropout as _dropout

from ..ops.attention_scores import scores_mxu as _scores_mxu

NEG_INF = -1e9  # matches the additive-mask convention (finite to stay bf16-safe)


def flash_applies(use_flash: bool, dropout_rate: float) -> bool:
    """Does this attention call trace the flash kernel? The kernel has
    no dropout, but dropout is a no-op outside training — eval/serving
    traces of a dropout>0 model keep the kernel. A training trace with
    dropout takes the dense O(s^2) path, and says so (once per call
    site, at trace time): a cell meant to measure the kernel must not
    measure the dense path unawares."""
    if not use_flash:
        return False
    if dropout_rate == 0.0 or not in_training():
        return True
    warnings.warn(
        f"use_flash with dropout {dropout_rate} in training: the flash "
        f"kernel has no dropout, so attention traces the dense O(s^2) "
        f"path", stacklevel=3)
    return False


def tp_partitioned() -> bool:
    """Does the partitioner split this trace over a ``tp`` axis larger
    than 1 (a mesh is active and the trace is not already per shard,
    inside a ``shard_map``)? Weights then carry the rule table's ``tp``
    shardings, and a reshape that merges a sharded dimension into another
    makes the partitioner gather them."""
    from ..parallel.mesh import TP

    mesh = active_mesh()
    return (mesh is not None and mesh.shape.get(TP, 1) > 1
            and not jax.sharding.get_abstract_mesh().manual_axes)


def flash_sdpa(q, k, v, causal: bool, key_bias=None, num_heads=None):
    """The flash kernel over [b, h, s, d] with an optional additive
    [b, s_k] key bias; with ``num_heads``, over [b, s, h*d] as a
    projection leaves it, or over a fused self-attention projection
    [b, s, 3 * h*d] given as ``q`` with ``k`` and ``v`` None (the kernels
    read and write those in place: ops/flash_attention.py). Under a
    Trainer's multi-device mesh the kernel runs per shard inside
    ``shard_map`` — batch over the data axes, heads over ``tp`` (whole
    heads of the minor dimension in the projections' layout) — because
    GSPMD cannot partition a Mosaic kernel (its lowering refuses any jit
    over more than one device). A dim the axis size does not divide
    stays whole on every shard, as GSPMD itself would leave it. Inside
    an enclosing ``shard_map`` (pipeline stages, the shard-local
    gradient paths) the call is already per-shard and goes straight to
    the kernel."""
    from ..ops.flash_attention import flash_attention
    from ..parallel.mesh import DATA_AXES, TP, dividing_axes

    args = [x for x in (q, k, v) if x is not None]
    mesh = active_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return flash_attention(*args, causal=causal, key_bias=key_bias,
                               num_heads=num_heads)

    batch = dividing_axes(mesh, q.shape[0], DATA_AXES) or None
    heads = dividing_axes(mesh, num_heads or q.shape[1], (TP,)) or None
    if num_heads is None:
        spec = P(batch, heads, None, None)
    else:
        spec = P(batch, None, heads)
        if heads:
            num_heads //= mesh.shape[TP]
    if k is None and heads:
        # a shard's lanes would have to be its third of each of q, k, v
        args = list(jnp.split(q, 3, axis=-1))
    specs = [spec] * len(args)
    if key_bias is not None:
        args.append(jnp.broadcast_to(key_bias, (q.shape[0], key_bias.shape[-1])))
        specs.append(P(batch, None))

    def per_shard(*a):
        qkv, bias = (a[:-1], a[-1]) if key_bias is not None else (a, None)
        return flash_attention(*qkv, causal=causal, key_bias=bias,
                               num_heads=num_heads)

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=tuple(specs),
                       out_specs=spec, check_vma=False)
    return fn(*args)


def scaled_dot_product_attention(
    q, k, v,
    attn_mask: Optional[jax.Array] = None,
    causal: bool = False,
    dropout_rate: float = 0.0,
    use_flash: Optional[bool] = None,
):
    """Fused SDPA over [batch, heads, seq, head_dim] tensors.

    ``attn_mask``: additive mask broadcastable to [b, h, sq, sk] (0 keep,
    NEG_INF drop) — the convention fluid models built by hand. ``causal``
    adds the autoregressive mask. Accumulation in fp32 regardless of
    input dtype (MXU-native bf16 inputs stay bf16 on the matmul inputs).
    """
    if use_flash is None:
        use_flash = False
    if flash_applies(use_flash, dropout_rate):
        if attn_mask is None:
            return flash_sdpa(q, k, v, causal)
        if attn_mask.ndim == 4 and attn_mask.shape[1:3] == (1, 1):
            return flash_sdpa(q, k, v, causal, key_bias=attn_mask[:, 0, 0, :])
        # a dense mask is not the kernel's: flash_attention traces the
        # XLA composition (and warns), which GSPMD partitions itself
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, attn_mask=attn_mask)

    head_dim = q.shape[-1]
    scale = 1.0 / math.sqrt(head_dim)
    logits = _scores_mxu(q, k, scale)
    if attn_mask is not None:
        logits = logits + attn_mask
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(cm, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0:
        probs = _dropout(probs, dropout_rate, dropout_implementation="upscale_in_train")
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def multi_head_attention(
    queries,
    keys=None,
    values=None,
    num_heads: int = 8,
    d_model: Optional[int] = None,
    attn_mask: Optional[jax.Array] = None,
    causal: bool = False,
    dropout_rate: float = 0.0,
    cache: Optional[dict] = None,
    use_flash: Optional[bool] = None,
    fuse_qkv: bool = False,
    name: Optional[str] = None,
):
    """Multi-head attention over [batch, seq, d_model] inputs.

    Parameter names (q_proj/k_proj/v_proj/out_proj) are chosen to match
    transformer_tp_rules so Megatron-style TP falls out of the rule
    table. ``cache`` enables incremental decoding: pass {'k':..,'v':..,
    'index': step} and the layer updates it functionally (returned as
    second output) — the while-loop decoder analog.

    ``fuse_qkv`` computes the three projections as ONE matmul against a
    [d_in, 3, d_model] weight (self-attention; cross-attention fuses
    K/V into a [d_in, 2, d_model] ``kv_proj``). One MXU pass of
    (b·s, d)×(d, 3d) instead of three (d, d) passes — better systolic
    utilization at small d_model and a third of the weight-load
    traffic. The 3/2 axis is kept explicit (einsum ``bsd,dke->bske``)
    so the tp sharding on the last axis survives the split into q/k/v
    without GSPMD resharding (rules: transformer_tp_rules qkv_proj).
    """
    helper = LayerHelper("mha", name=name)
    self_attn = keys is None
    keys = queries if keys is None else keys
    values = keys if values is None else values
    d_model = d_model or queries.shape[-1]
    head_dim = d_model // num_heads
    dtype = queries.dtype

    def proj(x, pname, out_dim):
        w = helper.create_parameter(f"{pname}/w", (x.shape[-1], out_dim), jnp.float32,
                                    initializer=init.Xavier())
        b = helper.create_parameter(f"{pname}/b", (out_dim,), jnp.float32,
                                    initializer=init.Constant(0.0))
        x, w = cast_compute(x, w)
        return jnp.matmul(x, w) + b.astype(x.dtype)

    def fused_proj(x, pname, n_out):
        # per-sub-projection Xavier fans: variance must match the
        # unfused layout, not the concatenated shape
        w = helper.create_parameter(
            f"{pname}/w", (x.shape[-1], n_out, d_model), jnp.float32,
            initializer=init.Xavier(fan_in=x.shape[-1], fan_out=d_model))
        b = helper.create_parameter(f"{pname}/b", (n_out, d_model), jnp.float32,
                                    initializer=init.Constant(0.0))
        x, w = cast_compute(x, w)
        out = jnp.einsum("bsd,dke->bske", x, w) + b.astype(x.dtype)
        return tuple(out[:, :, i] for i in range(n_out))

    if fuse_qkv and self_attn:
        from ..core.errors import enforce
        enforce(values is queries,
                "fuse_qkv self-attention reads Q/K/V from the same "
                "source; a distinct values tensor would be silently "
                "dropped — pass fuse_qkv=False")
        q, k, v = fused_proj(queries, "qkv_proj", 3)
    elif fuse_qkv:
        # cross-attention: the fused layout needs K and V to read the
        # same source. The call signature decides the param tree
        # (keys=None → qkv_proj; keys given → q_proj+kv_proj), so a
        # distinct values tensor must fail loudly rather than silently
        # fall back to a third parameter layout.
        from ..core.errors import enforce
        enforce(values is keys,
                "fuse_qkv cross-attention requires values to be keys "
                "(or omitted); pass fuse_qkv=False for distinct K/V "
                "sources")
        q = proj(queries, "q_proj", d_model)
        k, v = fused_proj(keys, "kv_proj", 2)
    else:
        q = proj(queries, "q_proj", d_model)
        k = proj(keys, "k_proj", d_model)
        v = proj(values, "v_proj", d_model)

    # the flash kernels take the projections as they are; a cache and a
    # dense mask are the [b, h, s, hd] paths'
    key_mask = attn_mask is None or (attn_mask.ndim == 4
                                     and attn_mask.shape[1:3] == (1, 1))
    flash = bool(use_flash) and cache is None and key_mask
    if flash and flash_applies(True, dropout_rate):
        out = flash_sdpa(
            q, k, v, causal, num_heads=num_heads,
            key_bias=None if attn_mask is None else attn_mask[:, 0, 0, :])
        return proj(out, "out_proj", d_model)

    def split_heads(x):
        b, s, _ = x.shape
        return x.reshape(b, s, num_heads, head_dim).transpose(0, 2, 1, 3)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)

    new_cache = None
    if cache is not None:
        idx = cache["index"]
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, idx, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, idx, 0))
        k, v = ck, cv
        new_cache = {"k": ck, "v": cv, "index": idx + q.shape[2]}
        # mask out cache positions beyond the current step
        kpos = jnp.arange(ck.shape[2])
        step_mask = jnp.where(kpos[None, None, None, :] <= idx, 0.0, NEG_INF)
        attn_mask = step_mask if attn_mask is None else attn_mask + step_mask
        causal = False

    # ``flash``: flash_applies has spoken (and warned) for this call
    out = scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, causal=causal,
                                       dropout_rate=dropout_rate,
                                       use_flash=use_flash and not flash)
    b, h, s, hd = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    out = proj(out, "out_proj", d_model)
    if cache is not None:
        return out, new_cache
    return out


def ffn(x, d_inner: int, dropout_rate: float = 0.0, activation: str = "relu",
        name: Optional[str] = None):
    """Position-wise feed-forward with TP-rule-compatible names."""
    from .ops import apply_activation
    helper = LayerHelper("ffn", name=name)
    d_model = x.shape[-1]
    w1 = helper.create_parameter("ffn_in/w", (d_model, d_inner), jnp.float32,
                                 initializer=init.Xavier())
    b1 = helper.create_parameter("ffn_in/b", (d_inner,), jnp.float32,
                                 initializer=init.Constant(0.0))
    w2 = helper.create_parameter("ffn_out/w", (d_inner, d_model), jnp.float32,
                                 initializer=init.Xavier())
    b2 = helper.create_parameter("ffn_out/b", (d_model,), jnp.float32,
                                 initializer=init.Constant(0.0))
    x, w1, w2 = cast_compute(x, w1, w2)
    h = apply_activation(jnp.matmul(x, w1) + b1.astype(x.dtype), activation)
    if dropout_rate:
        h = _dropout(h, dropout_rate, dropout_implementation="upscale_in_train")
    return jnp.matmul(h, w2) + b2.astype(x.dtype)


def positional_encoding(seq_len: int, d_model: int, dtype=jnp.float32):
    """Sinusoidal position table (the position_encoding_init of the
    reference's transformer benchmark model)."""
    pos = jnp.arange(seq_len)[:, None].astype(jnp.float32)
    i = jnp.arange(d_model // 2)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, 2 * i / d_model)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return pe.astype(dtype)


def padding_mask(ids, pad_id: int = 0):
    """[b, s] ids -> additive mask [b, 1, 1, s]."""
    m = (ids == pad_id)
    return jnp.where(m, NEG_INF, 0.0)[:, None, None, :]
