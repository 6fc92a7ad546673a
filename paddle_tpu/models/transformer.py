"""Transformer (encoder-decoder, WMT en-de "base" config).

Capability analog of the reference's fluid transformer benchmark
(benchmark/fluid/models/machine_translation.py builds attention from
primitive ops; fluid has no attention kernels — SURVEY §5). Re-designed
TPU-first: pre-LN residual blocks, bf16-friendly, parameter names
aligned with parallel.transformer_tp_rules for TP/FSDP sharding, flash
attention switchable.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import layers as L
from ..framework import LayerHelper, maybe_remat, name_scope
from ..layers import attention as A
from ..ops.fused_ce import softmax_cross_entropy_sum
from .. import initializer as init


@dataclasses.dataclass
class TransformerConfig:
    src_vocab: int = 32000
    trg_vocab: int = 32000
    max_len: int = 256
    d_model: int = 512
    d_inner: int = 2048
    num_heads: int = 8
    num_encoder_layers: int = 6
    num_decoder_layers: int = 6
    dropout: float = 0.1
    label_smooth_eps: float = 0.1
    use_flash: bool = False
    # one [d,3,d] (self) / [d,2,d] (cross K/V) projection matmul per
    # attention instead of three — see layers/attention.py fuse_qkv
    fuse_qkv: bool = False
    # chunked logits-free CE (ops/fused_ce.py); ce_chunk = rows of a chunk
    # (the whole vocabulary a chunk)
    fused_ce: bool = False
    ce_chunk: int = 4096
    # per-block jax.checkpoint: drop intra-layer activations, recompute
    # in backward (memory_optimize analog). False still honors the
    # ambient framework.remat_mode the Trainer sets from strategy.remat.
    remat: bool = False
    # stacked-block representation (layers.stacked): per-layer params on
    # a leading [L, ...] axis — required for pipeline parallelism
    # (DistStrategy.pp_microbatches) and scan-compiled on a single chip
    # (one traced layer body instead of L unrolled copies: ~L x faster
    # compiles). Dropout works on the scan path (per-layer rng_fold);
    # the pipeline path still needs dropout == 0.
    stacked: bool = False
    dtype: str = "float32"


def base_config(**kw) -> TransformerConfig:
    return TransformerConfig(**kw)


def _embed(ids, vocab, d_model, dtype, scope_name):
    with name_scope(scope_name):
        emb = L.embedding(ids, size=[vocab, d_model], dtype=dtype,
                          param_attr=None)
    return emb * (d_model ** 0.5)


def encoder_layer(x, cfg: TransformerConfig, mask):
    h = L.layer_norm(x, begin_norm_axis=2)
    h = A.multi_head_attention(h, num_heads=cfg.num_heads, attn_mask=mask,
                               dropout_rate=cfg.dropout, use_flash=cfg.use_flash,
                               fuse_qkv=cfg.fuse_qkv)
    x = x + L.dropout(h, cfg.dropout, dropout_implementation="upscale_in_train")
    h = L.layer_norm(x, begin_norm_axis=2)
    h = A.ffn(h, cfg.d_inner, dropout_rate=cfg.dropout)
    return x + L.dropout(h, cfg.dropout, dropout_implementation="upscale_in_train")


def decoder_layer(x, enc_out, cfg: TransformerConfig, self_mask, cross_mask,
                  cache: Optional[dict] = None):
    h = L.layer_norm(x, begin_norm_axis=2)
    if cache is not None:
        h, cache = A.multi_head_attention(h, num_heads=cfg.num_heads, causal=False,
                                          dropout_rate=0.0, cache=cache,
                                          fuse_qkv=cfg.fuse_qkv)
    else:
        h = A.multi_head_attention(h, num_heads=cfg.num_heads, causal=True,
                                   attn_mask=self_mask, dropout_rate=cfg.dropout,
                                   use_flash=cfg.use_flash, fuse_qkv=cfg.fuse_qkv)
    x = x + L.dropout(h, cfg.dropout, dropout_implementation="upscale_in_train")
    h = L.layer_norm(x, begin_norm_axis=2)
    h = A.multi_head_attention(h, keys=enc_out, num_heads=cfg.num_heads,
                               attn_mask=cross_mask, dropout_rate=cfg.dropout,
                               fuse_qkv=cfg.fuse_qkv)
    x = x + L.dropout(h, cfg.dropout, dropout_implementation="upscale_in_train")
    h = L.layer_norm(x, begin_norm_axis=2)
    h = A.ffn(h, cfg.d_inner, dropout_rate=cfg.dropout)
    x = x + L.dropout(h, cfg.dropout, dropout_implementation="upscale_in_train")
    return (x, cache) if cache is not None else x


def encode(src_ids, cfg: TransformerConfig):
    dtype = jnp.dtype(cfg.dtype)
    x = _embed(src_ids, cfg.src_vocab, cfg.d_model, dtype, "src")
    x = x + A.positional_encoding(src_ids.shape[1], cfg.d_model, dtype)[None]
    x = L.dropout(x, cfg.dropout, dropout_implementation="upscale_in_train")
    mask = A.padding_mask(src_ids)
    with name_scope("encoder"):
        if cfg.stacked:
            from ..layers import stacked as S
            stack = S.encoder_stack_params(cfg.num_encoder_layers,
                                           cfg.d_model, cfg.d_inner)
            key_bias = mask[:, 0, 0, :]  # additive [b, s]
            x = S.apply_stacked(x, stack, S.make_encoder_block,
                                extras=key_bias, num_heads=cfg.num_heads,
                                use_flash=cfg.use_flash, remat=cfg.remat,
                                dropout_rate=cfg.dropout)
        else:
            for _ in range(cfg.num_encoder_layers):
                # fresh wrapper per layer: jax.checkpoint caches the traced
                # body per fn object, and each layer must trace (and create
                # its own params) separately
                x = maybe_remat(lambda a, m: encoder_layer(a, cfg, m),
                                enabled=cfg.remat or None)(x, mask)
        x = L.layer_norm(x, begin_norm_axis=2)
    return x, mask


def decode_hidden(trg_ids, enc_out, cross_mask, cfg: TransformerConfig):
    """Decoder stack up to (hidden states, vocab projection weight) —
    split out so the loss can run the projection chunked (fused_ce)."""
    dtype = jnp.dtype(cfg.dtype)
    x = _embed(trg_ids, cfg.trg_vocab, cfg.d_model, dtype, "trg")
    x = x + A.positional_encoding(trg_ids.shape[1], cfg.d_model, dtype)[None]
    x = L.dropout(x, cfg.dropout, dropout_implementation="upscale_in_train")
    with name_scope("decoder"):
        if cfg.stacked:
            from ..layers import stacked as S
            stack = S.decoder_stack_params(cfg.num_decoder_layers,
                                           cfg.d_model, cfg.d_inner)
            extras = {"enc": enc_out, "enc_bias": cross_mask[:, 0, 0, :]}
            x = S.apply_stacked(x, stack, S.make_decoder_block,
                                extras=extras, num_heads=cfg.num_heads,
                                use_flash=cfg.use_flash, causal=True,
                                remat=cfg.remat, dropout_rate=cfg.dropout)
        else:
            for _ in range(cfg.num_decoder_layers):
                x = maybe_remat(lambda a, e, cm: decoder_layer(a, e, cfg, None, cm),
                                enabled=cfg.remat or None)(x, enc_out, cross_mask)
        x = L.layer_norm(x, begin_norm_axis=2)
    helper = LayerHelper("logits_proj")
    w = helper.create_parameter("w", (cfg.d_model, cfg.trg_vocab), dtype,
                                initializer=init.Xavier())
    return x, w


def decode(trg_ids, enc_out, cross_mask, cfg: TransformerConfig):
    x, w = decode_hidden(trg_ids, enc_out, cross_mask, cfg)
    return jnp.matmul(x, w)


def make_decoder(cfg: TransformerConfig, max_len: int, beam_size: int = 1,
                 bos_id: int = 1, eos_id: int = 2, length_penalty_alpha: float = 0.0):
    """Incremental decoding program (beam_search_op capability): cached
    self-attention KV, one token per step, greedy or beam. Shares
    parameter names with make_model's train program, so params from a
    trained Trainer scope load directly.

    Returns a program fn: (src_ids [b, s]) -> ids [b, max_len] (greedy)
    or [b, beam, max_len] (beam)."""
    from ..core.errors import enforce
    from ..framework import reuse_names
    from ..layers.beam_search import beam_search, greedy_search

    enforce(not cfg.stacked,
            "make_decoder (incremental decoding) supports the per-layer "
            "param layout only; build it with cfg.stacked=False")

    def decode_program(src_ids):
        dtype = jnp.dtype(cfg.dtype)
        b = src_ids.shape[0]
        enc_out, src_mask = encode(src_ids, cfg)
        K = beam_size
        if K > 1:
            # tile encoder outputs per beam
            enc_out = jnp.repeat(enc_out, K, axis=0)
            src_mask = jnp.repeat(src_mask, K, axis=0)
        rows = b * K
        head_dim = cfg.d_model // cfg.num_heads
        caches = [
            {"k": jnp.zeros((rows, cfg.num_heads, max_len, head_dim), dtype),
             "v": jnp.zeros((rows, cfg.num_heads, max_len, head_dim), dtype),
             "index": jnp.asarray(0, jnp.int32)}
            for _ in range(cfg.num_decoder_layers)
        ]
        pe = A.positional_encoding(max_len, cfg.d_model, dtype)

        def run_step(tokens, caches):
            with reuse_names():
                pos = caches[0]["index"]
                with name_scope("trg"):
                    x = L.embedding(tokens, size=[cfg.trg_vocab, cfg.d_model],
                                    dtype=cfg.dtype) * (cfg.d_model ** 0.5)
                x = x[:, None, :]  # [rows, 1, d_model]
                x = x + jax.lax.dynamic_slice_in_dim(pe, pos, 1, axis=0)[None]
                new_caches = []
                with name_scope("decoder"):
                    for li in range(cfg.num_decoder_layers):
                        x, c = decoder_layer(x, enc_out, cfg, None, src_mask,
                                             cache=caches[li])
                        new_caches.append(c)
                    x = L.layer_norm(x, begin_norm_axis=2)
                helper = LayerHelper("logits_proj")
                w = helper.create_parameter("w", (cfg.d_model, cfg.trg_vocab), dtype,
                                            initializer=init.Xavier())
                logits = jnp.matmul(x[:, 0], w)
                return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), new_caches

        # materialize params once outside the scan (init-mode safety)
        _, caches0 = run_step(jnp.full((rows,), bos_id, jnp.int32), caches)
        del caches0
        if K > 1:
            seqs, scores = beam_search(run_step, caches, b, K, max_len,
                                       bos_id=bos_id, eos_id=eos_id,
                                       length_penalty_alpha=length_penalty_alpha)
            return {"ids": seqs, "scores": scores}
        seqs = greedy_search(run_step, caches, rows, max_len, bos_id=bos_id,
                             eos_id=eos_id)
        return {"ids": seqs}

    return decode_program


def make_model(cfg: TransformerConfig):
    """Program fn: (src_ids[b,s], trg_ids[b,t], labels[b,t]) -> dict.
    Loss = label-smoothed CE over non-pad target tokens, matching the
    reference benchmark's objective."""

    def transformer(src_ids, trg_ids, labels):
        enc_out, src_mask = encode(src_ids, cfg)
        eps = cfg.label_smooth_eps
        lab = labels.astype(jnp.int32)
        nonpad = (labels != 0).astype(jnp.float32)
        token_count = jnp.maximum(nonpad.sum(), 1.0)
        if cfg.fused_ce:
            # Chunked projection+CE: never materializes [b,t,vocab]
            # logits (ops/fused_ce.py) — the LM-head HBM hot spot.
            x, w = decode_hidden(trg_ids, enc_out, src_mask, cfg)
            b, t, d = x.shape
            loss = softmax_cross_entropy_sum(
                x.reshape(b * t, d), w, None, lab.reshape(-1),
                (nonpad / token_count).reshape(-1), eps, cfg.ce_chunk)
            return {"loss": loss, "token_count": token_count}
        logits = decode(trg_ids, enc_out, src_mask, cfg)
        # Label-smoothed CE without materializing a [b,t,vocab] one-hot:
        # loss = (1-eps)·NLL(target) + eps·mean(-logp) — algebraically
        # identical to smoothing over the uniform prior, HBM-friendly.
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        ce = (1.0 - eps) * nll - eps * jnp.mean(logp, axis=-1)
        loss = jnp.sum(ce * nonpad) / token_count
        return {"loss": loss, "logits": logits, "token_count": token_count}

    return transformer
