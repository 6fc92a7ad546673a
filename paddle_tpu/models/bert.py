"""BERT-base pretraining — the BASELINE "BERT-base pretraining
(ParallelExecutor multi-chip allreduce)" config. Encoder shares the
transformer blocks; heads = masked-LM + next-sentence, trained with
AdamW/Lamb over a dp/fsdp/tp mesh."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from .. import layers as L
from ..framework import LayerHelper, maybe_remat, name_scope
from ..layers import attention as A
from .. import initializer as init
from .transformer import TransformerConfig, encoder_layer


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    max_len: int = 512
    type_vocab: int = 2
    d_model: int = 768
    d_inner: int = 3072
    num_heads: int = 12
    num_layers: int = 12
    dropout: float = 0.1
    use_flash: bool = False
    # fused [d,3,d] QKV projection (layers/attention.py fuse_qkv)
    fuse_qkv: bool = False
    # chunked logits-free CE for the MLM head (ops/fused_ce.py): never
    # materializes [b, masked, vocab] logits, and sidesteps the
    # involuntary-remat resharding XLA's partitioner hits on the dense
    # head's scatter-grad under fsdp
    fused_ce: bool = False
    ce_chunk: int = 4096         # rows of a chunk, the whole vocabulary each
    # per-block jax.checkpoint over encoder layers (memory_optimize analog)
    remat: bool = False
    dtype: str = "float32"


def base_config(**kw) -> BertConfig:
    return BertConfig(**kw)


def encode(input_ids, token_type_ids, cfg: BertConfig):
    dtype = jnp.dtype(cfg.dtype)
    with name_scope("word"):
        x = L.embedding(input_ids, size=[cfg.vocab_size, cfg.d_model], dtype=dtype)
    with name_scope("pos"):
        helper = LayerHelper("pos_table")
        pos = helper.create_parameter("w", (cfg.max_len, cfg.d_model), dtype,
                                      initializer=init.Normal(0, 0.02))
        x = x + pos[None, :input_ids.shape[1]]
    with name_scope("type"):
        x = x + L.embedding(token_type_ids, size=[cfg.type_vocab, cfg.d_model], dtype=dtype)
    x = L.layer_norm(x, begin_norm_axis=2)
    x = L.dropout(x, cfg.dropout, dropout_implementation="upscale_in_train")

    mask = A.padding_mask(input_ids)
    tcfg = TransformerConfig(d_model=cfg.d_model, d_inner=cfg.d_inner,
                             num_heads=cfg.num_heads, dropout=cfg.dropout,
                             use_flash=cfg.use_flash, fuse_qkv=cfg.fuse_qkv,
                             dtype=cfg.dtype)
    with name_scope("encoder"):
        for _ in range(cfg.num_layers):
            # fresh wrapper per layer (jax.checkpoint caches per fn object)
            x = maybe_remat(lambda a, m: encoder_layer(a, tcfg, m),
                            enabled=cfg.remat or None)(x, mask)
        x = L.layer_norm(x, begin_norm_axis=2)
    return x


def make_pretrain_model(cfg: BertConfig):
    """Program fn: (input_ids, token_type_ids, mlm_positions, mlm_labels,
    nsp_label) -> dict. mlm_positions: [b, num_masked] gather indices."""

    def bert(input_ids, token_type_ids, mlm_positions, mlm_labels, nsp_label):
        seq = encode(input_ids, token_type_ids, cfg)
        dtype = seq.dtype

        # masked LM head
        b = seq.shape[0]
        gathered = jnp.take_along_axis(
            seq, mlm_positions[..., None].astype(jnp.int32), axis=1)  # [b, m, d]
        h = L.fc(gathered, cfg.d_model, num_flatten_dims=2, act="gelu", name="mlm_transform")
        h = L.layer_norm(h, begin_norm_axis=2)
        helper = LayerHelper("mlm_out")
        w = helper.create_parameter("w", (cfg.d_model, cfg.vocab_size), dtype,
                                    initializer=init.Normal(0, 0.02))
        bias = helper.create_parameter("b", (cfg.vocab_size,), dtype,
                                       initializer=init.Constant(0.0))
        if cfg.fused_ce:
            from ..ops.fused_ce import softmax_cross_entropy_sum
            rows = b * h.shape[1]
            mlm_loss = softmax_cross_entropy_sum(
                h.reshape(rows, cfg.d_model), w, bias, mlm_labels.reshape(-1),
                jnp.full((rows,), 1.0 / rows), 0.0, cfg.ce_chunk)
        else:
            mlm_logits = jnp.matmul(h, w) + bias
            mlm_loss = L.mean(L.softmax_with_cross_entropy(mlm_logits, mlm_labels))

        # next-sentence head over [CLS]
        pooled = L.fc(seq[:, 0], cfg.d_model, act="tanh", name="pooler")
        nsp_logits = L.fc(pooled, 2, name="nsp_out")
        nsp_loss = L.mean(L.softmax_with_cross_entropy(nsp_logits, nsp_label))

        loss = mlm_loss + nsp_loss
        return {"loss": loss, "mlm_loss": mlm_loss, "nsp_loss": nsp_loss}

    return bert
