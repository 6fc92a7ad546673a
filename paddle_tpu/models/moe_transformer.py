"""MoE transformer LM — GShard/Switch-style causal model whose FFNs are
top-k-routed expert banks sharded over the mesh ``ep`` axis.

The trainable-model realization of `parallel/moe.py` (SURVEY §2.2 gap
row: the reference's only model partitioning is the distributed lookup
table, distribute_transpiler.py:1100-1339 — expert parallelism is its
modern descendant). Every ``moe_every``-th block's FFN is a MoE layer;
the load-balance aux losses are summed into the objective. Built
against a target mesh (pass ``mesh=None`` for the dense single-device
path with identical per-token numerics when capacity permits).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import layers as L
from ..core.errors import enforce
from ..framework import name_scope
from ..layers import attention as A
from ..parallel.moe import moe
from .lm_head import lm_head_loss


@dataclasses.dataclass
class MoeTransformerConfig:
    vocab_size: int = 32000
    max_len: int = 1024
    d_model: int = 512
    d_inner: int = 2048          # dense-block FFN width
    d_expert: int = 1024         # per-expert FFN width
    num_heads: int = 8
    num_layers: int = 6
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2           # every Nth block's FFN is MoE
    aux_weight: float = 0.01     # load-balance loss weight
    dropout: float = 0.0
    use_flash: bool = False
    fused_ce: bool = True
    ce_chunk: int = 4096         # rows of a chunk of the fused head
    dtype: str = "float32"


def base_config(**kw) -> MoeTransformerConfig:
    return MoeTransformerConfig(**kw)


def make_model(cfg: MoeTransformerConfig, mesh=None):
    """Program fn: (ids [b, s], labels [b, s]) -> {"loss", "ce_loss",
    "aux_loss"}. Next-token CE over non-pad labels + aux_weight · Σ
    load-balance losses."""

    def moe_lm(ids, labels):
        dtype = jnp.dtype(cfg.dtype)
        s = ids.shape[1]
        enforce(s <= cfg.max_len, f"seq {s} exceeds max_len {cfg.max_len}")
        with name_scope("tok"):
            x = L.embedding(ids, size=[cfg.vocab_size, cfg.d_model],
                            dtype=cfg.dtype)
        x = x + A.positional_encoding(cfg.max_len, cfg.d_model, dtype)[:s][None]
        x = L.dropout(x, cfg.dropout, dropout_implementation="upscale_in_train")

        aux_total = jnp.float32(0.0)
        with name_scope("blocks"):
            for i in range(cfg.num_layers):
                h = L.layer_norm(x, begin_norm_axis=2)
                h = A.multi_head_attention(h, num_heads=cfg.num_heads,
                                           causal=True,
                                           dropout_rate=cfg.dropout,
                                           use_flash=cfg.use_flash)
                x = x + L.dropout(h, cfg.dropout,
                                  dropout_implementation="upscale_in_train")
                h = L.layer_norm(x, begin_norm_axis=2)
                if cfg.moe_every and (i + 1) % cfg.moe_every == 0:
                    h, aux = moe(h, num_experts=cfg.num_experts,
                                 d_ff=cfg.d_expert, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor,
                                 mesh=mesh)
                    aux_total = aux_total + aux
                else:
                    h = A.ffn(h, cfg.d_inner, dropout_rate=cfg.dropout)
                x = x + L.dropout(h, cfg.dropout,
                                  dropout_implementation="upscale_in_train")
            x = L.layer_norm(x, begin_norm_axis=2)

        ce_loss, _ = lm_head_loss(x, labels, cfg.vocab_size, dtype,
                                  cfg.fused_ce, cfg.ce_chunk)
        loss = ce_loss + cfg.aux_weight * aux_total
        return {"loss": loss, "ce_loss": ce_loss, "aux_loss": aux_total}

    return moe_lm
