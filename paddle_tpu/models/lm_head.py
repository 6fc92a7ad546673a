"""Shared causal-LM output head: vocab projection + next-token CE over
non-pad labels, with the chunked logits-free variant (ops/fused_ce.py,
its gradients made where its logits are) as the production path. Used
by models/gpt.py and models/moe_transformer.py so pad handling and the
fused-CE call cannot diverge between the LM families."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..framework import LayerHelper
from ..ops.fused_ce import softmax_cross_entropy_sum


def lm_head_loss(x, labels, vocab_size: int, dtype, fused_ce: bool,
                 ce_chunk: int, pad_id: int = 0):
    """(loss, token_count) for hidden states x [b, t, d] vs labels
    [b, t]. Creates/fetches the ``lm_head_N/w`` parameter. ``ce_chunk``
    is the rows of a chunk of the fused head."""
    helper = LayerHelper("lm_head")
    w = helper.create_parameter("w", (x.shape[-1], vocab_size), dtype,
                                initializer=init.Xavier())
    with jax.named_scope("ce"):
        lab = labels.astype(jnp.int32)
        nonpad = (labels != pad_id).astype(jnp.float32)
        token_count = jnp.maximum(nonpad.sum(), 1.0)
        b, t, d = x.shape
        if fused_ce:
            # the weights come from the labels: no gradient flows to them
            loss = softmax_cross_entropy_sum(
                x.reshape(b * t, d), w, None, lab.reshape(-1),
                (nonpad / token_count).reshape(-1), 0.0, ce_chunk)
        else:
            logits = jnp.matmul(x, w)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ce = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
            loss = jnp.sum(ce * nonpad) / token_count
    return loss, token_count
