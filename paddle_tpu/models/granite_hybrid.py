"""Granite 4.0-H (``model_type: granitemoehybrid``, IBM) — the serving path of
one rank of an expert-parallel stage.

The published model: ``layer_types`` says which layers mix by Mamba-2
(``layers/mamba2.py`` round the state-space-dual kernel of ``ops/ssd.py``)
and which by plain grouped-query attention with no positional encoding at
all (``layers/gqa.py``: no rotation, no gate, the softmax scale
``attention_multiplier``), one in ten; every layer's second half is
``num_local_experts`` routed experts, ``num_experts_per_tok`` a token by a
softmax over the selected logits (``parallel/moe.py``
:func:`~paddle_tpu.parallel.moe.softmax_topk_route`), plus a shared expert;
two RMSNorms a layer, one before each half. Four multipliers: the embedding
times ``embedding_multiplier``, each half summed into the stream times
``residual_multiplier``, the attention's scores times
``attention_multiplier``, the logits over ``logits_scaling``. The head is
the embedding.

No chip holds a layer's 72 experts beside ten layers of mixers, so the
config says which share this program holds, as ``models/trinity.py``'s does:
``experts_held`` contiguous experts from ``first_expert`` in every layer
(the router still scores all ``num_local_experts`` and takes the published
count; the layer computes the part of the result its own experts give,
:func:`~paddle_tpu.parallel.moe.moe_held`, and adds the shared expert),
``vocab_size`` rows of the vocabulary, and ``num_hidden_layers`` layers from
published index ``first_layer``. Nothing stands in for the absent chips or
their exchange.

This module serves only: :func:`make_generator` and :func:`make_scorer`,
through the contract of ``layers/decoding.py`` (the first step with its write
switch: the states are arrays a fusion writes in place). No ``make_model``:
``ops/ssd.py`` has no backward (ROADMAP R5).

**What is carried**, two kinds side by side: a Mamba-2 layer's convolution
last three inputs ``[3, rows, d_inner + 2 d_state]`` and float32 state ``[rows, groups,
d_state, 128]`` (4 MB a row and layer at the published sizes), and an
attention layer's keys and values ``[rows, T, kv_heads * hd]``, ``T`` the
request's length padded to the flash kernel's key blocks. ``decode.plan``
says how much each is (``state_bytes``, ``tail_bytes``, ``kv_bytes``).

**The prefill** walks the prompt a piece of ``prefill_chunk`` tokens at a
time (``decoding.chunked_walk``; a multiple of the recurrence's chunk)
through every layer. The layers are written out, each with its own
parameters (``layer_<published index>/...``): the stack is not uniform.

**What a request reports of its state.** Beside ``ids``, an audit of the
first Mamba-2 layer's first lane group of heads (``AUDIT_LANES`` channels):
what their recurrence was given at every position (``audit_dt [b, t,
heads]``, ``audit_x [b, t, lanes]``, ``audit_b [b, t, d_state]``) and their
state as the request left it (``audit_state [b, d_state, lanes]``), which is a
function of the three by definition (``benchmarks/families/granite_hybrid.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..core.errors import enforce
from ..framework import LayerHelper, name_scope
from ..layers import blocks as B
from ..layers import decoding
from ..layers import gqa as G
from ..layers import mamba2 as M
from ..ops.flash_attention import padded_keys
from ..ops.ssd import LANES
from ..parallel import moe

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass
class GraniteHybridConfig:
    """Published key names where the meaning is the published one; the held
    share beside them."""
    vocab_size: int = 100352            # rows of the vocabulary held here
    hidden_size: int = 4096
    num_hidden_layers: int = 40         # layers held here, from ``first_layer``
    # published: attention at layers 5, 15, 25, 35, Mamba-2 everywhere else;
    # a layer's kind is ``layer_types[its published index]``
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,)
                                    + (MAMBA,) * 4) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 128                 # hidden_size / num_attention_heads
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    intermediate_size: int = 768        # one routed expert's width
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72         # the router's width (all experts)
    num_experts_per_tok: int = 10
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    # the share held here
    first_layer: int = 0                # published index of the first layer held
    experts_held: int = 72
    first_expert: int = 0
    prefill_chunk: int = 512            # tokens a piece of the prefill
    pair_block: int = 2048              # (token, expert) pairs a grouped product
    dtype: str = "bfloat16"

    @property
    def mamba_dims(self) -> M.Mamba2Dims:
        return M.Mamba2Dims(self.hidden_size, self.mamba_n_heads,
                            self.mamba_d_head, self.mamba_d_state,
                            self.mamba_d_conv, self.mamba_chunk_size,
                            self.rms_norm_eps,
                            self.residual_multiplier)

    @property
    def attention_dims(self) -> G.GQADims:
        return G.GQADims(self.hidden_size, self.num_attention_heads,
                         self.num_key_value_heads, self.head_dim, 0, 0.0,
                         self.rms_norm_eps)

    @property
    def layer_indices(self) -> Tuple[int, ...]:
        return tuple(range(self.first_layer,
                           self.first_layer + self.num_hidden_layers))


def base_config(**kw) -> GraniteHybridConfig:
    return GraniteHybridConfig(**kw)


AUDIT_LAYER, AUDIT_LANES = 0, LANES     # the Mamba-2 layer and channels audited


def _ffn_params(cfg: GraniteHybridConfig, dtype):
    """A layer's second half: the norm before it (``ffn_norm/g``, with the
    shared expert), the router and the held experts' banks ``[experts_held,
    ...]`` (the published fused input matrix as its gate and up halves)."""
    d, f, held = cfg.hidden_size, cfg.intermediate_size, cfg.experts_held
    routed = B.params(LayerHelper("experts", name="experts"), {
        "router/w": ((d, cfg.num_local_experts), init.Normal(0.0, d ** -0.5)),
        "gate/w": ((held, d, f), d), "up/w": ((held, d, f), d),
        "down/w": ((held, f, d), f)}, None, dtype)
    return {**B.gated_ffn_params(d, cfg.shared_intermediate_size, dtype,
                                 name="shared"),
            **{(k if k.startswith("router/") else "experts/" + k): v
               for k, v in routed.items()}}


def _ffn_half(cfg: GraniteHybridConfig, x, p):
    """``x + residual * (shared(u) + the held experts' part)``, ``u =
    rms(x)``."""
    b, s, d = x.shape
    u = B.rms_norm(x, p["ffn_norm/g"], cfg.rms_norm_eps)
    flat = u.reshape(b * s, d)
    experts, weights = moe.softmax_topk_route(flat, p["router/w"],
                                              cfg.num_experts_per_tok)
    routed = moe.moe_held(
        flat, experts, weights, p["experts/gate/w"], p["experts/up/w"],
        p["experts/down/w"], first_expert=cfg.first_expert,
        experts_held=cfg.experts_held, experts_total=cfg.num_local_experts,
        pair_block=cfg.pair_block, back="gather", routing="softmax_topk")
    with jax.named_scope("shared"):
        shared = B.gated_ffn(u, p["gate/w"], p["up/w"], p["down/w"])
    return B.residual(x, shared.astype(jnp.float32) + routed.reshape(b, s, d),
                      cfg.residual_multiplier)


def _decoder(cfg: GraniteHybridConfig, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, by name), the prefill of
    ``prompt_ids``, the one-token step that follows it, and what the
    generator returns of the last state."""
    indices = cfg.layer_indices
    enforce(0 < len(indices) and indices[-1] < len(cfg.layer_types),
            f"granite_hybrid: layers {indices[:1]}..{indices[-1:]} of "
            f"{len(cfg.layer_types)} layer_types")
    enforce(0 <= cfg.first_expert
            and cfg.first_expert + cfg.experts_held <= cfg.num_local_experts,
            f"granite_hybrid: experts {cfg.first_expert}.."
            f"{cfg.first_expert + cfg.experts_held} of {cfg.num_local_experts}")
    kinds = [cfg.layer_types[i] for i in indices]
    enforce(MAMBA in kinds, "granite_hybrid: no Mamba-2 layer among the held")
    mdims, adims, dtype = cfg.mamba_dims, cfg.attention_dims, jnp.dtype(cfg.dtype)
    enforce(adims.heads % adims.kv_heads == 0,
            f"granite_hybrid: {adims.heads} query heads on {adims.kv_heads} "
            f"key heads")
    rows, p_len = prompt_ids.shape
    max_len = p_len + max_new_tokens
    decoding.check_length(p_len, max_new_tokens, cfg.max_position_embeddings)
    d, eps = cfg.hidden_size, cfg.rms_norm_eps
    chunk = min(cfg.prefill_chunk, p_len)
    enforce(chunk == p_len or chunk % cfg.mamba_chunk_size == 0,
            f"granite_hybrid: a piece of {chunk} tokens is no whole number "
            f"of the recurrence's chunks of {cfg.mamba_chunk_size}")

    # every parameter once, by name; the loops close over the arrays
    w_emb = decoding.token_embedding(cfg.vocab_size, d, dtype)
    per_layer = []
    for i, kind in zip(indices, kinds):
        with name_scope(f"layer_{i}"):
            per_layer.append((
                M.mamba2_params(mdims, dtype) if kind == MAMBA
                else G.plain_params(adims, dtype), _ffn_params(cfg, dtype)))
    final_g = LayerHelper("final_norm").create_parameter(
        "g", (d,), jnp.float32, initializer=init.Constant(1.0))

    def embed(ids):
        with jax.named_scope("tok"):
            return (w_emb[ids].astype(jnp.float32) * cfg.embedding_multiplier
                    ).astype(dtype)

    def head(x_last):   # [rows, d] -> log-probs over the held rows; tied
        with jax.named_scope("head"):
            return jax.nn.log_softmax(jnp.einsum(
                "rd,vd->rv", B.rms_norm(x_last, final_g, eps), w_emb,
                preferred_element_type=jnp.float32) / cfg.logits_scaling,
                axis=-1)

    # ---- what is carried: a tail and a state, or keys and values
    # which of its kind's entries a layer has
    slot = [kinds[:l].count(k) for l, k in enumerate(kinds)]
    mamba = [M.empty_carry(rows, mdims, dtype)] * kinds.count(MAMBA)
    kv = [(jnp.zeros((rows, padded_keys(max_len), adims.kv_width), dtype),) * 2
          ] * kinds.count(ATTENTION)
    decoding.record_plans(
        "state+kv", rows, max_len, adims.heads, len(indices), cfg.dtype,
        adims.kv_width,
        {"state": [m[1] for m in mamba], "tail": [m[0] for m in mamba],
         "kv": kv},
        prefill={"chunk": chunk, "pieces": -(-p_len // chunk)},
        state_layers=len(mamba), state_dtype="float32", kv_layers=len(kv),
        kv_heads=adims.kv_heads, full_len=padded_keys(max_len),
        first_step="write_switch")
    lanes = min(AUDIT_LANES, mdims.d_inner)
    audit_slot = [l for l, k in enumerate(kinds) if k == MAMBA][AUDIT_LAYER]

    def audited(given):
        """The audited channels of what a recurrence was handed."""
        dt, xs, b = given
        return tuple(a.astype(jnp.float32) for a in (dt, xs[..., :lanes], b))

    # ---- prefill: the prompt a piece at a time through every layer
    def prefill_piece(carried, p0, length):
        mamba, kv = (list(c) for c in carried)
        x = embed(jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1))
        for l, (lp, ffn) in enumerate(per_layer):
            j = slot[l]
            if kinds[l] == MAMBA:
                x, mamba[j], handed = M.mamba2_prefill(x, lp, mdims, mamba[j])
                if l == audit_slot:
                    given = audited(handed)
            else:
                x, kv[j] = G.plain_prefill(
                    x, lp, adims, kv[j], p0, cfg.attention_multiplier,
                    cfg.residual_multiplier)
            x = _ffn_half(cfg, x, ffn)
        return (mamba, kv), (x[:, -1], given)

    with jax.named_scope("prefill"):
        (mamba, kv), x_last, seen = decoding.chunked_walk(
            prefill_piece, (mamba, kv), p_len, chunk)
        first_logp = head(x_last)
        mamba = [M.ring_of(m, p_len) for m in mamba]
    state0 = decoding.start(
        {"mamba": mamba, "kv": list(kv)}, p_len, first_logp,
        decoding.audit_log(rows, max_new_tokens, [
            ((mdims.heads,), jnp.float32), ((lanes,), jnp.float32),
            ((mdims.d_state,), jnp.float32)]))

    # ---- one step: each layer's one-token form over what it carries. In the
    # first a Mamba-2 layer keeps its state and tail (``write``); what the
    # attention layers put at position p the next step writes over
    def layers(tokens, carried, index, first):
        mamba, kv = list(carried["mamba"]), list(carried["kv"])
        x = embed(tokens)[:, None, :]
        for l, (lp, ffn) in enumerate(per_layer):
            j = slot[l]
            if kinds[l] == MAMBA:
                x, mamba[j], handed = M.mamba2_decode(x, lp, mdims, mamba[j],
                                                      index, ~first)
                if l == audit_slot:
                    given = audited(handed)
            else:
                x, kv[j] = G.plain_decode(
                    x, lp, adims, kv[j], index, cfg.attention_multiplier,
                    cfg.residual_multiplier)
            x = _ffn_half(cfg, x, ffn)
        return x, {"mamba": mamba, "kv": kv}, given

    def audit(state):
        """The generator's ``audit_*`` outputs from the loop's last state."""
        with jax.named_scope("audit"):
            dt, xs, b = decoding.audit_join(seen, state, max_new_tokens)
            return {"audit_dt": dt, "audit_x": xs, "audit_b": b,
                    "audit_state": state["mamba"][AUDIT_LAYER][1][:, 0, :, :lanes]}

    return (state0, decoding.step_with_write_switch(layers, head, p_len),
            audit)


# ``make_generator(cfg, max_new_tokens, bos_id=1, eos_id=2)``: greedy
# incremental generation over the carried states and the cache, a program fn
# ``(prompt_ids [b, p]) -> {"ids": [b, max_new_tokens], "audit_dt",
# "audit_x", "audit_b", "audit_state"}`` (the module's docstring says what the
# audit holds); ``make_scorer(cfg)``: the same prefill and step under given
# continuations
make_generator = functools.partial(decoding.make_generator, _decoder)
make_scorer = functools.partial(decoding.make_scorer, _decoder)


__all__ = ["ATTENTION", "GraniteHybridConfig", "MAMBA", "base_config",
           "make_generator", "make_scorer"]
