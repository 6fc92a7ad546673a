"""Trinity (``model_type: afmoe``, Arcee) — the serving path of one rank of
an expert-parallel stage.

The published model: gated grouped-query attention (``layers/gqa.py``) in
every layer, ``layer_types`` saying which layers see a sliding window (and
rotate their queries and keys) and which see everything (and rotate
nothing); *sandwich norms*, an RMSNorm before and one after the mixer and
the FFN alike (four a layer); layers with index below ``num_dense_layers``
with a dense gated FFN, the others with ``num_experts`` routed experts,
``num_experts_per_tok`` a token by sigmoid scores with a selection bias,
normalised and scaled by ``route_scale`` (``parallel/moe.py``
:func:`~paddle_tpu.parallel.moe.sigmoid_topk_route`), plus a shared expert;
the embedding times ``sqrt(hidden_size)`` (``mup_enabled``); an untied head.

No chip holds a layer's 256 experts, so the config says which share this
program holds, as ``models/kimi_k2.py``'s does: ``experts_held`` contiguous
experts from ``first_expert`` in every expert layer (the router still scores
all ``num_experts`` and takes the published count; the layer computes the
part of the result its own experts give,
:func:`~paddle_tpu.parallel.moe.moe_held`, and adds the shared expert),
``vocab_size`` rows of the vocabulary, and ``num_hidden_layers`` layers from
published index ``first_layer`` (the rest lie on further chips as pipeline
stages). Nothing stands in for the absent chips or their exchange.

This module serves only: :func:`make_generator` and :func:`make_scorer`,
through the contract of ``layers/decoding.py`` (the first step's plain form:
a step writes its caches at one row). No ``make_model``: the flash backward
takes neither a window nor grouped heads (ROADMAP R4).

**What is carried**, two shapes by layer type: a window layer's ring of the
last ``sliding_window`` keys and values, ``[rows, window, kv_heads * hd]``
each (``layers/kv_ring.py``), and a full layer's every key and value,
``[rows, T, kv_heads * hd]``, ``T`` the request's length padded to the flash
kernel's key blocks, so that a prefill piece hands the kernel the cache as
it lies. ``decode.plan`` says how much each is (``window_kv_bytes``,
``full_kv_bytes``).

**The prefill** walks the prompt a piece of ``prefill_chunk`` tokens at a
time (``decoding.chunked_walk``) through every layer: a window layer's piece
reads what the window held and its own keys, a full layer's everything
written so far. The layers are written out, each with its own parameters
(``layer_<published index>/...``): the stack is not uniform.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..core.errors import enforce
from ..framework import LayerHelper, name_scope
from ..layers import blocks as B
from ..layers import decoding, kv_ring
from ..layers import gqa as M
from ..ops.flash_attention import padded_keys
from ..parallel import moe

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class TrinityConfig:
    """Published key names where the meaning is the published one; the held
    share beside them."""
    vocab_size: int = 200192            # rows of the vocabulary held here
    hidden_size: int = 3072
    num_hidden_layers: int = 60         # layers held here, from ``first_layer``
    num_dense_layers: int = 6           # published: layers below it are dense
    intermediate_size: int = 12288      # the dense layers' FFN width
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    # published: three sliding layers, then a full one, fifteen times; a
    # layer's kind is ``layer_types[its published index]``
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 15
    num_experts: int = 256              # the router's width (all experts)
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    moe_intermediate_size: int = 3072
    route_scale: float = 2.448
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    mup_enabled: bool = True            # the embedding times sqrt(hidden_size)
    # the share held here
    first_layer: int = 0                # published index of the first layer held
    experts_held: int = 256
    first_expert: int = 0
    prefill_chunk: int = 2048           # tokens a piece of the prefill
    dtype: str = "bfloat16"

    @property
    def dims(self) -> M.GQADims:
        return M.GQADims(self.hidden_size, self.num_attention_heads,
                         self.num_key_value_heads, self.head_dim,
                         self.sliding_window, self.rope_theta,
                         self.rms_norm_eps)

    @property
    def layer_indices(self) -> Tuple[int, ...]:
        return tuple(range(self.first_layer,
                           self.first_layer + self.num_hidden_layers))


# The selection bias as initialised here: small against the scores' spread,
# large enough to decide some selections (the published buffer is moved by
# the load balancer, not by the loss, and starts at zero).
SELECT_BIAS_STD = 0.01


def base_config(**kw) -> TrinityConfig:
    return TrinityConfig(**kw)


def _ffn_params(cfg: TrinityConfig, dense: bool, dtype):
    """A layer's FFN half: the norm before it (``ffn_norm/g``, with the
    dense FFN or the shared expert), the norm after it, and either the dense
    FFN or the shared expert, the router with its selection bias and the
    held experts' banks ``[experts_held, ...]``."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    p = {"post_norm/g": B.params(LayerHelper("ffn_post", name="ffn_post"),
                                 {"norm/g": ((d,), None)}, None,
                                 dtype)["norm/g"]}
    if dense:
        return {**p, **B.gated_ffn_params(d, cfg.intermediate_size, dtype)}
    held = cfg.experts_held
    routed = B.params(LayerHelper("experts", name="experts"), {
        "router/w": ((d, cfg.num_experts), init.Normal(0.0, d ** -0.5)),
        "router/select_bias": ((cfg.num_experts,),
                               init.Normal(0.0, SELECT_BIAS_STD)),
        "gate/w": ((held, d, f), d), "up/w": ((held, d, f), d),
        "down/w": ((held, f, d), f)}, None, dtype)
    return {**p, **B.gated_ffn_params(d, f * cfg.num_shared_experts, dtype,
                                      name="shared"),
            **{(k if k.startswith("router/") else "experts/" + k): v
               for k, v in routed.items()}}


def _ffn_half(cfg: TrinityConfig, x, p):
    """``x + rms(FFN(rms(x; g_pre)); g_post)``, the FFN dense, or the shared
    expert plus the held experts' part."""
    eps = cfg.rms_norm_eps
    h = B.rms_norm(x, p["ffn_norm/g"], eps)
    if "router/w" not in p:
        f = B.gated_ffn(h, p["gate/w"], p["up/w"], p["down/w"])
    else:
        b, s, d = x.shape
        flat = h.reshape(b * s, d)
        experts, weights = moe.sigmoid_topk_route(
            flat, p["router/w"], p["router/select_bias"],
            cfg.num_experts_per_tok, cfg.route_scale)
        routed = moe.moe_held(
            flat, experts, weights, p["experts/gate/w"], p["experts/up/w"],
            p["experts/down/w"], first_expert=cfg.first_expert,
            experts_held=cfg.experts_held, experts_total=cfg.num_experts)
        with jax.named_scope("shared"):
            shared = B.gated_ffn(h, p["gate/w"], p["up/w"], p["down/w"])
        f = (shared.astype(jnp.float32) + routed.reshape(b, s, d)
             ).astype(x.dtype)
    return x + B.rms_norm(f, p["post_norm/g"], eps)


def _decoder(cfg: TrinityConfig, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, by name), the prefill of
    ``prompt_ids`` and the cached step that follows it; no audit."""
    indices = cfg.layer_indices
    enforce(0 < len(indices) and indices[-1] < len(cfg.layer_types),
            f"trinity: layers {indices[:1]}..{indices[-1:]} of "
            f"{len(cfg.layer_types)} layer_types")
    enforce(0 <= cfg.first_expert
            and cfg.first_expert + cfg.experts_held <= cfg.num_experts,
            f"trinity: experts {cfg.first_expert}.."
            f"{cfg.first_expert + cfg.experts_held} of {cfg.num_experts}")
    dims, dtype = cfg.dims, jnp.dtype(cfg.dtype)
    enforce(dims.heads % dims.kv_heads == 0,
            f"trinity: {dims.heads} query heads on {dims.kv_heads} key heads")
    rows, p_len = prompt_ids.shape
    max_len = p_len + max_new_tokens
    decoding.check_length(p_len, max_new_tokens, cfg.max_position_embeddings)
    d, window = cfg.hidden_size, cfg.sliding_window
    sliding = [cfg.layer_types[i] == SLIDING for i in indices]

    # every parameter once, by name; the loops close over the arrays
    w_emb = decoding.token_embedding(cfg.vocab_size, d, dtype)
    per_layer = []
    for i in indices:
        with name_scope(f"layer_{i}"):
            per_layer.append((
                M.attention_params(dims, dtype),
                _ffn_params(cfg, i < cfg.num_dense_layers, dtype)))
    final_g, w_head = decoding.untied_head(cfg.vocab_size, d, dtype)

    def embed(ids):
        with jax.named_scope("tok"):
            x = w_emb[ids]
            if cfg.mup_enabled:
                x = (x.astype(jnp.float32) * math.sqrt(d)).astype(dtype)
            return x

    def head(x_last):   # [rows, d] -> log-probs over the held rows
        with jax.named_scope("head"):
            return decoding.log_probs(
                B.rms_norm(x_last, final_g, cfg.rms_norm_eps), w_head)

    # ---- what is carried: a window's keys and values, or all of them
    # which of its kind's entries a layer has
    slot = [sliding[:l].count(sliding[l]) for l in range(len(indices))]
    kv = lambda length: (jnp.zeros((rows, length, dims.kv_width), dtype),) * 2
    held = [kv(window)] * sliding.count(True)
    full = [kv(padded_keys(max_len))] * sliding.count(False)
    chunk = min(cfg.prefill_chunk, p_len)
    decoding.record_plans("kv", rows, max_len, dims.heads, len(indices),
        cfg.dtype, dims.kv_width, {"window_kv": held, "full_kv": full},
        prefill={"chunk": chunk, "pieces": -(-p_len // chunk)},
        kv_heads=dims.kv_heads, window=window, window_layers=len(held),
        full_layers=len(full), full_len=padded_keys(max_len),
        first_step="conditional")

    # ---- prefill: the prompt a piece at a time through every layer
    def prefill_piece(carried, p0, length):
        held, full = (list(c) for c in carried)
        x = embed(jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1))
        for l, (lp, ffn) in enumerate(per_layer):
            j = slot[l]
            if sliding[l]:
                x, held[j] = M.window_prefill(x, lp, dims, held[j], p0)
            else:
                x, full[j] = M.full_prefill(x, lp, dims, full[j], p0)
            x = _ffn_half(cfg, x, ffn)
        return (held, full), (x[:, -1], ())

    with jax.named_scope("prefill"):
        (held, full), x_last, _ = decoding.chunked_walk(
            prefill_piece, (held, full), p_len, chunk)
        first_logp = head(x_last)
        ring = [kv_ring.ring_of(h, p_len, window) for h in held]

    # ---- one cached step: each layer's one-token form over what it carries
    def layers(tokens, carried, index):
        ring, full = list(carried["ring"]), list(carried["full"])
        x = embed(tokens)[:, None, :]
        for l, (lp, ffn) in enumerate(per_layer):
            j = slot[l]
            if sliding[l]:
                x, ring[j] = M.window_decode(x, lp, dims, ring[j], index)
            else:
                x, full[j] = M.full_decode(x, lp, dims, full[j], index)
            x = _ffn_half(cfg, x, ffn)
        return x, {"ring": ring, "full": full}

    return (decoding.start({"ring": ring, "full": list(full)}, p_len,
                           first_logp),
            decoding.step_in_conditional(layers, head), decoding.no_audit)


# ``make_generator(cfg, max_new_tokens, bos_id=1, eos_id=2)``: greedy
# incremental generation over the rings and the full caches, a program fn
# ``(prompt_ids [b, p]) -> {"ids": [b, max_new_tokens]}``;
# ``make_scorer(cfg)``: the same prefill and step under given continuations
make_generator = functools.partial(decoding.make_generator, _decoder)
make_scorer = functools.partial(decoding.make_scorer, _decoder)


__all__ = ["FULL", "SLIDING", "TrinityConfig", "base_config",
           "make_generator", "make_scorer"]
