"""Phi-4-mini-flash (``model_type: phi4flash``) — the serving path of a SambaY
decoder-hybrid-decoder (arXiv:2507.06607): a *self-decoder* of Mamba-1 and
sliding-window attention layers that ends in one full-attention layer, and a
*cross-decoder* of gated memory units and cross-attention layers that read
what the self-decoder left: the last Mamba layer's output before its gate
(the memory) and the full-attention layer's keys and values. Every layer is
a mixer (``layers/sambay.py``) and a gated SiLU FFN, pre-normed with
LayerNorm; no positions; the head is the embedding.

Which mixer a layer has follows from its index ``l`` of ``num_hidden_layers
= L`` (:func:`mixer_kinds`; published: 32): even ``l <= L / 2`` Mamba, odd
``l < L / 2 + 1`` window attention, ``l = L / 2 + 1`` full attention, then
even ``l`` a gated memory unit and odd ``l`` cross-attention.

This module serves only: :func:`make_generator`, through the contract of
``layers/decoding.py`` (``prompt_ids [b, p] -> {"ids": [b, new], ...}``, the
first step with its write switch). No ``make_model``:
``ops/selective_scan.py`` and the windowed flash call have no backward
(ROADMAP Reach).

**What is carried**, three kinds side by side and a fourth that is none:
a Mamba layer's convolution tail ``[rows, 3, d_inner]`` and float32 state
``[rows, d_state, d_inner]``; a window layer's ring of the last ``window``
keys and values ``[rows, window, kv_heads * hd]`` each; the full-attention
layer's keys and values ``[rows, max_len, kv_heads * hd]``, stored once and
read by that layer and by every cross layer; the gated memory units and the
cross layers carry nothing. ``decode.plan`` says how much each is.

**The prefill** walks the prompt a piece of ``cfg.prefill_chunk`` tokens at
a time (``decoding.chunked_walk``: a scan, then a shorter tail as one more
piece) through the layers below the full-attention layer, and writes that
layer's keys and values. Everything above reads
nothing of a prompt position but those keys and values, and the first token
needs the last position's output only: so the full-attention layer's own
query and FFN and the whole cross-decoder run at the last prompt position
alone, in their one-token forms (``prefill.plan``: ``self_layers``,
``kv_layers``, ``cross_positions``). That is the published design's linear
prefill, taken one layer further than the paper words it.

The layers are written out, each with its own parameters (``layer_<l>/...``),
as ``models/minicpm_sala.py`` writes its own and for its reason: the stack is
not uniform, and a scanned stack slices every weight out of a stacked array
on each turn (PERF.md section 6, PR 33).

**What a request reports of its state.** Beside ``ids``, an audit of Mamba
layer ``AUDIT_LAYER``'s first ``AUDIT_CHANNELS`` channels: what their
recurrence was given at every position (``audit_delta``, ``audit_u [b, t,
channels]``, ``audit_b [b, t, d_state]``) and their state as the request
left it (``audit_state [b, d_state, channels]``), which is a function of the
three by definition; whoever reads both sees how exactly the state was
carried across pieces and steps (``benchmarks/families/phi4_flash.py``).
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
from ..layers import decoding, kv_ring
from ..layers import sambay as S

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def mixer_kinds(num_layers: int) -> Tuple[str, ...]:
    """The published pattern for a stack of ``num_layers`` (a multiple of
    4): the self-decoder is layers ``0 .. num_layers / 2 + 1``."""
    half = num_layers // 2
    return tuple(
        MAMBA if l <= half and l % 2 == 0 else
        WINDOW if l < half else
        FULL if l == half + 1 else
        GMU if l % 2 == 0 else CROSS for l in range(num_layers))


@dataclasses.dataclass
class Phi4FlashConfig:
    """Published key names; the four ``mamba_*`` are the published
    ``Phi4FlashConfig``'s (its defaults)."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0              # 0: "auto", ceil(hidden_size / 16)
    prefill_chunk: int = 512            # tokens a piece of the prefill
    dtype: str = "bfloat16"

    @property
    def dims(self) -> S.SambaDims:
        d = self.hidden_size
        return S.SambaDims(
            d, self.num_attention_heads, self.num_key_value_heads,
            d // self.num_attention_heads, self.sliding_window,
            self.mamba_expand * d, self.mamba_d_state, self.mamba_d_conv,
            self.mamba_dt_rank or -(-d // 16), self.layer_norm_eps)


def base_config(**kw) -> Phi4FlashConfig:
    return Phi4FlashConfig(**kw)


AUDIT_LAYER, AUDIT_CHANNELS = 0, 128    # the Mamba layer and channels audited

_PARAMS = {MAMBA: S.mamba_params, WINDOW: S.attention_params,
           FULL: S.attention_params, GMU: S.gmu_params, CROSS: S.cross_params}


def _decoder(cfg: Phi4FlashConfig, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, by name), the prefill of
    ``prompt_ids``, the one-token step that follows it, and what the
    generator returns of the last state."""
    kinds = mixer_kinds(cfg.num_hidden_layers)
    enforce(cfg.num_hidden_layers % 4 == 0 and cfg.num_hidden_layers >= 4,
            f"phi4_flash: {cfg.num_hidden_layers} layers are no whole "
            f"number of the pattern's fours")
    dims, dtype = cfg.dims, jnp.dtype(cfg.dtype)
    enforce(dims.heads % 4 == 0 and dims.kv_heads * 2 == dims.heads,
            f"phi4_flash: differential attention pairs {dims.heads} query "
            f"heads over {dims.kv_heads} key heads, two to one")
    rows, p_len = prompt_ids.shape
    max_len = p_len + max_new_tokens
    decoding.check_length(p_len, max_new_tokens, cfg.max_position_embeddings)
    d, eps, full = cfg.hidden_size, cfg.layer_norm_eps, kinds.index(FULL)

    w_emb = decoding.token_embedding(cfg.vocab_size, d, dtype)
    per_layer = []
    for l, kind in enumerate(kinds):
        with name_scope(f"layer_{l}"):
            per_layer.append((_PARAMS[kind](dims, dtype),
                              S.ffn_params(dims, cfg.intermediate_size, dtype)))
    helper = LayerHelper("final_norm")
    final = [helper.create_parameter(n, (d,), jnp.float32,
                                     initializer=init.Constant(c))
             for n, c in (("g", 1.0), ("b", 0.0))]

    def embed(ids):
        with jax.named_scope("tok"):
            return w_emb[ids]

    def head(x_last):   # [rows, d] -> log-probs; the head is the embedding
        with jax.named_scope("head"):
            return jax.nn.log_softmax(jnp.einsum(
                "rd,vd->rv", B.layer_norm(x_last, *final, eps), w_emb,
                preferred_element_type=jnp.float32), axis=-1)

    ffn_of = functools.partial(B.ffn_block, eps=eps, norm="layer",
                               gate_dtype=dtype, sum_in_scope=True)

    # ---- what is carried
    n_mamba, n_window = kinds.count(MAMBA), kinds.count(WINDOW)
    # which of its kind's entries a layer has
    slot = [kinds[:l].count(k) for l, k in enumerate(kinds)]
    mamba = [(jnp.zeros((rows, dims.d_conv - 1, dims.d_inner), dtype),
              jnp.zeros((rows, dims.d_state, dims.d_inner), jnp.float32))
             ] * n_mamba
    held = [(jnp.zeros((rows, dims.window, dims.kv_width), dtype),) * 2
            ] * n_window
    shared = (jnp.zeros((rows, max_len, dims.kv_width), dtype),) * 2
    chunk = min(cfg.prefill_chunk, p_len)
    # the three kinds of carry apart; how the prompt is walked and what the
    # skip leaves out
    decoding.record_plans(
        "state+window+shared", rows, max_len, cfg.num_attention_heads,
        cfg.num_hidden_layers, cfg.dtype, dims.kv_width,
        {"state": mamba, "window_kv": held, "shared_kv": shared},
        prefill={"chunk": chunk, "pieces": -(-p_len // chunk),
                 "self_layers": full, "kv_layers": 1,
                 "cross_layers": len(kinds) - full, "cross_positions": 1},
        state_layers=n_mamba, state_dtype="float32", window_layers=n_window,
        shared_kv_readers=1 + kinds.count(CROSS),
        carry_free_layers=kinds.count(GMU) + kinds.count(CROSS),
        kv_bytes=decoding.nbytes((held, shared)), first_step="write_switch")
    at = slice(0, min(AUDIT_CHANNELS, dims.d_inner))
    audit_slot = [l for l, k in enumerate(kinds) if k == MAMBA][AUDIT_LAYER]

    def audited(given):
        """The audited channels of what a recurrence was handed."""
        delta, u, b = given
        return delta[..., at], u[..., at], b

    # ---- the self-decoder's lower part over a piece, and K*, V* of it
    def prefill_piece(carried, p0, length):
        mamba, held, shared = (list(c) for c in carried)
        x = embed(jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1))
        for l in range(full):
            lp, ffn = per_layer[l]
            j = slot[l]
            if kinds[l] == MAMBA:
                x, mamba[j], memory, handed = S.mamba_prefill(x, lp, dims,
                                                              mamba[j])
                if l == audit_slot:
                    given = audited(handed)
            else:
                x, held[j] = S.window_prefill(x, lp, dims, held[j], p0, l)
            x = ffn_of(x, ffn)
        shared = S.shared_kv(x, per_layer[full][0], dims, shared, p0)
        return (mamba, held, tuple(shared)), ((x[:, -1], memory[:, -1]), given)

    # ---- everything from the full-attention layer up, for one position
    def upper(x, memory, shared, index):
        """``x [rows, 1, d]`` into the full-attention layer at position
        ``index``, ``memory [rows, 1, d_inner]`` the same token's."""
        for l in range(full, len(kinds)):
            lp, ffn = per_layer[l]
            if kinds[l] == FULL:
                x, shared = S.shared_decode(x, lp, dims, shared, index, l)
            elif kinds[l] == GMU:
                x = S.gmu(x, lp, dims, memory)
            else:
                x = S.cross_decode(x, lp, dims, shared, index, l)
            x = ffn_of(x, ffn)
        return x, shared

    with jax.named_scope("prefill"):
        (mamba, held, shared), (x_last, m_last), seen = decoding.chunked_walk(
            prefill_piece, (mamba, held, shared), p_len, chunk)
        x_last, shared = upper(x_last[:, None], m_last[:, None], shared,
                               jnp.asarray(p_len - 1, jnp.int32))
        first_logp = head(x_last[:, 0])
    width = at.stop
    ring = [kv_ring.ring_of(h, p_len, dims.window) for h in held]
    state0 = decoding.start(
        {"mamba": list(mamba), "ring": ring, "shared": shared}, p_len,
        first_logp, decoding.audit_log(
            rows, max_new_tokens,
            [((width,), jnp.float32)] * 2 + [((dims.d_state,), jnp.float32)]))

    # ---- one step: each layer's one-token form over what it carries. In the
    # first a Mamba layer keeps its state (``write``); what the attention
    # layers put at position p the next step writes over
    def layers(tokens, carried, index, first):
        mamba, ring = list(carried["mamba"]), list(carried["ring"])
        x = embed(tokens)[:, None, :]
        for l in range(full):
            lp, ffn = per_layer[l]
            j = slot[l]
            if kinds[l] == MAMBA:
                x, mamba[j], memory, handed = S.mamba_decode(
                    x, lp, dims, mamba[j], ~first)
                if l == audit_slot:
                    given = audited(handed)
            else:
                x, ring[j] = S.window_decode(x, lp, dims, ring[j], index, l)
            x = ffn_of(x, ffn)
        x, shared = upper(x, memory, carried["shared"], index)
        return x, {"mamba": mamba, "ring": ring, "shared": shared}, given

    def audit(state):
        """The generator's ``audit_*`` outputs from the loop's last state."""
        with jax.named_scope("audit"):
            delta, u, b = decoding.audit_join(seen, state, max_new_tokens)
            return {"audit_delta": delta, "audit_u": u, "audit_b": b,
                    "audit_state": state["mamba"][AUDIT_LAYER][1][..., at]}

    return (state0, decoding.step_with_write_switch(layers, head, p_len),
            audit)


# ``make_generator(cfg, max_new_tokens, bos_id=1, eos_id=2)``: greedy
# incremental generation over the carried states and caches, a program fn
# ``(prompt_ids [b, p]) -> {"ids": [b, max_new_tokens], "audit_delta",
# "audit_u", "audit_b", "audit_state"}`` (the module's docstring says what the
# audit holds)
make_generator = functools.partial(decoding.make_generator, _decoder)


__all__ = ["CROSS", "FULL", "GMU", "MAMBA", "Phi4FlashConfig", "WINDOW",
           "base_config", "make_generator", "mixer_kinds"]
