"""Brumby (``model_type: brumby``) — the serving path of a decoder whose
every layer is a power-retention mixer (``layers/retention.py``) and a dense
gated SiLU FFN, pre-normed, the head untied.

``layer_indices`` lists the published indices of the layers held here (a
pipeline stage holds a run of the published stack; every layer is of one
kind, so the index only names the parameters); the rest of the depth lies on
further chips. Nothing stands in for it.

This module serves only: :func:`make_generator`, through the contract of
``layers/decoding.py`` (``prompt_ids [b, p] -> {"ids": [b, new], ...}``, the
first step with its write switch). There is no ``make_model``: no cut of
this model trains on one chip, and neither kernel has a backward (ROADMAP
R5). Matrices are created and held in ``cfg.dtype``; norm scales and the
gate's bias are float32.

The carried state has **no key/value slab**: for each layer one float32
array ``[rows, kv_heads, head_dim + 8, 8704]``, the state with its key sum
(``ops/power_retention.py``), 0.6 GB a layer at 16 rows, the same at every
position, and beside it a window of the last ``W =
ops/power_retention.WINDOW`` tokens a key head (keys, values, log-gates:
0.5 MB a layer at 16 rows and ``W`` 7, also the same at every position):
a step reads every state once, puts its token into the window and folds a
window into one state in ``W``, so it writes an ``W``-th of what it holds.
``decode.plan`` says so (``cache_kind="state"``, ``kv_bytes=0``,
``state_write_bytes``, ``window_bytes``). The states are carried in place
through both loops: the kernels write into the buffer they read, the loops
alias their carry.

The prefill walks the prompt a chunk of the recurrence
(``ops/power_retention.CHUNK`` tokens) at a time (``decoding.chunked_walk``:
a scan, then a tail shorter than a chunk as one more piece); the layers are
written out, each with its own parameters (``layer_<published index>/...``),
as ``models/minicpm_sala.py`` writes its own and for its reason.

**What a request reports of its state.** A state is never returned (0.6 GB a
layer), and a few hundred greedy tokens show little of how it was carried.
So beside ``ids`` a request returns an audit of one key head
(``AUDIT_LAYER``, ``AUDIT_HEAD``): ``audit_k``, ``audit_v [b, t, head_dim]``
and ``audit_log_gamma [b, t]``, what the recurrence was given at each of the
``t = p + new - 1`` positions its state holds, and ``audit_sums [b, head_dim +
8, head_dim]``, that head's sums for the products of dimension 0 as the
request left them (``ops/power_retention.first_products``: a value's
dimensions, the key sum, zeros). The sums are a function of the three by
definition; whoever reads both sees how exactly the state was carried. 12 MB
at 16 rows x 1,279 positions, left on the device unless a caller fetches it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.errors import enforce
from ..framework import name_scope
from ..layers import blocks as B
from ..layers import decoding
from ..layers import retention as R
from ..ops import power_retention


@dataclasses.dataclass
class BrumbyConfig:
    """Published key names."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    num_hidden_layers: int = 40             # layers held here
    layer_indices: Optional[Tuple[int, ...]] = None     # their published indices
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    dtype: str = "bfloat16"

    @property
    def indices(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_hidden_layers))
                if self.layer_indices is None else tuple(self.layer_indices))

    @property
    def retention(self) -> R.RetentionDims:
        return R.RetentionDims(
            self.hidden_size, self.num_attention_heads,
            self.num_key_value_heads, self.head_dim, self.rms_norm_eps,
            self.rope_theta)


def base_config(**kw) -> BrumbyConfig:
    return BrumbyConfig(**kw)


AUDIT_LAYER, AUDIT_HEAD = 0, 0      # the held layer and key head a request audits


def _decoder(cfg: BrumbyConfig, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, by name), the chunked
    prefill of ``prompt_ids``, the one-token step that follows it, and what
    the generator returns of the last state."""
    indices = cfg.indices
    enforce(len(indices) == cfg.num_hidden_layers,
            f"brumby: {cfg.num_hidden_layers} layers, published indices "
            f"{indices}")
    dims, dtype = cfg.retention, jnp.dtype(cfg.dtype)
    rows, p_len = prompt_ids.shape
    decoding.check_length(p_len, max_new_tokens, cfg.max_position_embeddings)
    d, eps = cfg.hidden_size, cfg.rms_norm_eps

    w_emb = decoding.token_embedding(cfg.vocab_size, d, dtype)
    per_layer = []
    for index in indices:
        with name_scope(f"layer_{index}"):
            per_layer.append((R.retention_params(dims, dtype),
                              B.gated_ffn_params(d, cfg.intermediate_size,
                                                 dtype)))
    final_g, w_head = decoding.untied_head(cfg.vocab_size, d, dtype)

    def embed(ids):
        with jax.named_scope("tok"):
            return w_emb[ids]

    def head(x_last):   # [rows, d] -> log-probs
        with jax.named_scope("head"):
            return decoding.log_probs(B.rms_norm(x_last, final_g, eps), w_head)

    states = [power_retention.empty_state(rows, dims.kv_heads, dims.head_dim)
              ] * len(per_layer)
    windows = [power_retention.empty_window(rows, dims.kv_heads, dims.head_dim,
                                            dtype)] * len(per_layer)
    chunk = min(power_retention.CHUNK, p_len)
    # a carried state, a window of tokens beside it and nothing else: of what
    # is held, a value's rows and the key sum's; a step writes back one state
    # of every ``WINDOW`` (a last short run's too)
    held = decoding.nbytes(states)
    high = dims.head_dim + power_retention.NORM_ROWS
    pairs = rows * cfg.num_key_value_heads
    runs = -(-pairs // power_retention.WINDOW)
    decoding.record_plans(
        "state", rows, p_len + max_new_tokens, cfg.num_attention_heads,
        cfg.num_hidden_layers, "float32", 0,
        {"kv": 0, "state": held * dims.head_dim // high,
         "norm": held * power_retention.NORM_ROWS // high},
        prefill={"chunk": chunk, "chunks": p_len // chunk},
        state_layers=len(states), state_dtype="float32",
        state_write_bytes=held * runs // pairs,
        window_bytes=decoding.nbytes(windows), first_step="write_switch")
    hd = dims.head_dim
    at = slice(AUDIT_HEAD * hd, (AUDIT_HEAD + 1) * hd)

    def through(x, carried, mix):
        """The layers over ``x [rows, s, d]``, each with what it carries
        (``mix(x, layer_params, carried) -> (x, carried, given)``);
        ``given``: what the audited head's recurrence was handed, ``(k, v
        [rows, s, hd], log gamma [rows, s])``."""
        carried = list(carried)
        for i, (lp, ffn) in enumerate(per_layer):
            x, carried[i], (k, v, log_gamma) = mix(x, lp, carried[i])
            if i == AUDIT_LAYER:
                given = (k[..., at], v[..., at], log_gamma[..., AUDIT_HEAD])
            x = B.ffn_block(x, ffn, eps, gate_dtype=dtype, sum_in_scope=True)
        return x, carried, given

    # ---- prefill: the prompt a chunk at a time
    def prefill_piece(states, p0, length):
        ids = jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1)
        x, states, given = through(
            embed(ids), states,
            lambda x, lp, s: R.retention_prefill(x, lp, dims, s, p0))
        return states, (x[:, -1], given)

    with jax.named_scope("prefill"):
        states, x_last, seen = decoding.chunked_walk(prefill_piece, states,
                                                     p_len, chunk)
        first_logp = head(x_last)
    # a layer carries on what the prefill left and an empty window beside it
    state0 = decoding.start(
        {"s": list(zip(states, windows))}, p_len, first_logp,
        decoding.audit_log(rows, max_new_tokens, [
            ((hd,), dtype), ((hd,), dtype), ((), jnp.float32)]))

    # ---- one step: each layer's one-token form over its own state and
    # window, the state's update switched off in the first
    # (``retention_decode``)
    def layers(tokens, carried, index, first):
        x, new, given = through(
            embed(tokens)[:, None, :], carried["s"],
            lambda x, lp, sw: R.retention_decode(x, lp, dims, sw, index,
                                                 ~first))
        return x, {"s": new}, given

    def audit(state):
        """The generator's ``audit_*`` outputs from the loop's last state."""
        with jax.named_scope("audit"):
            k, v, log_gamma = decoding.audit_join(seen, state, max_new_tokens)
            return {"audit_k": k, "audit_v": v, "audit_log_gamma": log_gamma,
                    # (the last token went in at ``index - 1``; what the
                    # head's window still holds of it and those before is
                    # added to the slice, not folded into the state)
                    "audit_sums": power_retention.folded_first_products(
                        *state["s"][AUDIT_LAYER], state["index"] - 1,
                        AUDIT_HEAD)}

    return (state0, decoding.step_with_write_switch(layers, head, p_len),
            audit)


# ``make_generator(cfg, max_new_tokens, bos_id=1, eos_id=2)``: greedy
# incremental generation over the carried states, a program fn ``(prompt_ids
# [b, p]) -> {"ids": [b, max_new_tokens], "audit_k", "audit_v",
# "audit_log_gamma", "audit_sums"}`` (the module's docstring says what the
# audit holds)
make_generator = functools.partial(decoding.make_generator, _decoder)


__all__ = ["BrumbyConfig", "base_config", "make_generator"]
