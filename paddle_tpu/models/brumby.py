"""Brumby (``model_type: brumby``) — the serving path of a decoder whose
every layer is a power-retention mixer (``layers/retention.py``) and a dense
gated SiLU FFN, pre-normed, the head untied.

``layer_indices`` lists the published indices of the layers held here (a
pipeline stage holds a run of the published stack; every layer is of one
kind, so the index only names the parameters); the rest of the depth lies on
further chips. Nothing stands in for it.

This module serves only: :func:`make_generator`, the contract of
``gpt.make_generator`` (``prompt_ids [b, p] -> {"ids": [b, new], ...}``
through ``greedy_search``). There is no ``make_model``: no cut of this model
trains on one chip, and neither kernel has a backward (ROADMAP R5). Matrices
are created and held in ``cfg.dtype``; norm scales and the gate's bias are
float32.

The carried state has **no key/value slab**: for each layer one float32
array ``[rows, kv_heads, head_dim + 8, 8704]``, the state with its key sum
(``ops/power_retention.py``), 0.6 GB a layer at 16 rows, the same at every
position. ``decode.plan`` says so (``cache_kind="state"``, ``kv_bytes=0``).
It is carried in place through both loops: the kernels write into the
buffer they read, the loops alias their carry.

The prefill walks the prompt a chunk of the recurrence
(``ops/power_retention.CHUNK`` tokens) at a time under one ``lax.scan`` with
no conditional in it (a tail shorter than a chunk follows the scan as one
more piece); the layers are written out, each with its own parameters
(``layer_<published index>/...``), as ``models/minicpm_sala.py`` writes its
own and for its reason.

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
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..core.errors import enforce
from ..framework import LayerHelper, name_scope
from ..layers import latent as M
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


def _ffn_block(x, p, eps: float):
    """``x + FFN(RMSNorm(x))`` as ``layers/latent.ffn_block`` computes it but
    for one rounding: the gate product's result is taken in ``x``'s dtype
    (as the published bfloat16 inference takes it) before the SiLU in
    float32. With a float32 result the compiler walks a one-row step's
    ``[d, width]`` matrix in strips of 512 columns, 16 KB pieces half a
    megabyte apart, and on the chip that walk took 260, 274 or 293 us by a
    level fixed for a process's life; with this result it walks whole rows,
    as it walks ``up`` and ``down``, in 237 us in every process (PERF.md
    section 6, PR 39; ``tests/test_tpu_compile.py`` holds the walk)."""
    h = M.rms_norm(x, p["ffn_norm/g"], eps)
    with jax.named_scope("ffn"):
        gate = jnp.matmul(h, p["gate/w"]).astype(jnp.float32)
        up = jnp.matmul(h, p["up/w"], preferred_element_type=jnp.float32)
        return x + jnp.matmul((jax.nn.silu(gate) * up).astype(x.dtype),
                              p["down/w"])


def _record_plans(cfg: BrumbyConfig, states, rows, max_len, chunk, chunks):
    """``decode.plan`` beside the other generators', with what is new here:
    a carried state and nothing else. ``prefill.plan``: how the prompt is
    walked."""
    from ..core import profiler

    high = cfg.head_dim + power_retention.NORM_ROWS
    held = sum(a.size * a.dtype.itemsize for a in states)
    profiler.record_span(
        "decode.plan", time.time_ns(), 0, rows=rows, max_len=max_len,
        heads=cfg.num_attention_heads, layers=cfg.num_hidden_layers,
        cache_kind="state", cache_dtype="float32", lane_width=0,
        cache_bytes=held, kv_bytes=0, state_bytes=held * cfg.head_dim // high,
        norm_bytes=held * power_retention.NORM_ROWS // high,
        state_layers=len(states), state_dtype="float32")
    profiler.record_span("prefill.plan", time.time_ns(), 0, chunk=chunk,
                         chunks=chunks, rows=rows)


def _decoder(cfg: BrumbyConfig, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)`` for ``layers/beam_search``: the
    parameters (created or fetched here, once, by name), the chunked prefill
    of ``prompt_ids``, the one-token step that follows it, and what the
    generator returns of the last state."""
    indices = cfg.indices
    enforce(len(indices) == cfg.num_hidden_layers,
            f"brumby: {cfg.num_hidden_layers} layers, published indices "
            f"{indices}")
    dims, dtype = cfg.retention, jnp.dtype(cfg.dtype)
    rows, p_len = prompt_ids.shape
    enforce(p_len + max_new_tokens <= cfg.max_position_embeddings,
            f"prompt {p_len} + max_new {max_new_tokens} exceeds "
            f"max_position_embeddings {cfg.max_position_embeddings}")
    d, eps = cfg.hidden_size, cfg.rms_norm_eps

    with name_scope("tok"):
        w_emb = LayerHelper("embedding").create_parameter(
            "w", (cfg.vocab_size, d), dtype, initializer=init.Normal(0.0, 1.0))
    per_layer = []
    for index in indices:
        with name_scope(f"layer_{index}"):
            per_layer.append((R.retention_params(dims, dtype),
                              M.gated_ffn_params(d, cfg.intermediate_size,
                                                 dtype)))
    final_g = LayerHelper("final_norm").create_parameter(
        "g", (d,), jnp.float32, initializer=init.Constant(1.0))
    w_head = LayerHelper("lm_head").create_parameter(
        "w", (d, cfg.vocab_size), dtype,
        initializer=init.Normal(0.0, d ** -0.5))

    def embed(ids):
        with jax.named_scope("tok"):
            return w_emb[ids]

    def head(x_last):   # [rows, d] -> log-probs
        with jax.named_scope("head"):
            return jax.nn.log_softmax(jnp.matmul(
                M.rms_norm(x_last, final_g, eps), w_head,
                preferred_element_type=jnp.float32), axis=-1)

    states = [power_retention.empty_state(rows, dims.kv_heads, dims.head_dim)
              ] * len(per_layer)
    chunk = min(power_retention.CHUNK, p_len)
    whole = p_len // chunk
    _record_plans(cfg, states, rows, p_len + max_new_tokens, chunk, whole)
    hd = dims.head_dim
    at = slice(AUDIT_HEAD * hd, (AUDIT_HEAD + 1) * hd)

    def through(x, states, mix):
        """The layers over ``x [rows, s, d]``; ``given``: what the audited
        head's recurrence was handed, ``(k, v [rows, s, hd], log gamma
        [rows, s])``."""
        states = list(states)
        for i, (lp, ffn) in enumerate(per_layer):
            x, states[i], (k, v, log_gamma) = mix(x, lp, states[i])
            if i == AUDIT_LAYER:
                given = (k[..., at], v[..., at], log_gamma[..., AUDIT_HEAD])
            x = _ffn_block(x, ffn, eps)
        return x, states, given

    # ---- prefill: the prompt a chunk at a time
    def prefill_piece(states, p0, length):
        ids = jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1)
        x, states, given = through(
            embed(ids), states,
            lambda x, lp, s: R.retention_prefill(x, lp, dims, s, p0))
        return states, (x[:, -1], given)

    with jax.named_scope("prefill"):
        if whole == 1:
            states, (x_last, given) = prefill_piece(states, 0, chunk)
            seen = [given]
        else:
            states, (lasts, given) = jax.lax.scan(
                lambda s, p0: prefill_piece(s, p0, chunk), states,
                jnp.arange(whole, dtype=jnp.int32) * chunk)
            x_last = lasts[-1]
            # [pieces, rows, chunk, ...] -> [rows, pieces * chunk, ...]
            seen = [jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1).reshape(
                (rows, whole * chunk) + a.shape[3:]), given)]
        if p_len > whole * chunk:
            states, (x_last, given) = prefill_piece(states, whole * chunk,
                                                    p_len - whole * chunk)
            seen.append(given)
        logp0 = head(x_last)
    # the steps that consume a token: all but the first, which takes logp0
    steps = max(max_new_tokens - 1, 1)
    state0 = {"s": states, "index": jnp.asarray(p_len, jnp.int32),
              "logp0": logp0, "first": jnp.asarray(True),
              "given": (jnp.zeros((rows, steps, hd), dtype),
                        jnp.zeros((rows, steps, hd), dtype),
                        jnp.zeros((rows, steps), jnp.float32))}

    # ---- one step: each layer's one-token form over its own state
    def step_fn(tokens, state):
        index, first = state["index"], state["first"]
        # the first step consumes the prefill's distribution and must write
        # nothing (position p holds the first generated token): the layers
        # run with the state's update switched off, outside the conditional
        # (see ``retention_decode``), and the conditional holds the head
        # alone. What that step leaves in the audit's first place, the next
        # step writes over: both stand at position p.
        with jax.named_scope("decode_step"):
            x, new, given = through(
                embed(tokens)[:, None, :], state["s"],
                lambda x, lp, s: R.retention_decode(x, lp, dims, s, index,
                                                    ~first))
            logp = jax.lax.cond(first, lambda _: state["logp0"],
                                lambda _: head(x[:, 0]), operand=None)
            kept = jax.tree.map(
                lambda log, a: jax.lax.dynamic_update_slice_in_dim(
                    log, a, index - p_len, axis=1), state["given"], given)
        return logp, {"s": new, "logp0": state["logp0"], "given": kept,
                      "index": jnp.where(first, index, index + 1),
                      "first": jnp.asarray(False)}

    def audit(state):
        """The generator's ``audit_*`` outputs from the loop's last state."""
        with jax.named_scope("audit"):
            k, v, log_gamma = (
                jnp.concatenate(parts[:-1] + (parts[-1][:, :max_new_tokens - 1],),
                                axis=1)
                for parts in zip(*seen, state["given"]))
            return {"audit_k": k, "audit_v": v, "audit_log_gamma": log_gamma,
                    "audit_sums": power_retention.first_products(
                        state["s"][AUDIT_LAYER], AUDIT_HEAD)}

    return state0, step_fn, audit


def make_generator(cfg: BrumbyConfig, max_new_tokens: int, bos_id: int = 1,
                   eos_id: int = 2):
    """Greedy incremental generation over the carried states. Returns a
    program fn: ``(prompt_ids [b, p]) -> {"ids": [b, max_new_tokens],
    "audit_k", "audit_v", "audit_log_gamma", "audit_sums"}`` (the module's
    docstring says what the audit holds)."""
    from ..layers.beam_search import greedy_search

    def generate(prompt_ids):
        state0, step_fn, audit = _decoder(cfg, prompt_ids, max_new_tokens)
        ids, state = greedy_search(
            step_fn, state0, prompt_ids.shape[0], max_new_tokens,
            bos_id=bos_id, eos_id=eos_id, with_state=True)
        return {"ids": ids, **audit(state)}

    return generate


__all__ = ["BrumbyConfig", "base_config", "make_generator"]
