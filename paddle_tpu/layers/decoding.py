"""The decoding contract of the served models, and the parts a model builds
its side of it from.

Every served model (``models/gpt.py``, ``kimi_k2.py``, ``minicpm_sala.py``,
``brumby.py``, ``phi4_flash.py``, ``trinity.py``, ``granite_hybrid.py``) has
one function::

    _decoder(cfg, prompt_ids [rows, p], max_new_tokens) -> (state0, step_fn, audit)

``state0`` is what the prefill of ``prompt_ids`` left: the model's carried
arrays under its own keys, and beside them ``index`` (the position the next
token is written at: ``p``), ``logp0`` (the prefill's distribution over the
first generated token), ``first`` and ``given`` (the audit log, ``()`` where
a model keeps none). ``step_fn(tokens [rows], state) ->
(logp [rows, vocab], state)`` is one decoding step, what
``layers/beam_search.py``'s searches scan; ``audit(last state) -> {name:
array}`` is what a request returns of its state beside ``ids``. A decoder
that serves beams takes their number as a fourth argument and repeats its
rows by it (GPT's). The contract has two callers: :func:`make_generator`,
the program every ``models/*.make_generator`` returns, and
:func:`make_scorer`, the same prefill and step under given continuations.

A model writes its config, its parameters by name, a prefill piece and a
step's layers for each kind of mixer, and what it carries by part. The first
step, the chunked walk of a prompt, the audit log, the plan record and the
frame's parameter names are one decision each and live here (``MIGRATION.md``,
"Adding a served model"). The carry's pytree is part of a compiled program
(dict keys sort, lists keep their order: that is the loop's tuple order), so
a model's keys stay as they are.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..core.errors import enforce
from ..framework import LayerHelper, name_scope
from .beam_search import beam_search, greedy_search

# the contract's own keys of a state; every other key is the model's carry
_OWN = ("index", "logp0", "first", "given")


# -- the frame ---------------------------------------------------------------------


def check_length(p_len: int, max_new_tokens: int, limit: int,
                 name: str = "max_position_embeddings"):
    enforce(p_len + max_new_tokens <= limit,
            f"prompt {p_len} + max_new {max_new_tokens} exceeds {name} "
            f"{limit}")


def token_embedding(vocab: int, d: int, dtype):
    """``tok/embedding_0/w [vocab, d]``, created or fetched by name."""
    with name_scope("tok"):
        return LayerHelper("embedding").create_parameter(
            "w", (vocab, d), dtype, initializer=init.Normal(0.0, 1.0))


def untied_head(vocab: int, d: int, dtype):
    """``(final_norm_0/g [d] float32, lm_head_0/w [d, vocab])``."""
    g = LayerHelper("final_norm").create_parameter(
        "g", (d,), jnp.float32, initializer=init.Constant(1.0))
    w = LayerHelper("lm_head").create_parameter(
        "w", (d, vocab), dtype, initializer=init.Normal(0.0, d ** -0.5))
    return g, w


def log_probs(h, w_head):
    """``log_softmax(h W)`` over the vocabulary, the product accumulated
    and the softmax taken in float32."""
    return jax.nn.log_softmax(jnp.matmul(
        h, w_head, preferred_element_type=jnp.float32), axis=-1)


# -- the first step ------------------------------------------------------------------


def start(carried: Dict[str, Any], p_len: int, first_logp, log=()):
    """``state0``: ``carried`` (the model's own keys) as the prefill of
    ``p_len`` tokens left it, ``first_logp [rows, vocab]`` the prefill's
    distribution, ``log`` an :func:`audit_log` where the model keeps one
    (``given``; empty, it adds nothing to the loop's carry)."""
    enforce(not set(carried) & set(_OWN), f"carried keys {sorted(carried)}")
    return {**carried, "index": jnp.asarray(p_len, jnp.int32),
            "logp0": first_logp, "first": jnp.asarray(True), "given": log}


def _carried(state):
    return {k: v for k, v in state.items() if k not in _OWN}


def _advanced(state, new, given):
    """The state a step leaves: the index moves only once a generated token
    has been written (position ``p`` holds the first)."""
    index, first = state["index"], state["first"]
    return {**new, "logp0": state["logp0"], "given": given,
            "index": jnp.where(first, index, index + 1),
            "first": jnp.asarray(False)}


def step_in_conditional(layers: Callable, head: Callable):
    """``step_fn`` with the layers inside the conditional: the first step
    takes the branch that hands back ``logp0`` and the carry untouched,
    every other runs ``layers(tokens, carried, index) -> (x [rows, 1, d],
    carried)`` and ``head(x[:, 0])``. The plain form, one step's layers a
    request the cheaper: GPT, Kimi-K2 and Trinity are built with it, and
    their steps on the chip copy no carry. (MiniCPM-SALA's copied all of
    its and took the other form in PR 48: ROADMAP D18.)"""

    def step_fn(tokens, state):
        carried = _carried(state)

        @jax.named_scope("decode_step")
        def incremental(_):
            x, new = layers(tokens, carried, state["index"])
            return head(x[:, 0]), new

        logp, new = jax.lax.cond(
            state["first"], lambda _: (state["logp0"], carried), incremental,
            operand=None)
        return logp, _advanced(state, new, state["given"])

    return step_fn


def step_with_write_switch(layers: Callable, head: Callable, p_len: int):
    """``step_fn`` with the layers outside the conditional and the head
    alone inside it: ``layers(tokens, carried, index, first) -> (x [rows, 1,
    d], carried, given)`` runs in every step, and in the first (``first``, a
    traced bool) it must leave nothing that outlasts the step: a layer that
    folds its token into a state takes ``~first`` as its ``write`` switch,
    and what a cache gets at position ``p`` the next step writes over, since
    it stands at ``p`` too. A conditional round arrays that a kernel writes
    in place makes the compiler copy them on both of its sides (0.6 GB a
    layer of Brumby's states, PR 39; MiniCPM-SALA's 36 arrays a step, PR 48),
    so they, Phi-4-mini-flash and Granite-4.0-H take this form. ``given`` (what the audited
    recurrence was handed, ``()`` without an audit) goes into the state's
    :func:`audit_log` at ``index - p_len``: the first step's entry is
    written over as well."""

    def step_fn(tokens, state):
        index, first = state["index"], state["first"]
        with jax.named_scope("decode_step"):
            x, new, given = layers(tokens, _carried(state), index, first)
            logp = jax.lax.cond(first, lambda _: state["logp0"],
                                lambda _: head(x[:, 0]), operand=None)
            kept = jax.tree.map(
                lambda log, a: jax.lax.dynamic_update_slice_in_dim(
                    log, a, index - p_len, axis=1), state["given"], given)
        return logp, _advanced(state, new, kept)

    return step_fn


# -- the chunked walk ----------------------------------------------------------------


def chunked_walk(piece: Callable, carried, p_len: int, chunk: int):
    """A prompt of ``p_len`` tokens through ``piece(carried, p0, length) ->
    (carried, (last, seen))``, ``chunk`` tokens at a time: one piece where
    the prompt is no longer than two chunks less one token, else one
    ``lax.scan`` over the whole chunks with no conditional in it, and a
    shorter tail after it as one more piece. ``last`` is what only the last
    piece's is wanted of (its last position's activation), ``seen`` arrays
    ``[rows, length, ...]`` wanted at every position (``()`` for none).
    Returns ``(carried, last, [seen ...])``: the scanned chunks' ``seen``
    joined to ``[rows, chunks * chunk, ...]``, then the tail's."""
    whole = p_len // chunk
    if whole == 1:
        carried, (last, seen) = piece(carried, 0, chunk)
        parts = [seen]
    else:
        carried, (lasts, seen) = jax.lax.scan(
            lambda c, p0: piece(c, p0, chunk), carried,
            jnp.arange(whole, dtype=jnp.int32) * chunk)
        last = jax.tree.map(lambda a: a[-1], lasts)
        # [pieces, rows, chunk, ...] -> [rows, pieces * chunk, ...]
        parts = [jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1).reshape(
            (a.shape[1], whole * chunk) + a.shape[3:]), seen)]
    if p_len > whole * chunk:
        carried, (last, seen) = piece(carried, whole * chunk,
                                      p_len - whole * chunk)
        parts.append(seen)
    return carried, last, parts


# -- the audit log -------------------------------------------------------------------


def audit_log(rows: int, max_new_tokens: int,
              entries: Sequence[Tuple[Tuple[int, ...], Any]]):
    """Empty buffers ``[rows, steps, *shape]`` for what the steps that
    consume a token are handed: all but the first, which takes ``logp0``."""
    steps = max(max_new_tokens - 1, 1)
    return tuple(jnp.zeros((rows, steps) + tuple(shape), dtype)
                 for shape, dtype in entries)


def audit_join(seen, state, max_new_tokens: int):
    """What the recurrence was handed at each of the ``p + max_new_tokens -
    1`` positions its state holds: the prefill's (:func:`chunked_walk`'s
    ``seen`` parts) and then the log of the loop's last ``state``."""
    return tuple(
        jnp.concatenate(parts[:-1] + (parts[-1][:, :max_new_tokens - 1],),
                        axis=1)
        for parts in zip(*seen, state["given"]))


def no_audit(state):
    return {}


# -- the plan record -----------------------------------------------------------------


def nbytes(arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(arrays))


def record_plans(kind: str, rows: int, max_len: int, heads: int, layers: int,
                 cache_dtype: str, lane_width: int, parts: Dict[str, Any],
                 prefill: Optional[Dict[str, int]] = None, **own):
    """One zero-length ``decode.plan`` span in the program's ring for each
    generator traced: what the decode loop carries, as held. ``parts``:
    ``name -> arrays (or their bytes)`` gives ``<name>_bytes`` for each and
    ``cache_bytes``, their sum; ``lane_width`` is the minor dimension of a
    stored slab (a multiple of 128 means the chip's tiling pads nothing);
    ``own`` is what only this model has. ``prefill``: the fields of a
    ``prefill.plan`` span beside ``rows``, how the prompt is walked."""
    from ..core import profiler

    by_part = {f"{name}_bytes": v if isinstance(v, int) else nbytes(v)
               for name, v in parts.items()}
    fields = dict(rows=rows, max_len=max_len, heads=heads, layers=layers,
                  cache_kind=kind, cache_dtype=cache_dtype,
                  lane_width=lane_width, cache_bytes=sum(by_part.values()))
    profiler.record_span("decode.plan", time.time_ns(), 0,
                         **{**fields, **by_part, **own})
    if prefill is not None:
        profiler.record_span("prefill.plan", time.time_ns(), 0, rows=rows,
                             **prefill)


# -- the contract's two callers ------------------------------------------------------


def make_generator(decoder: Callable, cfg, max_new_tokens: int,
                   bos_id: int = 1, eos_id: int = 2, beam_size: int = 1,
                   length_penalty_alpha: float = 0.0):
    """The program fn ``(prompt_ids [b, p]) -> {"ids": [b, max_new_tokens],
    **audit}``, greedy over ``decoder``'s step; with ``beam_size > 1``
    ``{"ids": [b, beam, max_new_tokens], "scores": [b, beam]}`` over the
    same step, the decoder given the beams as its fourth argument."""

    def generate(prompt_ids):
        b = prompt_ids.shape[0]
        if beam_size > 1:
            state0, step_fn, _ = decoder(cfg, prompt_ids, max_new_tokens,
                                         beam_size)
            seqs, scores = beam_search(
                step_fn, state0, b, beam_size, max_new_tokens, bos_id=bos_id,
                eos_id=eos_id, length_penalty_alpha=length_penalty_alpha)
            return {"ids": seqs, "scores": scores}
        state0, step_fn, audit = decoder(cfg, prompt_ids, max_new_tokens)
        ids, state = greedy_search(step_fn, state0, b, max_new_tokens,
                                   bos_id=bos_id, eos_id=eos_id,
                                   with_state=True)
        return {"ids": ids, **audit(state)}

    return generate


def make_scorer(decoder: Callable, cfg):
    """The generator's distributions under given continuations: teacher
    forcing through the decoder's own prefill, carry and step. A program fn
    ``(prompt_ids [b, p], next_ids [b, n]) -> {"logp": [b, n + 1, vocab]}``:
    row ``j`` is the distribution after ``j`` of ``next_ids``."""

    def score(prompt_ids, next_ids):
        state0, step_fn, _ = decoder(cfg, prompt_ids, next_ids.shape[1] + 1)
        # the step takes the token chosen before it; the first ignores its
        tokens = jnp.concatenate([next_ids[:, :1], next_ids], axis=1).T

        def step(state, tok):
            logp, state = step_fn(tok, state)
            return state, logp

        _, logp = jax.lax.scan(step, state0, tokens)
        return {"logp": logp.transpose(1, 0, 2)}

    return score


__all__ = ["audit_join", "audit_log", "check_length", "chunked_walk",
           "log_probs", "make_generator", "make_scorer", "nbytes", "no_audit",
           "record_plans", "start", "step_in_conditional",
           "step_with_write_switch", "token_embedding", "untied_head"]
