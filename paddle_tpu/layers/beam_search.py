"""Beam search decoding.

Analog of beam_search_op.cc / beam_search_decode_op.cc and the legacy
RecurrentGradientMachine generation path (SURVEY N28): batched beam
search compiled under jit — static max_len, lax.scan over steps,
top-k over (beam × vocab) per batch row, finished-beam freezing with
EOS, optional GNMT length penalty.

The step function contract (the reference's "score over candidates"
block): ``step_fn(tokens [B*beam], state) -> (logprobs [B*beam, vocab],
new_state)`` where state is any pytree carrying e.g. decoder caches.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e9


def _gather_beams(tree, idx, batch, beam):
    """Reindex the beam dimension of every [B*beam, ...] leaf."""
    def g(x):
        if x.ndim == 0 or x.shape[0] != batch * beam:
            return x  # non-batched leaf (e.g. a cache step index)
        xb = x.reshape((batch, beam) + x.shape[1:])
        return jnp.take_along_axis(
            xb, idx.reshape((batch, beam) + (1,) * (x.ndim - 1)), axis=1
        ).reshape((batch * beam,) + x.shape[1:])
    return jax.tree.map(g, tree)


def beam_search(
    step_fn: Callable,
    init_state: Any,
    batch_size: int,
    beam_size: int,
    max_len: int,
    bos_id: int = 1,
    eos_id: int = 2,
    length_penalty_alpha: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (sequences [B, beam, max_len], scores [B, beam]) sorted
    best-first. ``init_state`` leaves must be laid out [B*beam, ...]
    (tile per-batch state ``beam_size`` times first)."""
    B, K = batch_size, beam_size

    tokens0 = jnp.full((B * K,), bos_id, jnp.int32)
    # lane 0 active, others dead — so step 0 doesn't duplicate beams
    scores0 = jnp.tile(jnp.asarray([0.0] + [NEG_INF] * (K - 1), jnp.float32), (B,))
    finished0 = jnp.zeros((B * K,), jnp.bool_)
    seqs0 = jnp.zeros((B * K, max_len), jnp.int32)

    def step(carry, t):
        tokens, scores, finished, seqs, state = carry
        logp, new_state = step_fn(tokens, state)
        vocab = logp.shape[-1]
        # finished beams: only EOS continuation at zero cost
        frozen = jnp.full((B * K, vocab), NEG_INF).at[:, eos_id].set(0.0)
        logp = jnp.where(finished[:, None], frozen, logp)
        cand = scores[:, None] + logp  # [B*K, V]
        cand = cand.reshape(B, K * vocab)
        top_scores, top_idx = jax.lax.top_k(cand, K)  # [B, K]
        beam_idx = top_idx // vocab
        tok_idx = (top_idx % vocab).astype(jnp.int32)

        new_state = _gather_beams(new_state, beam_idx, B, K)
        seqs = _gather_beams(seqs, beam_idx, B, K)
        finished = _gather_beams(finished, beam_idx, B, K)
        tokens = tok_idx.reshape(-1)
        seqs = seqs.at[:, t].set(tokens)
        finished = finished | (tokens == eos_id)
        return (tokens, top_scores.reshape(-1), finished, seqs, new_state), None

    carry = (tokens0, scores0, finished0, seqs0, init_state)
    (tokens, scores, finished, seqs, _), _ = jax.lax.scan(
        step, carry, jnp.arange(max_len))

    seqs = seqs.reshape(B, K, max_len)
    scores = scores.reshape(B, K)
    if length_penalty_alpha > 0:
        lengths = jnp.sum((seqs != 0) & (seqs != eos_id), axis=-1).astype(jnp.float32) + 1.0
        penalty = jnp.power((5.0 + lengths) / 6.0, length_penalty_alpha)
        scores = scores / penalty
    order = jnp.argsort(-scores, axis=1)
    seqs = jnp.take_along_axis(seqs, order[..., None], axis=1)
    scores = jnp.take_along_axis(scores, order, axis=1)
    return seqs, scores


def greedy_search(step_fn, init_state, batch_size: int, max_len: int,
                  bos_id: int = 1, eos_id: int = 2, with_state: bool = False):
    """Greedy decode (beam_size=1 fast path). ``with_state``: return
    ``(seqs, the state the last step left)``."""
    tokens0 = jnp.full((batch_size,), bos_id, jnp.int32)
    finished0 = jnp.zeros((batch_size,), jnp.bool_)
    seqs0 = jnp.zeros((batch_size, max_len), jnp.int32)

    def step(carry, t):
        tokens, finished, seqs, state = carry
        logp, new_state = step_fn(tokens, state)
        nxt = jnp.argmax(logp, axis=-1).astype(jnp.int32)
        nxt = jnp.where(finished, eos_id, nxt)
        seqs = seqs.at[:, t].set(nxt)
        finished = finished | (nxt == eos_id)
        return (nxt, finished, seqs, new_state), None

    (tokens, finished, seqs, state), _ = jax.lax.scan(
        step, (tokens0, finished0, seqs0, init_state), jnp.arange(max_len))
    return (seqs, state) if with_state else seqs


def beam_search_decode(step_ids, step_parents, end_id: int = 2, name=None):
    """beam_search_decode_op analog: backtrack per-step (ids, parent beam
    indices) into full sequences.

    step_ids/step_parents: [T, B, K] int32 — token chosen at step t per
    beam, and the beam lane it extended. Returns (sequences [B, K, T],
    valid [B, K, T]) — valid marks tokens up to and including the first
    ``end_id``, the LoD-lengths equivalent of the reference's ragged
    sentence output.
    """
    step_ids = jnp.asarray(step_ids)
    step_parents = jnp.asarray(step_parents)
    t_steps, b, k = step_ids.shape

    def back(lane, inp):
        ids_t, par_t = inp                                   # [B, K]
        tok = jnp.take_along_axis(ids_t, lane, axis=1)       # [B, K]
        lane = jnp.take_along_axis(par_t, lane, axis=1)
        return lane, tok

    lane0 = jnp.tile(jnp.arange(k)[None, :], (b, 1))
    _, toks = jax.lax.scan(back, lane0, (step_ids[::-1], step_parents[::-1]))
    seqs = jnp.transpose(toks[::-1], (1, 2, 0))              # [B, K, T]
    ended_before = jnp.cumsum((seqs == end_id).astype(jnp.int32), axis=-1) \
        - (seqs == end_id).astype(jnp.int32)
    return seqs, ended_before == 0


def beam_search_decode_lod(seqs, valid, scores=None):
    """Package decoded beams as the reference's 2-level LoD output
    (beam_search_decode_op.cc): level 0 groups hypotheses per source
    sentence, level 1 gives each hypothesis's token count — the
    (sentence-level, token-level) nested structure the book
    machine-translation demo consumes.

    seqs/valid: [B, K, T] from :func:`beam_search_decode` (or
    :func:`beam_search` with valid = token-mask up to first EOS).
    Returns an ``LoDTensor`` of token ids with
    ``recursive_seq_lens = [[K]*B, per-hypothesis lengths]``; with
    ``scores`` [B, K], also returns a matching 2-level LoDTensor whose
    innermost lengths are 1 per hypothesis (the sentenceScores output).

    Runs on host after the device scan — the reference computes this op
    on CPU too (it is pure ragged bookkeeping, no FLOPs).
    """
    import numpy as np
    from .sequence import LoDTensor

    seqs = np.asarray(seqs)
    valid = np.asarray(valid).astype(bool)
    b, k, t = seqs.shape
    tokens, hyp_lens = [], []
    for i in range(b):
        for j in range(k):
            toks = seqs[i, j][valid[i, j]]
            tokens.append(toks)
            hyp_lens.append(len(toks))
    flat = np.concatenate(tokens) if tokens else np.zeros((0,), seqs.dtype)
    ids = LoDTensor(flat.astype(np.int32), [[k] * b, hyp_lens])
    if scores is None:
        return ids
    sc = np.asarray(scores).reshape(b * k)
    return ids, LoDTensor(sc, [[k] * b, [1] * (b * k)])
