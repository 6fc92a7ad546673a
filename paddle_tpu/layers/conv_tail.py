"""The short causal depthwise convolution in front of a state-space
recurrence, with what it carries: the last ``taps - 1`` inputs, the *tail*
``[rows, taps - 1, channels]``. ``layers/sambay.py`` (Mamba-1: 4 taps over
``d_inner`` 5,120) and ``layers/mamba2.py`` (Mamba-2: 4 taps over ``x``, ``B``
and ``C`` side by side, 8,448) carry the same thing, a piece's first outputs
reading the inputs before the piece out of it and a one-token step being a
piece of one (:func:`conv_silu`).

A step that shifts the tail (drops the oldest input, appends its own) reads
and writes every slot of an array its loop carries, and the compiler copies
the array first (PERF.md section 6, PR 49: nine copies a step in
``granite-serve-agent``). :func:`ring_of` and :func:`ring_step` are the step
over a **ring** instead, taps leading, ``[taps - 1, rows, channels]``: the
input of position ``t`` at slot ``t % (taps - 1)``, so a step writes one slot,
in place, over the input that has just left the convolution's reach, and
reads the slots in the order the position says.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv_silu(a, tail, w, b):
    """``silu(b + sum_i w[i] * a_(t - (taps - 1) + i))`` over the piece ``a
    [rows, s, channels]`` behind ``tail [rows, taps - 1, channels]``: ``w
    [taps, channels]`` and ``b [channels]`` float32, the sum in float32.
    Returns ``(c [rows, s, channels] float32, the new tail)``: the last
    ``taps - 1`` inputs, in the inputs' dtype."""
    s = a.shape[1]
    a = jnp.concatenate([tail, a], axis=1)
    conv = sum(a[:, i:i + s].astype(jnp.float32) * w[i]
               for i in range(w.shape[0]))
    return jax.nn.silu(conv + b), a[:, s:]


def ring_of(tail, p_len: int):
    """What a prefill of ``p_len`` tokens left (``tail [rows, taps - 1,
    channels]``, in order of position) as the ring the steps write: ``[taps
    - 1, rows, channels]``, position ``t`` at slot ``t % (taps - 1)``."""
    held = tail.shape[1]
    return jnp.roll(jnp.swapaxes(tail, 0, 1), (p_len - held) % held, axis=0)


def ring_step(a, ring, w, b, index, write):
    """One token's :func:`conv_silu` over the ring: ``a [rows, channels]``
    the input at position ``index`` (traced), ``ring [taps - 1, rows,
    channels]``. Returns ``(c [rows, channels] float32, ring)``; where
    ``write`` (a traced bool) is false the ring is left as it was."""
    held = ring.shape[0]
    # slot j holds position index - held + (j - index) % held: tap (j - index) % held
    taps = jnp.take(w[:held], (jnp.arange(held) - index) % held, axis=0)
    conv = sum(ring[j].astype(jnp.float32) * taps[j] for j in range(held))
    conv = conv + a.astype(jnp.float32) * w[held]
    slot = index % held
    old = jax.lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=True)
    new = jnp.where(write, a[None].astype(ring.dtype), old)
    return (jax.nn.silu(conv + b),
            jax.lax.dynamic_update_slice_in_dim(ring, new, slot, axis=0))
