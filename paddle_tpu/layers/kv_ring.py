"""The last ``window`` keys and values of a sliding-window attention layer,
as a prefill holds them and as the steps do. ``layers/sambay.py``
(differential attention over a 512-token window) and ``layers/gqa.py``
(grouped-query attention over a 4,096-token one) carry the same thing:
``(k, v)``, each ``[rows, window, kv_heads * head_dim]``, lane-dense as a
projection leaves them.

A prefill holds them **in order of position**: a piece's keys are laid
behind what the window held before it (:func:`joined`), the piece attends to
that through ``flash_attention(window=)`` with the slots that hold nothing
yet masked (:func:`empty_bias`), and the last ``window`` of it are kept
(:func:`kept`). :func:`ring_of` turns what the prefill left into **the ring**
the steps write: position ``t`` at slot ``t % window`` (:func:`write`), so a
step's key goes over the one that has just left the window, in place, and
slots ``<= index`` are attended (:func:`live`: all of them from position
``window - 1`` on).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .stacked import NEG_INF


def joined(held, new):
    """What a piece attends to: ``held [rows, window, c]`` of the positions
    before it, in order, then its own ``new [rows, s, c]``."""
    return jnp.concatenate([held, new], axis=1)


def kept(keys, window: int):
    """The last ``window`` of :func:`joined`: what the next piece finds."""
    return keys[:, -window:]


def empty_bias(keys: int, empty):
    """The key bias ``[keys]`` (every row's) over :func:`joined` keys whose
    first ``empty`` slots (traced: ``window - p0`` for a piece at positions
    ``p0 ..``) hold nothing yet."""
    return jnp.where(jnp.arange(keys) < empty, NEG_INF, 0.0)


def ring_of(held, p_len: int, window: int):
    """What a prefill of ``p_len`` positions left in order of position, as
    the ring the steps write: position ``t`` at slot ``t % window``."""
    return tuple(jnp.roll(a, p_len % window, axis=1) for a in held)


def write(cache, row, at):
    """``row [rows, c]`` into ``cache [rows, T, c]`` at index ``at`` of its
    second axis, in place: a ring's slot, or a full cache's position."""
    return jax.lax.dynamic_update_slice_in_dim(
        cache, row[:, None, :].astype(cache.dtype), at, axis=1)


def live(window: int, index):
    """The ring's slots a token at position ``index`` attends to."""
    return jnp.arange(window) <= index


__all__ = ["empty_bias", "joined", "kept", "live", "ring_of", "write"]
