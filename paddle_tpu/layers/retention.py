"""The mixer of a power-retention decoder (Brumby): grouped-query projections,
an RMSNorm on every head of q and k, rotary positions, a gate computed from
the token, and ``ops/power_retention.py`` in place of attention. A sibling of
``layers/sala.py`` and written as it is: pure functions of ``(activation,
layer_params, carried state)``; the norm, the rotary map and the parameter
maker are ``layers/blocks.py``'s.

``heads`` query heads over ``kv_heads`` key heads (a *group* of ``heads /
kv_heads`` reads one key head's state)::

    u = RMSNorm(x)      q, k, v = W_q u, W_k u, W_v u     q, k = RMSNorm_hd, rotated
    q = q / sqrt(hd)    log gamma = logsigmoid(W_g u + b_g)       (float32, a key head)
    o = retention(q, k, v, log gamma)                     x' = x + W_o o

The layer holds no cache of keys and values: it carries one float32 array,
the state with its key sum (``ops/power_retention.empty_state``), and beside
it the last ``W`` tokens' keys, values and log-gates a key head
(``ops/power_retention.empty_window``: ``W = WINDOW`` tokens, 257 numbers
each, whatever the context), which a step folds into an ``W``-th of the
states. A prefill's chunk takes the chunked form and leaves the window
empty, a step the one-token form; both also return what they gave the recurrence (``k``, ``v`` and the
log-gate), for a caller that audits the carried sums (``models/brumby.py``).
The gate has a bias, created zero: weights published without one load as
they are.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..framework import LayerHelper
from ..ops.power_retention import retention, retention_step, window_append
from .blocks import params, rms_norm, rope


class RetentionDims(NamedTuple):
    """One retention mixer (published key names in brackets)."""
    d_model: int            # hidden_size
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int           # head_dim
    eps: float              # rms_norm_eps
    theta: float            # rope_theta

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5


def retention_params(dims: RetentionDims, dtype) -> Dict[str, jax.Array]:
    """One layer's own parameters, under ``mixer/`` in the caller's scope;
    q, k and v are one matrix ``[out, in]``, q's rows first."""
    d, hd = dims.d_model, dims.head_dim
    wide, kv = dims.heads * hd, dims.kv_heads * hd
    return params(LayerHelper("mixer", name="mixer"), {
        "attn_norm/g": ((d,), None),
        "qkv/w": ((wide + 2 * kv, d), d),
        "q_norm/g": ((hd,), None), "k_norm/g": ((hd,), None),
        "gate/w": ((d, dims.kv_heads), d), "gate/b": ((dims.kv_heads,), 0.0),
        "o/w": ((wide, d), wide),
    }, None, dtype)


def _qkv(u, p, dims: RetentionDims, positions):
    """``q [b, s, heads * hd]`` (normed a head, rotated, scaled), ``k [b, s,
    kv * hd]`` (normed, rotated), ``v [b, s, kv * hd]``."""
    b, s, _ = u.shape
    hd = dims.head_dim
    wide, kv = dims.heads * hd, dims.kv_heads * hd
    qkv = jnp.einsum("bsd,od->bso", u, p["qkv/w"])
    i = jnp.arange(hd // 2, dtype=jnp.float32)
    freqs = dims.theta ** (-2.0 * i / hd)
    turn = lambda t, g: rope(rms_norm(t.reshape(b, s, -1, hd), g, dims.eps),
                               positions, freqs, head_axis=True)
    q = turn(qkv[..., :wide], p["q_norm/g"])
    k = turn(qkv[..., wide:wide + kv], p["k_norm/g"])
    q = (q.astype(jnp.float32) * dims.scale).astype(q.dtype)
    return q.reshape(b, s, -1), k.reshape(b, s, -1), qkv[..., wide + kv:]


@jax.named_scope("gate")
def log_gate(u, p):
    """``logsigmoid(W_g u + b_g) [b, s, kv]``, float32."""
    return jax.nn.log_sigmoid(jnp.matmul(
        u, p["gate/w"], preferred_element_type=jnp.float32) + p["gate/b"])


def retention_prefill(x, p, dims: RetentionDims, state, p0):
    """A chunk ``x [b, s, d]`` at positions ``p0 ..`` through the chunked
    recurrence, from ``state``. Returns ``(x + mixer, state, (k [b, s, kv *
    hd], v, log gamma [b, s, kv]))``: the last is what the recurrence was
    given."""
    with jax.named_scope("retention"):
        u = rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _qkv(u, p, dims, p0 + jnp.arange(x.shape[1]))
        log_gamma = log_gate(u, p)
        o, state = retention(q, k, v, log_gamma, state, dims.heads,
                             dims.kv_heads)
        x = x + jnp.matmul(o, p["o/w"])
    return x, state, (k, v, log_gamma)


def retention_decode(x, p, dims: RetentionDims, carried, index, write):
    """One token at position ``index`` (traced): ``x [rows, 1, d]``;
    ``carried`` is the layer's state and the window of tokens it keeps
    beside it (``ops/power_retention.empty_window``). Every state is read once; the
    token goes into the window, and of every ``W`` states one, whose turn it
    is at this position, has its window folded in and is written back, in
    place (``ops/power_retention.retention_step``). Where ``write`` (a
    traced bool) is false the token leaves nothing (a gate of 1 and a key of
    0, and no fold), and the caller hands the next token the same ``index``:
    a step that must not write still runs the kernel, since a conditional
    round a kernel that writes in place makes the compiler copy every state
    on both of its sides. Returns ``(x + mixer, (state, window), given)``,
    ``given`` as :func:`retention_prefill`'s, ``s = 1``."""
    state, window = carried
    with jax.named_scope("retention"):
        u = rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _qkv(u, p, dims, index[None])
        k = jnp.where(write, k, 0)
        log_gamma = jnp.where(write, log_gate(u, p), 0.0)
        o, state = retention_step(q[:, 0], k[:, 0], v[:, 0], log_gamma[:, 0],
                                  state, dims.heads, dims.kv_heads, window,
                                  index, write)
        window = window_append(window, k[:, 0], v[:, 0], log_gamma[:, 0], index)
        x = x + jnp.matmul(o[:, None, :], p["o/w"])
    return x, (state, window), (k, v, log_gamma)


__all__ = ["RetentionDims", "log_gate", "retention_decode", "retention_params",
           "retention_prefill"]
