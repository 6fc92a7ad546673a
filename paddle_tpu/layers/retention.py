"""The mixer of a power-retention decoder (Brumby): grouped-query projections,
an RMSNorm on every head of q and k, rotary positions, a gate computed from
the token, and ``ops/power_retention.py`` in place of attention. A sibling of
``layers/sala.py`` and written as it is: pure functions of ``(activation,
layer_params, carried state)``; ``layers/latent.py``'s ``rms_norm``, ``rope``,
``ffn_block`` and ``_params`` are the ones used here.

``heads`` query heads over ``kv_heads`` key heads (a *group* of ``heads /
kv_heads`` reads one key head's state)::

    u = RMSNorm(x)      q, k, v = W_q u, W_k u, W_v u     q, k = RMSNorm_hd, rotated
    q = q / sqrt(hd)    log gamma = logsigmoid(W_g u + b_g)       (float32, a key head)
    o = retention(q, k, v, log gamma)                     x' = x + W_o o

The layer holds no keys and no values: it carries one float32 array, the
state with its key sum (``ops/power_retention.empty_state``), whatever the
context. A prefill's chunk takes the chunked form, a step the one-token form;
both also return what they gave the recurrence (``k``, ``v`` and the
log-gate), for a caller that audits the carried sums (``models/brumby.py``).
The gate has a bias, created zero: weights published without one load as
they are.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..framework import LayerHelper
from ..ops.power_retention import retention, retention_step
from . import latent as M


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
    helper = LayerHelper("mixer", name="mixer")
    p = M._params(helper, {
        "attn_norm/g": ((d,), None),
        "qkv/w": ((wide + 2 * kv, d), d),
        "q_norm/g": ((hd,), None), "k_norm/g": ((hd,), None),
        "gate/w": ((d, dims.kv_heads), d), "o/w": ((wide, d), wide),
    }, None, dtype)
    p["gate/b"] = helper.create_parameter(
        "gate/b", (dims.kv_heads,), jnp.float32, initializer=init.Constant(0.0))
    return p


def _qkv(u, p, dims: RetentionDims, positions):
    """``q [b, s, heads * hd]`` (normed a head, rotated, scaled), ``k [b, s,
    kv * hd]`` (normed, rotated), ``v [b, s, kv * hd]``."""
    b, s, _ = u.shape
    hd = dims.head_dim
    wide, kv = dims.heads * hd, dims.kv_heads * hd
    qkv = jnp.einsum("bsd,od->bso", u, p["qkv/w"])
    i = jnp.arange(hd // 2, dtype=jnp.float32)
    freqs = dims.theta ** (-2.0 * i / hd)
    turn = lambda t, g: M.rope(M.rms_norm(t.reshape(b, s, -1, hd), g, dims.eps),
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
        u = M.rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _qkv(u, p, dims, p0 + jnp.arange(x.shape[1]))
        log_gamma = log_gate(u, p)
        o, state = retention(q, k, v, log_gamma, state, dims.heads,
                             dims.kv_heads)
        x = x + jnp.matmul(o, p["o/w"])
    return x, state, (k, v, log_gamma)


def retention_decode(x, p, dims: RetentionDims, state, index, write):
    """One token at position ``index`` (traced): ``x [rows, 1, d]``; the
    state is read and written once, in place. Where ``write`` (a traced
    bool) is false the token leaves the state and the key sum as they were
    (a gate of 1 and a key of 0): a step that must not write still runs the
    kernel, since a conditional round a kernel that writes in place makes
    the compiler copy every state on both of its sides. Returns as
    :func:`retention_prefill` does, ``s = 1``."""
    with jax.named_scope("retention"):
        u = M.rms_norm(x, p["attn_norm/g"], dims.eps)
        q, k, v = _qkv(u, p, dims, index[None])
        k = jnp.where(write, k, 0)
        log_gamma = jnp.where(write, log_gate(u, p), 0.0)
        o, state = retention_step(q[:, 0], k[:, 0], v[:, 0], log_gamma[:, 0],
                                  state, dims.heads, dims.kv_heads)
        x = x + jnp.matmul(o[:, None, :], p["o/w"])
    return x, state, (k, v, log_gamma)


__all__ = ["RetentionDims", "log_gate", "retention_decode", "retention_params",
           "retention_prefill"]
