"""The Mamba-2 mixer (arXiv:2405.21060; ``model_type: granitemoehybrid``'s
``mamba`` layers) round the state-space-dual recurrence of ``ops/ssd.py``. A
sibling of ``layers/sambay.py`` and ``layers/gqa.py`` and written as they
are: pure functions of ``(activation, layer_params, carried state)``,
parameters created by :func:`mamba2_params` under ``mixer/`` in the caller's
scope, the norm from ``layers/blocks.py``, the convolution and its tail from
``layers/conv_tail.py``.

One layer, ``x [.., d]`` (``di = heads * head_dim``, ``n = d_state``)::

    u = rms(x; g)
    [z | xBC | dt] = u W_in                  # di + (di + 2 n) + heads columns, no bias
    xBC = silu(conv4(xBC) + b_conv)          # depthwise, causal; the tail is the 3 inputs before
    [xs | B | C] = xBC                       # di as heads of head_dim; n; n (one group: every head's)
    dt = softplus(dt + dt_bias), a = -exp(A_log)      # a head, float32
    S_t = exp(dt_t a) S_{t-1} + (dt_t xs_t) B_t^T,  y_t = S_t C_t + D xs_t
    y = rms(y * silu(z); g_gate)             # the gate BEFORE the norm, one norm over all di
    x + residual * (y W_out)

What a layer carries: the convolution's last three inputs in the model's
dtype (through a prefill the tail ``[rows, 3, di + 2 n]`` in order of
position, through the steps the ring ``[3, rows, di + 2 n]`` of
``layers/conv_tail.py``, which a step writes at one slot, in place), and the
float32 state ``[rows, groups, n, 128]`` (``ops/ssd.py`` says why it lies
so). Both forms also return ``given = (dt,
xs, B)``, what the recurrence was handed, for a caller that audits the state.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..framework import LayerHelper
from ..ops import ssd
from . import conv_tail
from .blocks import params, residual, rms_norm


class Mamba2Dims(NamedTuple):
    """One decoder's Mamba-2 widths (published key names in brackets)."""
    d_model: int            # hidden_size
    heads: int              # mamba_n_heads
    head_dim: int           # mamba_d_head
    d_state: int            # mamba_d_state
    d_conv: int             # mamba_d_conv
    chunk: int              # mamba_chunk_size
    eps: float              # rms_norm_eps
    residual: float         # residual_multiplier

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:    # x, B and C side by side (one group)
        return self.d_inner + 2 * self.d_state


def mamba2_params(dims: Mamba2Dims, dtype) -> Dict[str, jax.Array]:
    """``W_in``'s columns are gate, ``xBC``, ``dt`` in that order. What goes
    with the recurrence is float32 and a head's: created as Mamba-2 is
    published to start, ``A = 1`` (``A_log`` 0), a step of 0.01, ``D = 1``,
    taps U(-1/2, 1/2)."""
    import math

    d, di, cw, h = dims.d_model, dims.d_inner, dims.conv_width, dims.heads
    return params(LayerHelper("mixer", name="mixer"), {
        "norm/g": ((d,), None), "in/w": ((d, 2 * di + 2 * dims.d_state + h), d),
        "conv/w": ((dims.d_conv, cw), init.Uniform(-0.5, 0.5)),
        "conv/b": ((cw,), 0.0), "dt/b": ((h,), math.log(math.expm1(0.01))),
        "a_log": ((h,), 0.0), "d": ((h,), 1.0), "gate_norm/g": ((di,), None),
        "out/w": ((di, d), di)}, None, dtype)


def empty_carry(rows: int, dims: Mamba2Dims, dtype):
    """``(tail, state)`` of a layer that has seen nothing."""
    return (jnp.zeros((rows, dims.d_conv - 1, dims.conv_width), dtype),
            ssd.empty_state(rows, dims.heads, dims.head_dim, dims.d_state))


def _inputs(x, p, dims: Mamba2Dims, conv):
    """``x [b, s, d]`` -> ``(xs [b, s, di]`` after the convolution and its
    SiLU (taken in float32, held in ``x``'s dtype), ``B, C [b, s, n]``
    likewise, ``dt [b, s, heads]`` float32 after the softplus, the gate ``z``
    in ``x``'s dtype, what the convolution carries on)``. ``conv(xBC) -> (c
    float32, carried)`` is the convolution over the piece or the step."""
    di, n = dims.d_inner, dims.d_state
    with jax.named_scope("in_proj"):
        zxd = jnp.matmul(rms_norm(x, p["norm/g"], dims.eps), p["in/w"])
    with jax.named_scope("conv"):
        c, carried = conv(zxd[..., di:di + dims.conv_width])
    dt = jax.nn.softplus(zxd[..., di + dims.conv_width:].astype(jnp.float32)
                         + p["dt/b"])
    c = c.astype(x.dtype)       # an activation: held in the model's dtype
    return (c[..., :di], c[..., di:di + n], c[..., di + n:], dt, zxd[..., :di],
            carried)


def _out(x, p, dims: Mamba2Dims, y, xs, z):
    """``y`` with the skip, gated, normed and projected: ``x + residual *
    W_out rms(y * silu(z))``."""
    with jax.named_scope("gated_norm"):
        y = y.astype(jnp.float32) + (jnp.repeat(p["d"], dims.head_dim)
                                     * xs.astype(jnp.float32))
        gated = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)),
                         p["gate_norm/g"], dims.eps).astype(x.dtype)
    with jax.named_scope("out_proj"):
        return residual(x, jnp.matmul(gated, p["out/w"]), dims.residual)


def mamba2_prefill(x, p, dims: Mamba2Dims, carried):
    """A piece ``x [b, s, d]`` through the chunked recurrence from ``carried
    = (tail, state)``. Returns ``(x + mixer, carried, given)``, ``given =
    (dt [b, s, heads], xs [b, s, di], B [b, s, n])`` what the recurrence was
    handed, as it was handed it (``xs`` and ``B`` rounded to the kernel's
    operand type, ``ops/ssd.OPERAND``)."""
    tail, state = carried
    with jax.named_scope("mamba2"):
        xs, b, c, dt, z, tail = _inputs(x, p, dims, lambda a: conv_tail.conv_silu(
            a, tail, p["conv/w"], p["conv/b"]))
        with jax.named_scope("ssd"):
            handed = (dt, xs.astype(ssd.OPERAND), b.astype(ssd.OPERAND))
            y, state = ssd.ssd(*handed, c, -jnp.exp(p["a_log"]), state,
                               dims.chunk)
        x = _out(x, p, dims, y, xs, z)
    return x, (tail, state), handed


def ring_of(carried, p_len: int):
    """What a prefill of ``p_len`` tokens left, as the steps carry it: the
    tail as the ring (``layers/conv_tail.ring_of``), the state as it is."""
    tail, state = carried
    return conv_tail.ring_of(tail, p_len), state


def mamba2_decode(x, p, dims: Mamba2Dims, carried, index, write):
    """One token ``x [rows, 1, d]`` at position ``index`` (traced) over
    ``carried = (ring, state)``. Where ``write`` (a traced bool) is false the
    token leaves the ring and the state as they were. Returns what
    :func:`mamba2_prefill` does, ``s = 1``."""
    ring, state = carried

    def conv(a):
        c, new = conv_tail.ring_step(a[:, 0], ring, p["conv/w"], p["conv/b"],
                                     index, write)
        return c[:, None], new

    with jax.named_scope("mamba2"):
        xs, b, c, dt, z, ring = _inputs(x, p, dims, conv)
        with jax.named_scope("ssd"):
            y, new_state = ssd.ssd_step(dt[:, 0], xs[:, 0], b[:, 0], c[:, 0],
                                        -jnp.exp(p["a_log"]), state)
        x = _out(x, p, dims, y[:, None, :], xs, z)
    return x, (ring, jnp.where(write, new_state, state)), (dt, xs, b)


__all__ = ["Mamba2Dims", "empty_carry", "mamba2_decode", "mamba2_params",
           "mamba2_prefill", "ring_of"]
