"""What stands round a mixer in every served decoder, once: the norms, the
rotary map, the gated SiLU FFN with its parameters, the residual sum and the
FFN block. ``layers/latent.py`` (latent attention), ``layers/sala.py``
(sparse and lightning attention), ``layers/retention.py`` (power retention),
``layers/sambay.py`` (Mamba, differential attention), ``layers/gqa.py``
(grouped-query attention, gated or plain) and ``layers/mamba2.py`` (Mamba-2)
hold mixers only and take these from here; the models pass :func:`ffn_block`
the arguments that give the expression each computes.

Pure functions of arrays, no ``LayerHelper`` call outside the two parameter
makers, so they trace under ``lax.scan``. Matrices are created and held in
the model's dtype and norm scales in float32 (``layers/stacked.py``, the
trained zoo's sibling, holds float32 master copies: ROADMAP D16).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..framework import LayerHelper
from .stacked import StackedInit


@jax.named_scope("rms")
def rms_norm(x, g, eps: float = 1e-5):
    """``x * rsqrt(mean(x^2) + eps) * g``, statistics and scale in float32,
    result in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("ln")
def layer_norm(x, g, b, eps: float):
    """LayerNorm with scale and bias; statistics, scale and bias in
    float32, the result in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def rope(x, positions, freqs, scale: float = 1.0, head_axis: bool = False):
    """Rotate the pairs ``(2i, 2i + 1)`` of ``x [..., s, dim]`` (with
    ``head_axis``: ``[..., s, H, dim]``) by ``positions[s] * freqs[i]``;
    angles, cos and sin in float32."""
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    if head_axis:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def residual(x, y, a: float):
    """``x + a * y`` summed in float32, in ``x``'s dtype."""
    return (x.astype(jnp.float32) + a * y.astype(jnp.float32)).astype(x.dtype)


# -- parameters ------------------------------------------------------------------


def params(helper: LayerHelper, shapes, layers: Optional[int], dtype
           ) -> Dict[str, jax.Array]:
    """``name -> (shape, how)``: with an int a matrix ``N(0, 1 / how)`` in
    ``dtype`` (its fan-in), else a float32 array: ``None`` a norm's scale
    (ones), a float that constant, an initializer its own values. With
    ``layers`` every shape gains the leading axis."""
    out = {}
    for name, (shape, how) in shapes.items():
        full = shape if layers is None else (layers,) + shape
        if isinstance(how, int):
            made = init.Normal(0.0, how ** -0.5)
            made = made if layers is None else StackedInit(made)
        elif how is None or isinstance(how, float):
            made = init.Constant(1.0 if how is None else how)
        else:
            made = how
        out[name] = helper.create_parameter(
            name, full, dtype if isinstance(how, int) else jnp.float32,
            initializer=made)
    return out


def gated_ffn_params(d_model: int, width: int, dtype,
                     layers: Optional[int] = None, name: str = "ffn"
                     ) -> Dict[str, jax.Array]:
    return params(LayerHelper(name, name=name), {
        "gate/w": ((d_model, width), d_model),
        "up/w": ((d_model, width), d_model),
        "down/w": ((width, d_model), width),
        "ffn_norm/g": ((d_model,), None)}, layers, dtype)


# -- the FFN ---------------------------------------------------------------------


def _gated(x, w_gate, w_up, w_down, gate_dtype):
    gate = jnp.matmul(x, w_gate,
                      preferred_element_type=gate_dtype).astype(jnp.float32)
    up = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
    return jnp.matmul((jax.nn.silu(gate) * up).astype(x.dtype), w_down)


@jax.named_scope("ffn")
def gated_ffn(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * (W_up x))``; products accumulate in
    float32, the gate is taken in float32 and rounded once."""
    return _gated(x, w_gate, w_up, w_down, jnp.float32)


def ffn_block(x, p, eps: float, norm: str = "rms", gate_dtype=jnp.float32,
              scale: Optional[float] = None, sum_in_scope: bool = False):
    """``x + FFN(norm(x))`` with the block's own norm, ``p`` as
    :func:`gated_ffn_params` leaves it (``norm="layer"``: with
    ``ffn_norm/b`` beside the scale).

    ``gate_dtype``: the type the gate product's result is taken in before
    the SiLU in float32. float32 rounds nothing ahead of it. ``x``'s dtype
    is how the published bfloat16 inference of Brumby and Phi-4 takes it:
    with a float32 result the compiler walks a one-row step's ``[d, width]``
    matrix in strips of 512 columns, 16 KB pieces half a megabyte apart, and
    on the chip that walk took 260, 274 or 293 us by a level fixed for a
    process's life; with this result it walks whole rows, as it walks ``up``
    and ``down``, in 237 us in every process (PERF.md section 6, PR 39;
    ``tests/test_tpu_compile.py`` holds the walk; ROADMAP S15 for the two
    models that take float32).

    ``scale``: the branch is summed as :func:`residual` sums it, ``x + scale
    * FFN`` in float32 (MiniCPM's ``scale_depth / sqrt(depth)``); ``None``
    is the plain sum in ``x``'s dtype, not a multiplication by 1.0.

    ``sum_in_scope``: the sum lies inside the ``ffn`` scope, so a trace
    places the fusion whose root it is (the ``down`` product's) under
    ``ffn``; outside it that fusion is the enclosing scope's. Which a model
    has is how its first PR wrote it, and its by-scope metrics were first
    read so (ROADMAP D19)."""
    h = (layer_norm(x, p["ffn_norm/g"], p["ffn_norm/b"], eps)
         if norm == "layer" else rms_norm(x, p["ffn_norm/g"], eps))

    def summed(y):
        return x + y if scale is None else residual(x, y, scale)

    with jax.named_scope("ffn"):
        y = _gated(h, p["gate/w"], p["up/w"], p["down/w"], gate_dtype)
        if sum_in_scope:
            return summed(y)
    return summed(y)


__all__ = ["ffn_block", "gated_ffn", "gated_ffn_params", "layer_norm",
           "params", "residual", "rms_norm", "rope"]
