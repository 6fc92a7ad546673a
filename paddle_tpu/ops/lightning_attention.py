"""Lightning (linear) attention — the chunked recurrence as a Pallas TPU
forward kernel, and its plain ``jnp`` form.

Per head, with a fixed decay ``lambda = exp(log_decay)`` and a float32
state ``S [d, d]`` (``S_{-1}`` the state handed in)::

    S_t = lambda S_{t-1} + k_t^T v_t          o_t = q_t S_t

A sequence is walked in chunks of ``CHUNK`` rows. Inside a chunk the sum
over earlier rows is one masked quadratic form, and what came before the
chunk is in ``S``::

    O = ((Q K^T) * D) V + (Lambda * Q) S      D[t, s] = lambda^(t - s), t >= s
    S <- lambda^C S + (K * lambda^(C - 1 - s))^T V      Lambda[t] = lambda^(t + 1)

Kernel ``lightning_fwd``: grid ``(rows, heads, chunks)``, chunks in order
(the innermost, ``arbitrary``), ``S`` in VMEM scratch across a head's
chunks, read from the state handed in at the first and written out at the
last. q, k and v are ``[b, s, heads * d]`` as the projections leave them:
a grid step's block is a chunk of one head's 128 lanes, picked by the
index map, so nothing is transposed. The decay never enters the kernel as
a matrix: ``D`` is ``exp`` of an iota times the head's ``log_decay``, a
scalar the kernel reads from SMEM (scalar prefetch).

Precision: ``Q K^T`` and the product with ``V`` take the operands' dtype
with float32 accumulation, the decayed scores rounded to the operands'
dtype once (as the flash kernel rounds its probabilities); both products
with the state run on float32 operands, since the state is float32 and
is carried across a whole prompt.

No backward: ROADMAP R5 queues the backward of the chunked scan.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NN, _NT, _TN, default_interpret

CHUNK = 256


def lightning_chunk(q, k, v, log_decay, state):
    """The plain form over one piece: ``q, k, v [b, s, h, d]``,
    ``log_decay [h]`` (float32, negative), ``state [b, h, d, d]`` float32.
    Returns ``(o [b, s, h, d] float32, state)``. Quadratic in ``s``: the
    kernel's tail and the tests' yardstick."""
    s = q.shape[1]
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    t = jnp.arange(s, dtype=f32)
    ld = log_decay.astype(f32)[:, None, None]                    # [h, 1, 1]
    diff = t[:, None] - t[None, :]
    decay = jnp.where(diff >= 0, jnp.exp(ld * jnp.maximum(diff, 0.0)), 0.0)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) * decay[None]
    carried = jnp.einsum("bthd,bhde->bthe", q, state) * jnp.exp(
        log_decay.astype(f32)[None, :] * (t[:, None] + 1.0))[None, :, :, None]
    o = jnp.einsum("bhts,bshd->bthd", scores, v) + carried
    k_decay = jnp.exp(log_decay.astype(f32)[None, :] * (s - 1.0 - t[:, None]))
    state = (state * jnp.exp(ld * s)[None]
             + jnp.einsum("bshd,bshe->bhde", k * k_decay[None, :, :, None], v))
    return o, state


def _kernel(ld_ref, q_ref, k_ref, v_ref, s0_ref, o_ref, s_out_ref, s_scr, *,
            chunk: int):
    h, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0, 0]

    f32 = jnp.float32
    ld = ld_ref[h]
    q, k, v = q_ref[0], k_ref[0], v_ref[0]                        # [C, d]
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = (t - s).astype(f32)
    decay = jnp.where(diff >= 0, jnp.exp(ld * jnp.maximum(diff, 0.0)), 0.0)
    scores = jax.lax.dot_general(q, k, _NT, preferred_element_type=f32) * decay
    intra = jax.lax.dot_general(scores.astype(v.dtype), v, _NN,
                                preferred_element_type=f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(f32)
    state = s_scr[...]
    carried = jax.lax.dot_general(q.astype(f32) * jnp.exp(ld * (row + 1.0)),
                                  state, _NN, preferred_element_type=f32)
    o_ref[0] = (intra + carried).astype(o_ref.dtype)
    k_decay = k.astype(f32) * jnp.exp(ld * (chunk - 1.0 - row))
    s_scr[...] = state * jnp.exp(ld * chunk) + jax.lax.dot_general(
        k_decay, v.astype(f32), _TN, preferred_element_type=f32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0, 0] = s_scr[...]


def _record_plan(heads: int, d: int, seq: int, chunks: int, tail: int, state):
    from ..core import profiler

    profiler.record_span(
        "lightning.plan", time.time_ns(), 0, heads=heads, head_dim=d, seq=seq,
        chunk=CHUNK, chunks=chunks, tail=tail, state_dtype=str(state.dtype))


def lightning_attention(q, k, v, log_decay, state, num_heads: int,
                        interpret=None):
    """``q, k, v [b, s, num_heads * d]`` (q already scaled), ``log_decay
    [num_heads]``, ``state [b, num_heads, d, d]`` float32 -> ``(o [b, s,
    num_heads * d] in q's dtype, state)``. Whole chunks of ``CHUNK`` rows
    go through the kernel, a shorter tail through :func:`lightning_chunk`
    (the state is exact at the sequence's own length either way)."""
    b, s, hd = q.shape
    d = hd // num_heads
    interpret = default_interpret() if interpret is None else interpret
    log_decay = log_decay.astype(jnp.float32)
    n = s // CHUNK
    _record_plan(num_heads, d, s, n, s - n * CHUNK, state)
    outs = []
    if n:
        whole = n * CHUNK
        block = pl.BlockSpec((1, CHUNK, d), lambda bi, h, c, ld: (bi, c, h))
        st = pl.BlockSpec((1, 1, d, d), lambda bi, h, c, ld: (bi, h, 0, 0))
        o, state = pl.pallas_call(
            functools.partial(_kernel, chunk=CHUNK),
            name="lightning_fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b, num_heads, n),
                in_specs=[block, block, block, st], out_specs=[block, st],
                scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((b, whole, hd), q.dtype),
                       jax.ShapeDtypeStruct(state.shape, jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(log_decay, q[:, :whole], k[:, :whole], v[:, :whole], state)
        outs.append(o)
    if s > n * CHUNK:
        cut = lambda a: a[:, n * CHUNK:].reshape(b, -1, num_heads, d)
        o, state = lightning_chunk(cut(q), cut(k), cut(v), log_decay, state)
        outs.append(o.reshape(b, -1, hd).astype(q.dtype))
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)), state


__all__ = ["CHUNK", "lightning_attention", "lightning_chunk"]
