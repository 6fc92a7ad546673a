"""The state-space-dual recurrence of a Mamba-2 layer (arXiv:2405.21060) —
over a run of tokens as a Pallas TPU forward kernel, its one-token form and
its plain ``lax.scan`` form.

A head ``h`` of ``heads`` carries a state ``S [head_dim, d_state]``. With the
step ``dt_t[h] > 0`` (input-dependent), ``a[h] < 0`` (the layer's own, one
scalar a head), ``B_t`` and ``C_t [d_state]`` (input-dependent, every
head's: one group) and ``u_t = dt_t x_t`` (taken in float32)::

    S_t = exp(dt_t a) S_{t-1} + u_t B_t^T
    y_t = S_t C_t

(the skip ``D x_t``, the gate and the norm are the caller's,
``layers/mamba2.py``). Unlike Mamba-1's (``ops/selective_scan.py``) the decay
is one scalar a head and token, so a chunk of ``Q`` tokens is a masked
quadratic form times a value, with ``L`` the running sum of ``dt a`` inside
the chunk::

    y_t   = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) u_s  +  exp(L_t) S_prev C_t
    S_new = exp(L_end) S_prev + sum_s exp(L_end - L_s) u_s B_s^T

and the work is on the MXU: ``C B^T`` ``[Q, Q]`` for every head at once, and
a head's ``[Q, Q] x [Q, head_dim]``, ``[Q, d_state] x [d_state, head_dim]``
and ``[d_state, Q] x [Q, head_dim]``.

**How heads share lanes.** A head is 64 wide and a vector register 128
lanes, so the heads are taken in *lane groups* of ``128 // head_dim`` (two)
as ``x`` holds them, side by side: a group's ``u`` is ``[Q, 128]``, and head
``i`` of it is that array with the other heads' lanes zeroed, so that every
product has a full 128-lane result and a group's two results add into one
array with no lane moved. Half the multiply-adds of the quadratic form meet
a zero; a 64-wide result would leave the same half of the MXU's columns
idle.

**The state** is float32 ``[rows, groups, d_state, 128]``: a lane group's
states transposed, the state index on sublanes and the group's head channels
on lanes (``state[r, g, n, i * head_dim + p] = S_(g * per + i)[p, n]``), so
that both of its products are plain ``[m, k] x [k, n]`` ones and a one-token
step broadcasts a head's decay down the sublanes. :func:`head_states` gives
the published ``[rows, heads, head_dim, d_state]`` view.

Kernel ``ssd_fwd``: grid ``(rows, group blocks, chunks)``, the chunks in
order (``arbitrary``), a block of ``GROUPS`` lane groups' states in VMEM
across a call's chunks (the output block itself: its index does not move
with the chunk), read from the state handed in at the first chunk and
written out once after the last; the state handed in is the state handed
back (``input_output_aliases``). ``C B^T`` is made once a grid step, for the
block's ``GROUPS * per`` heads. A head's ``L`` and ``dt`` down the sublanes
come out of ``[Q, heads]`` by a masked sum over lanes (one nonzero a row:
exact). Decays are ``exp`` of ``L_t - L_s <= 0`` in float32, masked before
the ``exp``, never a quotient of two ``exp``. ``u = dt x`` is taken in
float32 from the bfloat16 ``x``. The products take bfloat16 operands
(``OPERAND``) and accumulate in float32: ``u`` rounded once for the
quadratic form; the state's own update takes ``u`` times its float32 decay
weight as two bfloat16 terms (the rounded product and what the rounding
left), so that what is carried is float32-exact given the ``dt``, ``x`` and
``B`` the recurrence was handed. A ragged run is padded with tokens of ``dt = 0``,
which decay nothing and add nothing.

No backward: training through the scan is not written (ROADMAP R5).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import default_interpret

CHUNK = 256     # tokens a chunk: the published ``mamba_chunk_size``
GROUPS = 4      # lane groups (of 128 // head_dim heads) a grid step holds
LANES = 128
_NEG = -1e30    # exp(_NEG) == 0: a key after the query
OPERAND = jnp.bfloat16   # what the kernel's products take, whatever the model's dtype


def _per_group(head_dim: int) -> int:
    from ..core.errors import enforce

    enforce(LANES % head_dim == 0,
            f"ssd: heads of {head_dim} do not share {LANES} lanes evenly")
    return LANES // head_dim


def empty_state(rows: int, heads: int, head_dim: int, d_state: int):
    """A zero state in the layout the kernel and the step carry."""
    per = _per_group(head_dim)
    return jnp.zeros((rows, heads // per, d_state, LANES), jnp.float32)


def head_states(state, head_dim: int):
    """``state [rows, groups, d_state, 128]`` as the published ``[rows,
    heads, head_dim, d_state]``."""
    rows, groups, n, _ = state.shape
    per = _per_group(head_dim)
    return state.reshape(rows, groups, n, per, head_dim).transpose(
        0, 1, 3, 4, 2).reshape(rows, groups * per, head_dim, n)


# -- the definition, a token at a time ----------------------------------------------


def ssd_step(dt, x, b, c, a, state):
    """One token: ``x [rows, heads * head_dim]``, ``dt [rows, heads]``
    float32, ``b, c [rows, d_state]``, ``a [heads]`` (negative), ``state
    [rows, groups, d_state, 128]`` float32 -> ``(y [rows, heads * head_dim]
    float32, state)``. Plain ``jnp``: one fusion that reads and writes every
    state once."""
    f32 = jnp.float32
    rows, groups, n, lanes = state.shape
    hd = x.shape[-1] // dt.shape[-1]
    dt = dt.astype(f32)
    wide = lambda v: jnp.repeat(v, hd, axis=-1).reshape(rows, groups, 1, lanes)
    u = wide(dt) * x.astype(f32).reshape(rows, groups, 1, lanes)
    state = (wide(jnp.exp(dt * a.astype(f32))) * state
             + u * b.astype(f32)[:, None, :, None])
    y = jnp.sum(state * c.astype(f32)[:, None, :, None], axis=2)
    return y.reshape(rows, groups * lanes), state


def ssd_scan(dt, x, b, c, a, state):
    """The definition over a run: ``x [rows, s, heads * head_dim]``, ``dt
    [rows, s, heads]``, ``b, c [rows, s, d_state]`` -> ``(y [rows, s, heads *
    head_dim] float32, state)``, a token at a time under ``lax.scan``: the
    tests' yardstick."""
    def step(state, xs):
        y, state = ssd_step(*xs, a, state)
        return state, y

    state, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (dt, x, b, c)))
    return jnp.moveaxis(y, 0, 1), state


# -- the kernel ----------------------------------------------------------------------


def _kernel(x_ref, c_ref, bt_ref, dt_ref, la_ref, lat_ref, s0_ref, y_ref, s_ref,
            *, groups: int, per: int, head_dim: int):
    """One chunk of one row's ``groups`` lane groups. ``x_ref [1, Q, groups
    * 128]``, ``c_ref [1, Q, n]``, ``bt_ref [1, n, Q]`` (``B`` transposed:
    tokens on lanes), ``dt_ref`` and ``la_ref [1, Q, heads]`` the steps and
    their running sums ``L`` (every head's, tokens on sublanes), ``lat_ref
    [1, groups * per, Q]`` this block's heads' ``L`` with tokens on lanes,
    ``s0_ref`` / ``s_ref [1, groups, n, 128]`` the state in and out. ``lax``
    primitives where a ``jnp`` operator would do: each ``jnp`` call on a
    tracer leaves an event in ``core/profiler``'s ring."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    block, chunk = pl.program_id(1), pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = s0_ref[...]

    q = x_ref.shape[1]
    heads = la_ref.shape[2]
    dot = functools.partial(lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=f32)
    c, bt = c_ref[0], bt_ref[0]
    cb = dot(c, bt)                                         # [Q, Q]: C_t . B_s
    seen = lax.ge(lax.broadcasted_iota(jnp.int32, (q, q), 0),
                  lax.broadcasted_iota(jnp.int32, (q, q), 1))
    la, dt = la_ref[0], dt_ref[0]                           # [Q, heads]
    head_at = lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    lane_head = lax.div(lax.broadcasted_iota(jnp.int32, (1, LANES), 1),
                        jnp.int32(head_dim))
    first = lax.mul(block, jnp.int32(groups * per))         # this block's first head
    zeros = lambda shape: lax.full(shape, 0.0, f32)
    spread = lambda col, shape: lax.broadcast_in_dim(col, shape, (0, 1))

    def down(values, head):
        # one head's column of ``values [Q, heads]`` down the sublanes, [Q, 1]
        # (one nonzero a row: the sum is exact)
        return jnp.sum(lax.select(lax.eq(head_at, head), values,
                                  zeros((q, heads))), axis=1, keepdims=True)

    for g in range(groups):
        lanes = pl.ds(g * LANES, LANES)
        x = x_ref[0, :, lanes]                              # [Q, 128]
        state = s_ref[0, g]                                 # [n, 128] float32
        carried = dot(c, state.astype(bf16))                # [Q, 128]: S_prev C_t
        step, into_y, into_s = (zeros((q, LANES)),) * 3
        kept = zeros((1, LANES))
        forms, mine = [], []
        for i in range(per):
            head = lax.add(first, jnp.int32(g * per + i))
            mine.append(spread(lax.eq(lane_head, jnp.int32(i)), (q, LANES)))
            col = down(la, head)                            # [Q, 1]: L_t
            row = lat_ref[0, g * per + i:g * per + i + 1, :]   # [1, Q]: L_s
            diff = lax.sub(spread(col, (q, q)), spread(row, (q, q)))
            decay = lax.exp(lax.select(seen, diff, lax.full((q, q), _NEG, f32)))
            forms.append(lax.mul(decay, cb).astype(bf16))   # [Q, Q]
            end = col[q - 1:q, :]                           # [1, 1]: L_end
            step = lax.select(mine[i], spread(down(dt, head), (q, LANES)), step)
            into_y = lax.select(mine[i], spread(lax.exp(col), (q, LANES)), into_y)
            into_s = lax.select(mine[i], spread(lax.exp(lax.sub(
                spread(end, (q, 1)), col)), (q, LANES)), into_s)
            kept = lax.select(mine[i][:1], spread(lax.exp(end), (1, LANES)), kept)
        u = lax.mul(x.astype(f32), step)                    # dt x, float32
        value = u.astype(bf16)
        within = zeros((q, LANES))
        for form, here in zip(forms, mine):
            within = lax.add(within, dot(form, lax.select(
                here, value, lax.full((q, LANES), 0, bf16))))
        y_ref[0, :, lanes] = lax.add(within, lax.mul(into_y, carried)
                                     ).astype(y_ref.dtype)
        weighted = lax.mul(u, into_s)                       # [Q, 128]
        high = weighted.astype(bf16)
        low = lax.sub(weighted, high.astype(f32)).astype(bf16)
        s_ref[0, g] = lax.add(lax.mul(spread(kept, state.shape), state),
                              lax.add(dot(bt, high), dot(bt, low)))


def _record_plan(rows, tokens, heads, head_dim, d_state, chunk, chunks, state,
                 operand_bytes):
    from ..core import profiler

    profiler.record_span(
        "ssd.plan", time.time_ns(), 0, rows=rows, tokens=tokens, heads=heads,
        head_dim=head_dim, d_state=d_state, chunk=chunk, chunks=chunks,
        groups=GROUPS, state_dtype=str(state.dtype),
        state_bytes=state.size * state.dtype.itemsize,
        state_bytes_moved=2 * state.size * state.dtype.itemsize,
        operand_bytes=operand_bytes)


def ssd(dt, x, b, c, a, state, chunk: int = CHUNK, interpret=None):
    """``x [rows, s, heads * head_dim]``, ``dt [rows, s, heads]`` float32,
    ``b, c [rows, s, d_state]``, ``a [heads]`` float32 (negative), ``state
    [rows, groups, d_state, 128]`` float32 -> ``(y [rows, s, heads *
    head_dim]`` in ``x``'s dtype``, state)``. ``x``, ``b`` and ``c`` are
    taken in ``OPERAND`` (bfloat16) whatever they come in. The run goes through the kernel
    in chunks of ``chunk`` tokens (a run shorter than one: a single chunk of
    its own length), the last padded with tokens of ``dt = 0``."""
    from ..core.errors import enforce

    f32, bf16 = jnp.float32, jnp.bfloat16
    rows, s, width = x.shape
    heads, n = dt.shape[-1], b.shape[-1]
    hd = width // heads
    per = _per_group(hd)
    groups = heads // per
    enforce(heads % per == 0 and state.shape == (rows, groups, n, LANES),
            f"ssd: state {state.shape} for {heads} heads of {hd}, d_state {n}")
    interpret = default_interpret() if interpret is None else interpret
    block = min(GROUPS, groups)
    enforce(groups % block == 0, f"ssd: {groups} lane groups in {block}s")
    q = min(chunk, -(-s // 8) * 8)
    chunks = -(-s // q)
    pad = chunks * q - s
    out_dtype, x, dt = x.dtype, x.astype(bf16), dt.astype(f32)
    if pad:
        padded = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        x, dt, b, c = padded(x), padded(dt), padded(b), padded(c)
    steps = dt * a.astype(f32)                              # [rows, s, heads]
    # L: the running sum inside each chunk, float32
    la = jnp.cumsum(steps.reshape(rows, chunks, q, heads), axis=2
                    ).reshape(rows, chunks * q, heads)
    _record_plan(rows, s, heads, hd, n, q, chunks, state,
                 rows * chunks * q * (2 * 2 * width + 2 * 2 * n + 3 * 4 * heads))
    y, state = pl.pallas_call(
        functools.partial(_kernel, groups=block, per=per, head_dim=hd),
        name="ssd_fwd",
        grid=(rows, groups // block, chunks),
        in_specs=[
            pl.BlockSpec((1, q, block * LANES), lambda r, g, t: (r, t, g)),
            pl.BlockSpec((1, q, n), lambda r, g, t: (r, t, 0)),
            pl.BlockSpec((1, n, q), lambda r, g, t: (r, 0, t)),
            pl.BlockSpec((1, q, heads), lambda r, g, t: (r, t, 0)),
            pl.BlockSpec((1, q, heads), lambda r, g, t: (r, t, 0)),
            pl.BlockSpec((1, block * per, q), lambda r, g, t: (r, g, t)),
            pl.BlockSpec((1, block, n, LANES), lambda r, g, t: (r, g, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, q, block * LANES), lambda r, g, t: (r, t, g)),
            pl.BlockSpec((1, block, n, LANES), lambda r, g, t: (r, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, chunks * q, width), out_dtype),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, c.astype(bf16), jnp.swapaxes(b, 1, 2).astype(bf16), dt, la,
      jnp.swapaxes(la, 1, 2), state)
    return (y[:, :s] if pad else y), state


__all__ = ["CHUNK", "GROUPS", "OPERAND", "empty_state", "head_states", "ssd", "ssd_scan",
           "ssd_step"]
