"""The selective scan of a Mamba-1 layer (arXiv:2312.00752) — the recurrence
over a run of tokens as a Pallas TPU forward kernel, its one-token form and
its plain ``lax.scan`` form.

A channel ``c`` of ``d_inner`` carries ``d_state`` numbers. With the step
``Delta_t[c] > 0`` (input-dependent), ``A[n, c] < 0`` (the layer's own),
``B_t[n]`` and ``C_t[n]`` (input-dependent, shared by the channels) and
``u_t[c] = Delta_t[c] x_t[c]``::

    S_t[n, c] = exp(Delta_t[c] A[n, c]) S_{t-1}[n, c] + u_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

(the skip ``D x_t`` and the gate are the caller's, ``layers/sambay.py``).
Unlike ``lightning_fwd`` (``ops/lightning_attention.py``: one fixed decay a
head), ``retention_fwd`` (``ops/power_retention.py``: a gate a token and key
head) and ``ssd_fwd`` (``ops/ssd.py``: Mamba-2, one scalar a head and token),
whose chunks are products on the MXU, this has no matrix-product form: the
decay is a channel's and a state's own, so a chunk cannot be written as a
masked quadratic form times a value. The work is on the vector
units, one ``exp`` and five multiply-adds a state element and token, and is
bound by them and by the bytes of ``Delta``, ``u`` and ``y``.

The state is float32 ``[rows, d_state, d_inner]``: states on sublanes,
channels on lanes, so that the TPU's (8, 128) tiles pad nothing (a
``[rows, d_inner, 16]`` array is held at eight times its size) and a
token's ``Delta`` and ``u`` are rows that broadcast down the sublanes for
nothing.

Kernel ``mamba_fwd``: grid ``(rows, token blocks)``, the blocks in order
(``arbitrary``), a row's whole state ``[d_state, d_inner]`` in VMEM scratch
across them, read from the state handed in at the first and written out at
the last. Inside a step the channels are walked in tiles of ``LANES`` (the
walk written out: a lane offset is static), a tile's state held in
registers across the block's ``BLOCK`` tokens. ``B_t[n]`` and ``C_t[n]``
must lie along sublanes and be the same in every lane; the caller of the
kernel hands them in already spread over one 128-lane group (``[rows, s,
d_state, 128]`` float32, made by one XLA broadcast: 8 KB a token beside the
60 KB of ``Delta``, ``u`` and ``y``), since a lane broadcast of a column
picked at a traced index is the one thing the vector units do badly.

No backward: training through the scan is not written (ROADMAP Reach).
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

BLOCK = 64      # tokens a grid step: 76 KB a token of blocks, double-buffered
LANES = 512     # channels a tile of the walk: [16, 512] float32 is 8 registers


def mamba_step(delta, u, b, c, a, state):
    """One token: ``delta, u [rows, d_inner]`` float32, ``b, c [rows,
    d_state]``, ``a [d_state, d_inner]`` (negative), ``state [rows, d_state,
    d_inner]`` float32 -> ``(y [rows, d_inner] float32, state)``. Plain
    ``jnp``: one fusion that reads and writes every state once."""
    f32 = jnp.float32
    delta, u = delta.astype(f32), u.astype(f32)
    state = (jnp.exp(delta[:, None, :] * a.astype(f32)[None]) * state
             + u[:, None, :] * b.astype(f32)[:, :, None])
    return jnp.sum(state * c.astype(f32)[:, :, None], axis=1), state


def mamba_scan(delta, u, b, c, a, state):
    """The definition over a run: ``delta, u [rows, s, d_inner]``, ``b, c
    [rows, s, d_state]`` -> ``(y [rows, s, d_inner] float32, state)``, a
    token at a time under ``lax.scan``. The kernel's tail and the tests'
    yardstick."""
    def step(state, xs):
        y, state = mamba_step(*xs, a, state)
        return state, y

    state, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (delta, u, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def _kernel(delta_ref, u_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_out_ref,
            s_scr, *, block: int, lanes: int, d_inner: int):
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _():
        s_scr[...] = s0_ref[0]

    groups, n = lanes // 128, a_ref.shape[0]
    # row ``i`` of a ``[8, 128]`` tile, down the ``n`` sublanes of a state
    down = lambda tile, i: lax.broadcast_in_dim(
        lax.slice(tile, (i, 0), (i + 1, 128)), (n, 128), (0, 1))
    for c0 in range(0, d_inner, lanes):
        at = [pl.ds(c0 + 128 * g, 128) for g in range(groups)]
        a = [a_ref[:, lane] for lane in at]                    # [n, 128] each

        def eight(t8, states, at=at, a=a):
            # eight tokens a turn: a traced row offset has to be a whole
            # (8, 128) tile's, so a tile of ``Delta``, ``u`` and ``y`` is
            # moved at once and a token is a static row of it. (``lax``
            # primitives for ``*``, ``+``, ``exp`` and the sum: a kernel body
            # holds a few thousand of them, and each ``jnp`` one is a traced
            # call that leaves an event in ``core/profiler``'s ring.)
            rows = pl.ds(pl.multiple_of(t8 * 8, 8), 8)
            delta = [delta_ref[0, rows, lane] for lane in at]   # [8, 128]
            u = [u_ref[0, rows, lane] for lane in at]
            ys = [[] for _ in at]
            states = list(states)
            for i in range(8):
                bt, ct = b_ref[0, t8 * 8 + i], c_ref[0, t8 * 8 + i]  # [n, 128]
                for g in range(groups):
                    decay = lax.exp(lax.mul(down(delta[g], i), a[g]))
                    s = lax.add(lax.mul(decay, states[g]),
                                lax.mul(down(u[g], i), bt))
                    ys[g].append(lax.reduce_sum(lax.mul(s, ct), (0,)))
                    states[g] = s
            for g, lane in enumerate(at):
                y_ref[0, rows, lane] = lax.concatenate(
                    [lax.broadcast_in_dim(y, (1, 128), (1,)) for y in ys[g]], 0)
            return tuple(states)

        states = jax.lax.fori_loop(0, block // 8, eight,
                                   tuple(s_scr[:, lane] for lane in at))
        for lane, s in zip(at, states):
            s_scr[:, lane] = s

    @pl.when(t_blk == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = s_scr[...]


def _record_plan(rows, tokens, d_inner, d_state, blocks, tail, state):
    from ..core import profiler

    profiler.record_span(
        "mamba.plan", time.time_ns(), 0, rows=rows, tokens=tokens,
        d_inner=d_inner, d_state=d_state, chunk=BLOCK, blocks=blocks,
        tail=tail, lanes=min(LANES, d_inner), state_dtype=str(state.dtype),
        state_bytes=state.size * state.dtype.itemsize)


def selective_scan(delta, u, b, c, a, state, interpret=None):
    """``delta, u [rows, s, d_inner]`` float32, ``b, c [rows, s, d_state]``,
    ``a [d_state, d_inner]`` float32 (negative), ``state [rows, d_state,
    d_inner]`` float32 -> ``(y [rows, s, d_inner] float32, state)``. Whole
    blocks of ``BLOCK`` tokens go through the kernel, a shorter tail through
    :func:`mamba_scan` (the state is exact at the run's own length either
    way). ``d_inner`` is a multiple of 128 lanes."""
    from ..core.errors import enforce

    rows, s, d_inner = delta.shape
    n = a.shape[0]
    lanes = min(LANES, d_inner)
    enforce(d_inner % lanes == 0 and lanes % 128 == 0 and n % 8 == 0,
            f"selective_scan: d_inner {d_inner} in tiles of {lanes} lanes, "
            f"d_state {n} in sublanes of 8")
    interpret = default_interpret() if interpret is None else interpret
    f32 = jnp.float32
    delta, u, a = delta.astype(f32), u.astype(f32), a.astype(f32)
    blocks = s // BLOCK
    whole = blocks * BLOCK
    _record_plan(rows, s, d_inner, n, blocks, s - whole, state)
    outs = []
    if blocks:
        spread = lambda x: jnp.broadcast_to(
            x[:, :whole].astype(f32)[..., None], (rows, whole, n, 128))
        wide = pl.BlockSpec((1, BLOCK, d_inner), lambda r, t: (r, t, 0))
        narrow = pl.BlockSpec((1, BLOCK, n, 128), lambda r, t: (r, t, 0, 0))
        st = pl.BlockSpec((1, n, d_inner), lambda r, t: (r, 0, 0))
        y, state = pl.pallas_call(
            functools.partial(_kernel, block=BLOCK, lanes=lanes,
                              d_inner=d_inner),
            name="mamba_fwd",
            grid=(rows, blocks),
            in_specs=[wide, wide, narrow, narrow,
                      pl.BlockSpec((n, d_inner), lambda r, t: (0, 0)), st],
            out_specs=[wide, st],
            out_shape=[jax.ShapeDtypeStruct((rows, whole, d_inner), f32),
                       jax.ShapeDtypeStruct(state.shape, f32)],
            scratch_shapes=[pltpu.VMEM((n, d_inner), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(delta[:, :whole], u[:, :whole], spread(b), spread(c), a, state)
        outs.append(y)
    if s > whole:
        y, state = mamba_scan(delta[:, whole:], u[:, whole:], b[:, whole:],
                              c[:, whole:], a, state)
        outs.append(y)
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)), state


__all__ = ["BLOCK", "LANES", "mamba_scan", "mamba_step", "selective_scan"]
