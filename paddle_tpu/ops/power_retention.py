"""Power retention of degree 2 — the chunked recurrence as a Pallas TPU
forward kernel, the one-token step as a second kernel, and the plain
``jnp`` forms of both.

Per key head ``c`` (``g = heads / kv_heads`` query heads ``h`` with ``h // g
= c`` read its state), with a gate ``gamma_t = exp(log_gamma_t)`` computed
from the token, ``q`` already scaled::

    a_tj = exp(sum_{s=j+1..t} log_gamma_s) (q_t . k_j)^2        j <= t
    o_t  = sum_j a_tj v_j / (sum_j a_tj + EPS)

``(x . y)^2 = phi(x) . phi(y)`` for the feature map of all products ``x_a
x_b``, so the sum over earlier tokens is a state and a key sum::

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T      z_t = gamma_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + EPS)

**The feature map as held.** The symmetric map has ``d (d + 1) / 2`` entries
(8,256 for ``d = 128``). Here the head's ``d`` dimensions are cut into tiles
of ``TILE = 8`` and a feature is kept for every ``(a, b)`` with ``b`` at or
after the start of ``a``'s tile, in that order: ``rows(d) = 8,704`` for ``d =
128``, 5.4% over the symmetric count, every tile whole sublanes. A pair
inside one tile is held twice (``(a, b)`` and ``(b, a)``), a pair across
tiles once, so the key side is unweighted (:func:`features` ``weighted=False``)
and the query side carries a 2 on the pairs across tiles.

**The state.** One float32 array ``[rows, kv_heads, d + NORM_ROWS, rows(d)]``
a layer: features on the lanes, a value's ``d`` dimensions on the first ``d``
sublanes (``S^T``), the key sum ``z`` on sublane ``d``, the rest of that
sublane tile zero. The key sum rides the state's own products: ``v`` is
extended by a one (and zeros), which makes ``z`` the ``d``-th value dimension.

A sequence is walked in chunks of ``CHUNK`` tokens; with ``b_t`` the running
sum of ``log_gamma`` inside the chunk::

    num_t = sum_{j<=t} exp(b_t - b_j) (q_t . k_j)^2 [v_j, 1] + exp(b_t) phi(q_t)^T [S, z]
    [S, z] <- exp(b_C) [S, z] + sum_j exp(b_C - b_j) phi(k_j) [v_j, 1]^T

Kernel ``retention_fwd``: grid ``(rows, kv_heads, chunks)``, chunks in order
(the innermost, ``arbitrary``); a key head's state stays in VMEM across its
chunks (the output block, whose index does not move with the chunk) and is
the input's own buffer in HBM (``input_output_aliases``). Everything is
computed transposed, tokens on the lanes: a grid step takes ``q^T [d, g C]``
(the group's ``g`` query heads side by side: 5 heads are columns of one key
head's tile), ``k [C, d]`` and ``k^T``, ``[v, 1]^T`` and the chunk's ``b`` as
a row and as a column. ``phi`` never exists in HBM: for a run of ``a`` whose
features start on a lane tile, the slab ``phi^T [features, tokens]`` is built
in VMEM as ``x^T[a] * x^T[tile(a):]``, a sublane broadcast each, and
multiplied with the matching lanes of the state at once.

Kernel ``retention_step``: one token a row. Grid ``(rows, kv_heads)``; a grid
step reads one key head's state once, decays it, adds ``[v, 1] phi(k)^T``,
writes it once (again in place) and reads the group's queries out of the new
state on the way: elementwise products and a lane reduction a query, no
matmul (a product with 5 columns would load the MXU's weights for 5 columns'
worth of work). ``phi`` of the one token comes in from outside (:func:`features`):
48 vectors a row against a state of 64 x 136 of them.

Precision: ``q k^T`` takes the operands' dtype with float32 accumulation,
the decayed squares are rounded to the operands' dtype once for the product
with ``[v, 1]`` (as the flash kernel rounds its probabilities); ``phi`` is
built in float32 and both products with the state run on float32 operands at
full precision (``(q . k)^2`` as a sum of 8,704 signed terms cancels to a
hundredth of their size, and the state is carried across a whole generation).

No backward: ROADMAP R5 queues the backward of the chunked scan.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NN, _NT, default_interpret

CHUNK = 256
DEGREE = 2
TILE = 8            # dimensions a tile of the feature map
NORM_ROWS = 8       # sublanes the key sum takes in the state (one is used)
EPS = 1e-6          # eps_n: guards a zero divisor, nothing else
_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 100 * 2 ** 20
_Q_LANES = 256      # query columns a slab of the kernel's readout
_STEP_LANES = 512   # features a slab of the step kernel


def rows(d: int) -> int:
    """Features held for a head of ``d`` dimensions."""
    n = d // TILE
    return TILE * (n * d - TILE * n * (n - 1) // 2)


def symmetric_rows(d: int) -> int:
    return d * (d + 1) // 2


def features(x, weighted: bool):
    """``x [..., d] -> [..., rows(d)]``, in ``x``'s dtype: ``x_a x_b`` for
    every ``a`` and every ``b`` from the start of ``a``'s tile on;
    ``weighted`` doubles the pairs across tiles, so that ``features(x, True)
    . features(y, False) = (x . y)^2``."""
    d = x.shape[-1]
    parts = []
    for i in range(0, d, TILE):
        right = x[..., i:]
        if weighted and i + TILE < d:
            right = jnp.concatenate([right[..., :TILE], 2.0 * right[..., TILE:]],
                                    axis=-1)
        parts.append((x[..., i:i + TILE, None] * right[..., None, :]).reshape(
            x.shape[:-1] + (-1,)))
    return jnp.concatenate(parts, axis=-1)


def _slabs(d: int):
    """``[(first tile start, end, first feature, features)]``: runs of whole
    tiles of ``a`` whose features start on a lane tile (one run where the
    head is too small for that to matter)."""
    out, start, first, at = [], 0, 0, 0
    for i in range(0, d, TILE):
        at += TILE * (d - i)
        if at % 128 == 0 or i + TILE == d:
            out.append((start, i + TILE, first, at - first))
            start, first = i + TILE, at
    return out


def extend_values(v):
    """``[..., d] -> [..., d + NORM_ROWS]``: a one, then zeros, behind ``v``."""
    one = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    return jnp.concatenate(
        [v, one, jnp.zeros(v.shape[:-1] + (NORM_ROWS - 1,), v.dtype)], axis=-1)


def empty_state(batch: int, kv_heads: int, d: int):
    return jnp.zeros((batch, kv_heads, d + NORM_ROWS, rows(d)), jnp.float32)


def first_products(state, head: int):
    """``[b, d + NORM_ROWS, d]`` of key head ``head``: the sums its state
    holds for the products of dimension 0 with every dimension ``c`` (the
    first ``d`` lanes), ``sum_j decay_j k_j0 k_jc [v_j, 1]`` with ``decay_j``
    the gates after token ``j`` multiplied up: a value's ``d`` dimensions,
    then the key sum, then the zeros that fill its sublane tile, down the
    rows (a slice of the state as it lies: cutting the rows at ``d + 1``
    made the compiler transpose a whole state to lay 129 rows out). What an
    audit of a carried state reads, whatever layout holds it."""
    return state[:, head, :, :state.shape[2] - NORM_ROWS]


def _read_out(total, d: int):
    """``[..., d + NORM_ROWS] -> [..., d]``: numerators over the divisor."""
    return total[..., :d] / (total[..., d:d + 1] + EPS)


def retention_chunk(q, k, v, log_gamma, state):
    """The plain form over one piece: ``q [b, s, h, d]`` (scaled), ``k, v
    [b, s, kv, d]``, ``log_gamma [b, s, kv]`` (float32, not positive),
    ``state [b, kv, d + NORM_ROWS, rows(d)]`` float32. Returns ``(o [b, s, h,
    d] float32, state)``. Quadratic in ``s``: the kernel's tail and the
    tests' yardstick."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    f32 = jnp.float32
    qg = q.astype(f32).reshape(b, s, kv, h // kv, d)
    k, ve = k.astype(f32), extend_values(v.astype(f32))
    cum = jnp.cumsum(log_gamma.astype(f32), axis=1)                 # [b, s, kv]
    diff = cum[:, :, None] - cum[:, None, :]                         # [b, t, j, kv]
    t = jnp.arange(s)
    decay = jnp.where((t[:, None] >= t[None, :])[None, :, :, None],
                      jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    scores = jnp.einsum("btcgd,bjcd->btjcg", qg, k, precision=_HIGHEST)
    weights = scores * scores * decay[..., None]
    total = jnp.einsum("btjcg,bjce->btcge", weights, ve, precision=_HIGHEST)
    total = total + jnp.exp(cum)[..., None, None] * jnp.einsum(
        "btcgf,bcef->btcge", features(qg, True), state, precision=_HIGHEST)
    k_decay = jnp.exp(cum[:, -1:] - cum)                             # [b, s, kv]
    state = (state * jnp.exp(cum[:, -1])[..., None, None]
             + jnp.einsum("bjce,bjcf->bcef", ve * k_decay[..., None],
                          features(k, False), precision=_HIGHEST))
    return _read_out(total, d).reshape(b, s, h, d), state


def retention_step_plain(q, k, v, log_gamma, state):
    """The recurrent form, one token: ``q [b, h, d]``, ``k, v [b, kv, d]``,
    ``log_gamma [b, kv]``. Returns ``(o [b, h, d] float32, state)``: the
    step kernel's yardstick."""
    b, h, d = q.shape
    kv = k.shape[1]
    f32 = jnp.float32
    state = (state * jnp.exp(log_gamma.astype(f32))[..., None, None]
             + extend_values(v.astype(f32))[..., :, None]
             * features(k.astype(f32), False)[..., None, :])
    total = jnp.einsum("bcgf,bcef->bcge",
                       features(q.astype(f32).reshape(b, kv, h // kv, d), True),
                       state, precision=_HIGHEST)
    return _read_out(total, d).reshape(b, h, d), state


def _slab(xt, other, start: int, end: int, cols=slice(None)):
    """``phi^T`` of the tiles ``start .. end`` of ``a``: ``[features,
    tokens]`` from ``xt [d, tokens]``; ``other`` is ``xt`` (the key side) or
    ``2 xt`` (the query side's pairs across tiles)."""
    pieces = []
    for i in range(start, end, TILE):
        right = xt[i:, cols]
        if other is not xt and i + TILE < xt.shape[0]:
            right = jnp.concatenate([right[:TILE], other[i + TILE:, cols]], axis=0)
        # (``lax.mul``, the primitive that ``*`` binds: a kernel body holds a
        # thousand of these products a layer, and each ``*`` is a jitted
        # ``jnp.multiply`` whose trace is one more event in
        # ``core/profiler``'s ring, 8,000 a trace of the generator)
        pieces += [jax.lax.mul(xt[a:a + 1, cols], right)
                   for a in range(i, i + TILE)]
    return jnp.concatenate(pieces, axis=0)


def _fwd_kernel(q_ref, k_ref, kt_ref, v_ref, brow_ref, bcol_ref, s0_ref,
                o_ref, s_ref, carried_ref, *, chunk: int, group: int, d: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[0, 0] = s0_ref[0, 0]

    f32 = jnp.float32
    wide = group * chunk
    qt, k, vt = q_ref[0, 0, 0], k_ref[0], v_ref[0, 0, 0]   # [d, gC] [C, d] [d+8, C]
    brow, bcol = brow_ref[0, 0, 0], bcol_ref[0, 0, 0]      # [1, C] [C, 1]
    bq = jnp.concatenate([brow] * group, axis=1)           # [1, gC]
    # inside the chunk: key j (sublanes) against query t of each head (lanes)
    scores = jax.lax.dot_general(k, qt, _NN, preferred_element_type=f32)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, wide), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, wide), 1) & (chunk - 1)
    weights = jnp.where(j <= t, jnp.exp(jnp.minimum(bq - bcol, 0.0)), 0.0)
    weights = weights * scores * scores
    total = jax.lax.dot_general(vt, weights.astype(vt.dtype), _NN,
                                preferred_element_type=f32)   # [d+8, gC]
    # what came before the chunk: phi(q)^T against the state, a slab at a time
    qf = qt.astype(f32)
    q2 = 2.0 * qf
    for start, end, first, n in _slabs(d):
        held = s_ref[0, 0, :, first:first + n]
        for c in range(0, wide, _Q_LANES):
            cols = slice(c, min(c + _Q_LANES, wide))
            part = jax.lax.dot_general(
                held, _slab(qf, q2, start, end, cols), _NN,
                preferred_element_type=f32, precision=_HIGHEST)
            if first:
                carried_ref[:, cols] = jax.lax.add(carried_ref[:, cols], part)
            else:
                carried_ref[:, cols] = part
    total = total + jnp.exp(bq) * carried_ref[...]
    o_ref[0, 0, 0] = (total[:d] / (total[d:d + 1] + EPS)).astype(o_ref.dtype)
    # the state at the chunk's end
    kf = kt_ref[0, 0, 0].astype(f32)
    b_end = brow[:, chunk - 1:chunk]                        # [1, 1]
    vd = vt.astype(f32) * jnp.exp(b_end - brow)
    keep = jnp.exp(b_end)
    for start, end, first, n in _slabs(d):
        s_ref[0, 0, :, first:first + n] = (
            s_ref[0, 0, :, first:first + n] * keep + jax.lax.dot_general(
                vd, _slab(kf, kf, start, end), _NT,
                preferred_element_type=f32, precision=_HIGHEST))


def _step_kernel(gamma_ref, v_ref, pk_ref, pq_ref, s_ref, o_ref, s_out_ref, *,
                 group: int, lanes: int):
    r, c = pl.program_id(0), pl.program_id(1)
    gamma = gamma_ref[r * pl.num_programs(1) + c]
    v = v_ref[0, 0]                                         # [d+8, 1]
    high, n = s_ref.shape[2], s_ref.shape[3]
    sums = [jnp.zeros((high, 1), jnp.float32)] * group
    for first in range(0, n, lanes):
        at = slice(first, first + lanes)
        # (``lax.add`` / ``lax.mul`` for ``+`` / ``*`` as in ``_slab``)
        new = jax.lax.add(s_ref[0, 0, :, at] * gamma,
                          jax.lax.mul(v, pk_ref[0, 0, :, at]))
        s_out_ref[0, 0, :, at] = new
        sums = [jax.lax.add(acc, jnp.sum(
            jax.lax.mul(new, pq_ref[0, 0, i:i + 1, at]), axis=1, keepdims=True))
            for i, acc in enumerate(sums)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (high, 128), 1)
    out = jnp.zeros((high, 128), jnp.float32)
    for i, acc in enumerate(sums):
        out = jnp.where(lane == i, acc, out)
    o_ref[0, 0] = out


def _record_plan(heads: int, kv_heads: int, d: int, seq: int, chunks: int,
                 tail: int, form: str):
    from ..core import profiler

    profiler.record_span(
        "retention.plan", time.time_ns(), 0, heads=heads, kv_heads=kv_heads,
        head_dim=d, degree=DEGREE, state_rows=rows(d),
        state_rows_symmetric=symmetric_rows(d), seq=seq, chunk=CHUNK,
        chunks=chunks, tail=tail, state_dtype="float32", gate="token",
        form=form)


def retention(q, k, v, log_gamma, state, num_heads: int, num_kv_heads: int,
              interpret=None):
    """``q [b, s, num_heads * d]`` (scaled), ``k, v [b, s, num_kv_heads *
    d]``, ``log_gamma [b, s, num_kv_heads]`` float32, ``state`` as
    :func:`empty_state` lays it out -> ``(o [b, s, num_heads * d] in q's
    dtype, state)``. Whole chunks of ``CHUNK`` tokens go through the kernel,
    a shorter tail through :func:`retention_chunk` (the state is exact at
    the sequence's own length either way)."""
    b, s, hd = q.shape
    d, kv = hd // num_heads, num_kv_heads
    g = num_heads // kv
    interpret = default_interpret() if interpret is None else interpret
    log_gamma = log_gamma.astype(jnp.float32)
    n = s // CHUNK
    _record_plan(num_heads, kv, d, s, n, s - n * CHUNK, "chunked")
    outs = []
    if n:
        whole, high = n * CHUNK, d + NORM_ROWS
        # tokens to the lanes: [b, kv, chunks, dims, (heads of the group x) tokens]
        qt = q[:, :whole].reshape(b, n, CHUNK, kv, g, d).transpose(
            0, 3, 1, 5, 4, 2).reshape(b, kv, n, d, g * CHUNK)
        heads_t = lambda a: a.reshape(b, n, CHUNK, kv, -1).transpose(0, 3, 1, 4, 2)
        kt = heads_t(k[:, :whole])
        vt = heads_t(extend_values(v[:, :whole].reshape(b, whole, kv, d)))
        cum = jnp.cumsum(log_gamma[:, :whole].reshape(b, n, CHUNK, kv), axis=2)
        cum = cum.transpose(0, 3, 1, 2)                     # [b, kv, n, C]
        per = lambda *shape: pl.BlockSpec(
            (1, 1, 1) + shape, lambda bi, h, c: (bi, h, c, 0, 0))
        st = pl.BlockSpec((1, 1, high, rows(d)), lambda bi, h, c: (bi, h, 0, 0))
        o, state = pl.pallas_call(
            functools.partial(_fwd_kernel, chunk=CHUNK, group=g, d=d),
            name="retention_fwd",
            grid=(b, kv, n),
            in_specs=[per(d, g * CHUNK),
                      pl.BlockSpec((1, CHUNK, d), lambda bi, h, c: (bi, c, h)),
                      per(d, CHUNK), per(high, CHUNK), per(1, CHUNK),
                      per(CHUNK, 1), st],
            out_specs=[per(d, g * CHUNK), st],
            scratch_shapes=[pltpu.VMEM((high, g * CHUNK), jnp.float32)],
            out_shape=[jax.ShapeDtypeStruct((b, kv, n, d, g * CHUNK), q.dtype),
                       jax.ShapeDtypeStruct(state.shape, jnp.float32)],
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(qt, k[:, :whole], kt, vt, cum[..., None, :], cum[..., :, None], state)
        outs.append(o.reshape(b, kv, n, d, g, CHUNK).transpose(
            0, 2, 5, 1, 4, 3).reshape(b, whole, hd))
    if s > n * CHUNK:
        cut = lambda a, heads: a[:, n * CHUNK:].reshape(b, -1, heads, d)
        o, state = retention_chunk(cut(q, num_heads), cut(k, kv), cut(v, kv),
                                   log_gamma[:, n * CHUNK:], state)
        outs.append(o.reshape(b, -1, hd).astype(q.dtype))
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)), state


def retention_step(q, k, v, log_gamma, state, num_heads: int,
                   num_kv_heads: int, interpret=None):
    """One token a row: ``q [b, num_heads * d]`` (scaled), ``k, v [b,
    num_kv_heads * d]``, ``log_gamma [b, num_kv_heads]`` -> ``(o [b,
    num_heads * d] in q's dtype, state)``, the state read and written once,
    in place."""
    b, hd = q.shape
    d, kv = hd // num_heads, num_kv_heads
    g, high, n = num_heads // kv, d + NORM_ROWS, rows(d)
    interpret = default_interpret() if interpret is None else interpret
    _record_plan(num_heads, kv, d, 1, 0, 0, "step")
    f32 = jnp.float32
    pq = features(q.astype(f32).reshape(b, kv, g, d), True)
    pk = features(k.astype(f32).reshape(b, kv, 1, d), False)
    ve = extend_values(v.astype(f32).reshape(b, kv, d))[..., None]
    lanes = _STEP_LANES if n % _STEP_LANES == 0 else n
    per = lambda *shape: pl.BlockSpec((1, 1) + shape,
                                      lambda r, c, gm: (r, c, 0, 0))
    total, state = pl.pallas_call(
        functools.partial(_step_kernel, group=g, lanes=lanes),
        name="retention_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv),
            in_specs=[per(high, 1), per(1, n), per(g, n), per(high, n)],
            out_specs=[per(high, 128), per(high, n)]),
        out_shape=[jax.ShapeDtypeStruct((b, kv, high, 128), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.exp(log_gamma.astype(f32)).reshape(-1), ve, pk, pq, state)
    o = _read_out(total[..., :g].transpose(0, 1, 3, 2), d)       # [b, kv, g, d]
    return o.reshape(b, hd).astype(q.dtype), state


__all__ = ["CHUNK", "DEGREE", "EPS", "NORM_ROWS", "TILE", "empty_state",
           "extend_values", "features", "first_products", "retention",
           "retention_chunk",
           "retention_step", "retention_step_plain", "rows", "symmetric_rows"]
