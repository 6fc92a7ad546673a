"""Block-selected attention (InfLLM-V2's second stage) — a Pallas TPU
forward kernel over a key/value cache of grouped heads, and its plain
``jnp`` form.

A query at position ``i`` attends the keys ``<= i`` of a set of key blocks
(``block`` keys each): the first ``init_blocks``, the ``window_blocks``
ending with its own, and up to ``n_sel`` further blocks chosen for it by a
scorer (``layers/sala.py``), one choice for the ``group`` query heads that
share a key head. The forced blocks are the same for every query of one
query block, the chosen ones are the query's own, so kernel
``sparse_fwd`` has two halves, merged by their log-sum-exp:

- **the dense half**: a query block's ``block x group`` rows (1024 x 128 at
  64 queries of 16 heads) against the window's keys and the first blocks,
  a tile walk with an online softmax and the causal mask, as the flash
  kernel walks; its running max, sum and accumulator are left in VMEM
  scratch;
- **the gathered half**: a loop over the block's queries; a query's
  ``n_sel`` blocks are cut out of the resident keys by their indices, read
  from SMEM, and laid end to end, so that its ``group`` heads meet all of
  them in one ``[group, 128] x [128, n_sel * block]`` product (16 of the
  MXU's 128 rows: the half that selection makes a gather, PERF.md
  section 6, PR 33). Chosen blocks lie before the window, so no key of
  them is masked but those of absent choices (a count beside the indices).

Grid ``(rows, key heads, query blocks)``. A key head's whole ``[T, 128]``
key and value slabs are one block of the lane-dense ``[rows, T, heads *
128]`` cache, resident in VMEM across the query blocks of a call (the
index map does not move, so they are brought in once a row and head):
8.4 MB each at 32,896 keys, which bounds the context this kernel takes
(:data:`RESIDENT_BYTES`). Queries and outputs are ``[rows, key heads,
queries * group, 128]``, a query's heads on consecutive rows.

No backward: ROADMAP R15 queues it.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.errors import enforce
from .flash_attention import _NN, _NT, NEG_INF, default_interpret

# VMEM the resident key and value slabs of one key head may take (two
# buffers each); the chip has 128 MiB.
RESIDENT_BYTES = 80 << 20
ROW_TILE = 256      # rows of the dense half's score tile
KEY_TILE = 512      # its keys


def selection_mask(sel, positions, total: int, *, block: int,
                   window_blocks: int, init_blocks: int):
    """``[..., q, total]`` booleans: may the query at ``positions[q]``
    attend key ``t``? ``sel [..., q, n_sel + 1]`` holds a query's chosen
    block indices and, last, how many of them count."""
    n_sel = sel.shape[-1] - 1
    t = jnp.arange(total)
    own = positions // block                                      # [q]
    key_block = t // block
    forced = ((key_block[None, :] < init_blocks)
              | (key_block[None, :] > (own - window_blocks)[:, None]))
    live = jnp.arange(n_sel) < sel[..., n_sel:]                    # [..., q, n_sel]
    chosen = jnp.any((sel[..., :n_sel, None] == key_block) & live[..., None],
                     axis=-2)                                      # [..., q, total]
    return (forced | chosen) & (t[None, :] <= positions[:, None])


def sparse_attention_jnp(q, k_cache, v_cache, sel, p0, *, group: int,
                         block: int, window_blocks: int, init_blocks: int,
                         scale: float):
    """The plain form of :func:`sparse_attention`: a ``[queries, keys]``
    mask from the selection and one masked softmax over the whole cache."""
    b, n_kv, rows, d = q.shape
    total = k_cache.shape[1]
    n_q = rows // group
    mask = selection_mask(sel, p0 + jnp.arange(n_q), total, block=block,
                          window_blocks=window_blocks, init_blocks=init_blocks)
    f32 = jnp.float32
    k = k_cache.reshape(b, total, n_kv, d).astype(f32)
    v = v_cache.reshape(b, total, n_kv, d).astype(f32)
    s = jnp.einsum("bcqgd,btcd->bcqgt",
                   q.reshape(b, n_kv, n_q, group, d).astype(f32), k) * scale
    p = jax.nn.softmax(jnp.where(mask[:, :, :, None, :], s, NEG_INF), axis=-1)
    o = jnp.einsum("bcqgt,btcd->bcqgd", p, v)
    return o.reshape(b, n_kv, rows, d).astype(q.dtype)


def _kernel(p0_ref, sel_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, group, block, window_blocks, init_blocks, n_sel, scale,
            row_tile, key_tile):
    f32 = jnp.float32
    rows = block * group
    qb = p0_ref[0] // block + pl.program_id(2)
    first = jnp.maximum(qb - (window_blocks - 1), 0)   # the window's first block
    n_tiles = window_blocks * block // key_tile

    def scores(q, keys, key_pos, q_pos, extra=None):
        s = jax.lax.dot_general(q, keys, _NT, preferred_element_type=f32) * scale
        seen = key_pos <= q_pos
        if extra is not None:
            seen = seen & extra
        return jnp.where(seen, s, NEG_INF)

    def fold(carry, s, values):
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        a = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, l * a + jnp.sum(p, axis=1, keepdims=True),
                acc * a + jax.lax.dot_general(p.astype(values.dtype), values,
                                              _NN, preferred_element_type=f32))

    # -- the dense half: the window and the first blocks, a row tile at a time
    for r0 in range(0, rows, row_tile):
        q = q_ref[0, 0, r0:r0 + row_tile, :]
        q_pos = qb * block + (r0 + jax.lax.broadcasted_iota(
            jnp.int32, (row_tile, 1), 0)) // group

        def tile(j, carry):
            start = pl.multiple_of(first * block + j * key_tile, block)
            key_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, key_tile), 1)
            s = scores(q, k_ref[0, pl.ds(start, key_tile), :], key_pos, q_pos)
            return fold(carry, s, v_ref[0, pl.ds(start, key_tile), :])

        # a query's own key is in the window, so the running max is finite
        # from the tile that holds it on; before it, exp(NEG_INF - NEG_INF)
        # counts masked keys, and the tile that holds it wipes them out
        # (a = exp(NEG_INF - m) = 0)
        carry = (jnp.full((row_tile, 1), NEG_INF, f32),
                 jnp.zeros((row_tile, 1), f32),
                 jnp.zeros((row_tile, q.shape[-1]), f32))
        carry = jax.lax.fori_loop(0, n_tiles, tile, carry)
        lead = init_blocks * block
        key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, lead), 1)
        s = scores(q, k_ref[0, 0:lead, :], key_pos, q_pos,
                   extra=key_pos < first * block)      # else the window has them
        m, l, acc = fold(carry, s, v_ref[0, 0:lead, :])
        if n_sel == 0:
            o_ref[0, 0, r0:r0 + row_tile, :] = (acc / l).astype(o_ref.dtype)
        else:
            m_scr[r0:r0 + row_tile, :] = m
            l_scr[r0:r0 + row_tile, :] = l
            acc_scr[r0:r0 + row_tile, :] = acc
    if n_sel == 0:
        return

    # -- the gathered half: a query's chosen blocks, end to end
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_sel * block), 1)

    def query(t, _):
        def cut(ref):
            return jnp.concatenate(
                [ref[0, pl.ds(pl.multiple_of(sel_ref[0, 0, t, j] * block, block),
                              block), :] for j in range(n_sel)], axis=0)

        at = pl.ds(pl.multiple_of(t * group, group), group)
        s = jax.lax.dot_general(q_ref[0, 0, at, :], cut(k_ref), _NT,
                                preferred_element_type=f32) * scale
        s = jnp.where(lane < sel_ref[0, 0, t, n_sel] * block, s, NEG_INF)
        m, l, acc = fold((m_scr[at, :], l_scr[at, :], acc_scr[at, :]), s,
                         cut(v_ref))
        o_ref[0, 0, at, :] = (acc / l).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, block, query, 0)


def record_plan(context, total, n_blocks, topk, forced, block, tile, form,
                scorer=None):
    """One zero-length ``sparse.plan`` span for each sparse attention traced:
    its context, blocks and which form it took (``selected``: this kernel;
    ``dense``: the flash kernel or a plain product, ``layers/sala.py``), and
    the form of the scorer that chose the blocks: ``kernel``
    (``ops/block_select.py``, with its query tile, the compressed keys a
    step of its walk and all of them: ``scorer``, its plan), ``jnp`` (the
    plain scorer, one query a row) or ``none`` (no query selects)."""
    from ..core import profiler

    if scorer is None:
        scorer = {"scorer": "none" if form == "dense" else "jnp"}
    else:
        scorer = {"scorer": "kernel", **{f"scorer_{k}": v
                                         for k, v in scorer.items()}}
    profiler.record_span(
        "sparse.plan", time.time_ns(), 0, context=context, cache_len=total,
        blocks=n_blocks, topk=topk, forced_blocks=forced, block=block,
        row_tile=tile[0], key_tile=tile[1], form=form, **scorer)


def sparse_attention(q, k_cache, v_cache, sel, p0, *, group: int, block: int,
                     window_blocks: int, init_blocks: int, scale: float,
                     scorer=None, interpret=None):
    """``q [b, n_kv, queries * group, d]`` (a query's ``group`` heads on
    consecutive rows; the queries are positions ``p0 ..``, ``p0`` a traced
    multiple of ``block``), the cache ``k_cache, v_cache [b, T, n_kv * d]``
    filled at least up to the last query, ``sel [b, n_kv, queries, n_sel +
    1]`` int32 (a query's chosen blocks, the ones that count first, then
    their number) -> ``o`` like ``q``. ``scorer``: the plan of the kernel
    that made ``sel``, for the record."""
    b, n_kv, rows, d = q.shape
    total = k_cache.shape[1]
    n_sel = sel.shape[-1] - 1
    n_q = rows // group
    interpret = default_interpret() if interpret is None else interpret
    enforce(n_q % block == 0 and total % block == 0,
            f"sparse_attention: {n_q} queries and {total} keys in blocks of {block}")
    enforce(window_blocks * block <= total and init_blocks * block <= total,
            f"sparse_attention: a cache of {total} keys is shorter than the window")
    resident = 4 * total * d * k_cache.dtype.itemsize
    enforce(resident <= RESIDENT_BYTES,
            f"sparse_attention: {total} keys of one head do not stay in VMEM "
            f"({resident} bytes of {RESIDENT_BYTES})")
    span = window_blocks * block
    key_tile = max(t for t in (KEY_TILE, 256, 128, block) if span % t == 0
                   and t <= max(span, block))
    row_tile = min(ROW_TILE, block * group)
    enforce((block * group) % row_tile == 0,
            f"sparse_attention: {block * group} rows in tiles of {row_tile}")
    record_plan(total, total, total // block,
                 n_sel + window_blocks + init_blocks,
                 window_blocks + init_blocks, block, (row_tile, key_tile),
                 "selected", scorer)
    kv = pl.BlockSpec((1, total, d), lambda bi, c, i, p: (bi, 0, c))
    qo = pl.BlockSpec((1, 1, block * group, d), lambda bi, c, i, p: (bi, c, i, 0))
    chosen = pl.BlockSpec((1, 1, block, n_sel + 1),
                          lambda bi, c, i, p: (bi, c, i, 0),
                          memory_space=pltpu.SMEM)
    stat = pltpu.VMEM((block * group, 1), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, group=group, block=block,
                          window_blocks=window_blocks, init_blocks=init_blocks,
                          n_sel=n_sel, scale=scale, row_tile=row_tile,
                          key_tile=key_tile),
        name="sparse_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n_kv, n_q // block),
            in_specs=[chosen, qo, kv, kv], out_specs=qo,
            scratch_shapes=[stat, stat,
                            pltpu.VMEM((block * group, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=resident + (32 << 20)),
        interpret=interpret,
    )(jnp.reshape(p0, (1,)).astype(jnp.int32), sel, q, k_cache, v_cache)


__all__ = ["record_plan", "selection_mask", "sparse_attention",
           "sparse_attention_jnp"]
