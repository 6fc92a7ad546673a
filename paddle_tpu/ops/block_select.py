"""Block selection (InfLLM-V2's first stage) — a Pallas TPU kernel that
scores, pools and ranks where the scores are made.

The scorer of ``layers/sala.py`` (:func:`~paddle_tpu.layers.sala.select_blocks`,
this kernel's oracle) for a chunk of consecutive queries: each query head's
softmax over the *compressed keys* that exist for the query, summed over
the ``group`` heads that share a key head, pooled to key blocks (a block's
score is the largest of the ``per + 1`` kernels that touch it) and ranked:
the ``n_sel`` highest free blocks, highest first, the lower index first
among equals, and how many of them hold a score. The plain form writes the
float32 scores ``[rows, heads, queries, keys]`` to HBM and reads them back
four times, then rewrites the block scores once a rank (PERF.md section 6,
PR 33: 24.6 ms a call where the kernel it feeds takes 13.4); kernel
``select_fwd`` keeps all of it in VMEM and writes the indices alone.

Grid ``(rows, key heads, query tiles, heads of the group)``: a step is one
head's 128-lane slice of the queries, read from the projection's own ``[b,
s, heads * d]`` layout, its probabilities added into an accumulator that the
tile's last step pools and ranks (a head a step and not a loop inside one:
a kernel that writes out sixteen heads takes 0.9 s to trace and lower, and
set-up traces every sparse layer's three times). Keys lie on sublanes and
queries on lanes, as in the flash kernels: every per-query vector (a head's max and
sum, a rank's score and index) is one lane-major row, the softmax's and the
ranks' reductions run down sublanes, and the output ``[n_sel + 1, tile]`` is
lane-dense. The compressed keys come reordered once a call, outside the
kernel: entry ``r * blocks + b`` holds kernel ``per * b + r``, so the
kernels a block starts are ``per`` aligned row ranges, pooling is an
elementwise maximum of them (and of the last range read one row earlier)
and the keys of a run of blocks are ``per`` contiguous pieces. A key head's
reordered keys stay in VMEM across the query tiles (2,560 x 128 at 32,896
keys of context) beside a tile's float32 scores and accumulator (2,560 x
512 each).

A tile walks the keys in pieces of ``KEY_BLOCKS`` blocks and visits only
those that exist at its last query (of a prompt's queries on average half do
not), three times a step: scores from the MXU, masked, into a float32
scratch with their maximum; exponentials and their sum; the normalised
probabilities added into the group's accumulator. In the last step the
pooled, masked block scores are ranked by ``n_sel`` passes over the visited blocks, each a
maximum and the lowest index that holds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.errors import enforce
from .flash_attention import _NT, NEG_INF, default_interpret

# Queries a grid step (lanes) and key blocks a piece of the walk (sublanes),
# measured at the cell's shapes (PERF.md section 6, PR 38; ms a timed call
# at the prompt's first chunk / its fourth / its last): 256 x 128 1.75 /
# 2.47 / 3.92, 512 x 128 1.55 / 2.12 / 3.27, 1024 x 128 1.54 / 2.05 / 3.19
# with twice the scratch; with the heads inside a step, pieces of 64 and 32
# blocks were slower and of 256 blocks (more padding) no faster.
QUERY_TILE = 512
KEY_BLOCKS = 128


def _tiles(queries: int, n_blocks: int, block: int):
    """``(query tile, blocks a piece, pieces)`` of a call: the tile is the
    largest of ``QUERY_TILE``, 256, 128 and the block that divides the
    chunk, a piece whole sublane groups of eight."""
    tile = max(t for t in (QUERY_TILE, 256, 128, block) if queries % t == 0)
    piece = min(KEY_BLOCKS, -(-n_blocks // 8) * 8)
    return tile, piece, -(-n_blocks // piece)


def _kernel(p0_ref, q_ref, ck_ref, sel_ref, s_scr, acc_scr, x_scr, *,
            kernel_size, stride, block, per, init_blocks, window_blocks, n_sel,
            n_blocks, scale, tile, piece, pieces):
    f32 = jnp.float32
    head, heads = pl.program_id(3), pl.num_programs(3)
    span = piece * pieces                     # blocks with the padding
    first = p0_ref[0] + pl.program_id(2) * tile
    # pieces to visit: up to the last compressed key of the tile's last query
    exist = jax.lax.div(jnp.maximum(first + tile - kernel_size + stride, 0),
                        stride)
    visit = jnp.minimum(jax.lax.div(exist + per * piece - 1, per * piece), pieces)
    pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (piece, tile), 0)
    # kernel per * (c * piece + row) + r exists for the query at pos when
    # lead >= stride * (per * c * piece + r) + kernel_size - 1
    lead = pos - stride * per * row
    some = pos + 1 >= kernel_size            # the query has a key at all

    def rows_of(r, c):
        return pl.ds(pl.multiple_of(r * span + c * piece, 8), piece)

    @pl.when(head == 0)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    q = q_ref[0]

    def scores(c, m):
        for r in range(per):
            s = jax.lax.dot_general(ck_ref[0, rows_of(r, c), :], q, _NT,
                                    preferred_element_type=f32) * scale
            s = jnp.where(lead >= stride * (per * piece * c + r)
                          + kernel_size - 1, s, NEG_INF)
            s_scr[rows_of(r, c), :] = s
            m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        return m

    m = jax.lax.fori_loop(0, visit, scores, jnp.full((1, tile), NEG_INF, f32))

    def exponents(c, l):
        for r in range(per):
            e = jnp.exp(s_scr[rows_of(r, c), :] - m)
            s_scr[rows_of(r, c), :] = e
            l = l + jnp.sum(e, axis=0, keepdims=True)
        return l

    l = jax.lax.fori_loop(0, visit, exponents, jnp.zeros((1, tile), f32))
    # a query before the first compressed key has every key masked, a
    # uniform softmax and, by the oracle, a score of 0 everywhere
    inv = jnp.where(some, 1.0 / l, 0.0)

    def add(c, _):
        for r in range(per):
            acc_scr[rows_of(r, c), :] += s_scr[rows_of(r, c), :] * inv
        return 0

    jax.lax.fori_loop(0, visit, add, 0)

    @pl.when(head == heads - 1)
    def _():
        _rank(sel_ref, acc_scr, x_scr, visit, pos, row, block=block, per=per,
              init_blocks=init_blocks, window_blocks=window_blocks,
              n_sel=n_sel, n_blocks=n_blocks, piece=piece, pieces=pieces)


def _rank(sel_ref, acc_scr, x_scr, visit, pos, row, *, block, per, init_blocks,
          window_blocks, n_sel, n_blocks, piece, pieces):
    """A tile's last step: the group's summed probabilities in ``acc_scr``
    pooled to block scores in ``x_scr`` and ranked into ``sel_ref``."""
    f32 = jnp.float32
    span, tile = piece * pieces, pos.shape[1]
    # block scores: the largest of a block's own kernels and the one before
    # them; -1 where the block is not the query's to choose, -3 for padding
    own = jax.lax.div(pos, block)
    for c in range(pieces):
        at = lambda r: acc_scr[r * span + c * piece:r * span + (c + 1) * piece, :]
        pooled = functools.reduce(jnp.maximum, [at(r) for r in range(per)])
        start = (per - 1) * span + c * piece
        b = c * piece + row
        # (block 0 has no kernel before its own)
        before = jnp.where(b == 0, 0.0, acc_scr[start - 1:start - 1 + piece, :])
        free = (b >= init_blocks) & (b <= own - window_blocks)
        x = jnp.where(free, jnp.maximum(pooled, before), -1.0)
        x_scr[c * piece:(c + 1) * piece, :] = jnp.where(b < n_blocks, x, -3.0)

    # the ranks: a pass takes the last one's block out and finds the
    # largest score left, then the lowest block that holds it
    ranked = jnp.minimum(jnp.maximum(visit, -(-n_sel // piece)), pieces)
    index = row.astype(f32)

    def blocks_of(c):       # a piece's rows of x_scr and their block indices
        return (pl.ds(pl.multiple_of(c * piece, 8), piece),
                index + (c * piece).astype(f32))

    def rank(k, carry):
        taken, count = carry

        def largest(c, m):
            rows, blocks = blocks_of(c)
            x = jnp.where(blocks == taken, -2.0, x_scr[rows, :])
            x_scr[rows, :] = x
            return jnp.maximum(m, jnp.max(x, axis=0, keepdims=True))

        top = jax.lax.fori_loop(0, ranked, largest, jnp.full((1, tile), -4.0, f32))

        def lowest(c, i):
            rows, blocks = blocks_of(c)
            held = jnp.where(x_scr[rows, :] == top, blocks, float(span))
            return jnp.minimum(i, jnp.min(held, axis=0, keepdims=True))

        i = jax.lax.fori_loop(0, ranked, lowest,
                              jnp.full((1, tile), float(span), f32))
        sel_ref[0, 0, pl.ds(k, 1), :] = i.astype(jnp.int32)
        return i, count + (top >= 0.0).astype(jnp.int32)

    _, count = jax.lax.fori_loop(
        0, n_sel, rank, (jnp.full((1, tile), -1.0, f32),
                         jnp.zeros((1, tile), jnp.int32)))
    sel_ref[0, 0, n_sel:n_sel + 1, :] = count


def block_select(q, ck_cache, p0, *, group: int, head_dim: int,
                 kernel_size: int, stride: int, block: int, init_blocks: int,
                 window_blocks: int, n_sel: int, scale: float, interpret=None):
    """``q [b, s, heads * d]`` (a key head's ``group`` heads side by side,
    the projection's own layout; the queries are positions ``p0 ..``, ``p0``
    traced) and the compressed keys ``ck_cache [b, J, n_kv * d]`` filled at
    least up to the last query -> ``sel [b, n_kv, s, n_sel + 1]`` int32, as
    ``layers/sala.select_blocks`` gives it. Returns ``(sel, plan)``: the
    plan says the query tile, the compressed keys a step of the walk (a tile
    visits whole steps up to the last key that exists at its last query)
    and all of them."""
    b, s, wide = q.shape
    J, kv_wide = ck_cache.shape[1:]
    per = block // stride
    n_blocks = J // per
    d, n_kv = head_dim, kv_wide // head_dim
    enforce(per >= 2 and J % per == 0 and wide == n_kv * group * d,
            f"block_select: {J} compressed keys in blocks of {per} (two at "
            f"least), queries {wide} wide over {n_kv} key heads of {group}")
    enforce(s % block == 0, f"block_select: {s} queries in blocks of {block}")
    interpret = default_interpret() if interpret is None else interpret
    tile, piece, pieces = _tiles(s, n_blocks, block)
    span = piece * pieces
    # entry r * span + blk <- kernel per * blk + r, zeros beyond the last
    ck = ck_cache.reshape(b, n_blocks, per, kv_wide).transpose(0, 2, 1, 3)
    ck = jnp.pad(ck, ((0, 0), (0, 0), (0, span - n_blocks), (0, 0)))
    ck = ck.reshape(b, per * span, kv_wide)
    sel = pl.pallas_call(
        functools.partial(
            _kernel, kernel_size=kernel_size,
            stride=stride, block=block, per=per, init_blocks=init_blocks,
            window_blocks=window_blocks, n_sel=n_sel, n_blocks=n_blocks,
            scale=scale, tile=tile, piece=piece, pieces=pieces),
        name="select_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n_kv, s // tile, group),
            in_specs=[
                pl.BlockSpec((1, tile, d),
                             lambda bi, c, i, h, p: (bi, i, c * group + h)),
                pl.BlockSpec((1, per * span, d),
                             lambda bi, c, i, h, p: (bi, 0, c))],
            out_specs=pl.BlockSpec((1, 1, n_sel + 1, tile),
                                   lambda bi, c, i, h, p: (bi, c, 0, i)),
            scratch_shapes=[pltpu.VMEM((per * span, tile), jnp.float32),
                            pltpu.VMEM((per * span, tile), jnp.float32),
                            pltpu.VMEM((span, tile), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, n_sel + 1, s), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            # the three scratches, both blocks twice, room for the walks
            vmem_limit_bytes=(4 * (2 * per + 1) * span * tile
                              + 2 * (tile + per * span) * d * q.dtype.itemsize
                              + (16 << 20))),
        interpret=interpret,
    )(jnp.reshape(p0, (1,)).astype(jnp.int32), q, ck)
    plan = {"tile": tile, "key_step": per * piece, "keys": J}
    return sel.transpose(0, 1, 3, 2), plan


__all__ = ["block_select"]
