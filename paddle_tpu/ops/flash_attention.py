"""Flash attention — pallas TPU kernels (forward AND backward).

New first-class component per SURVEY §5/§7: the reference has no
attention kernels at all (attention was composed from mul/softmax ops in
models, e.g. benchmark/fluid/models/machine_translation.py), and no
answer to long sequences beyond LoD ragged batching. This supplies
O(seq) -memory attention on TPU: a forward kernel and two backward
kernels (dq; dk/dv) that recompute probabilities from the saved
logsumexp — the standard flash-attention-2 decomposition.

The tile walk, shared by the three kernels:

- A grid step brings in ``block_q`` rows of Q (with dO, lse, delta in
  the backward) and ``block_k`` rows of K/V for ``heads`` heads: whole
  sequences up to ``RESIDENT`` rows (a head's K and V are 128 KB each
  at 1024 x 64), ``STREAM_BLOCK`` rows beyond it, where K/V (Q/dO for
  dk/dv) stream from HBM on the innermost grid dimension and the
  running state lives in VMEM scratch across its steps.
- Inside the step the kernel loops over compute tiles of
  ``tile_q x tile_k`` scores, chosen apart from the DMA block. The outer
  loop holds one operand still (a Q tile in forward and dq, a K/V tile
  in dk/dv), the inner loop walks the other in chunks. The tile is as
  large as 512 x 512: the MXU takes the still operand as weights anew
  for every dot, and a tile must stream enough rows past them to pay
  for that (measured: see ``TILE``). Where the sequence is resident
  whole and short, the walk is written out when the kernel is traced
  (``_loop``): the compiler schedules a loop body alone, and only a walk
  it sees whole lets one tile's matmuls run under the next one's
  softmax.
- Scores are computed TRANSPOSED, ``[tile_k, tile_q]``: keys on
  sublanes, queries on lanes. Every per-query vector (running max and
  sum, lse, delta, query segment ids) is then one lane-major row — a
  vreg per 128 queries, the layout it has in HBM — where a
  ``[tile_q, 1]`` column costs a vreg per 8 queries; the softmax
  reductions run down sublanes (elementwise max/add of vregs) and not
  across lanes; and dk/dv need no transposed operand at all.
- The causal triangle is known per tile from the loop bounds: chunks
  above the diagonal are not visited, chunks the diagonal crosses take
  the iota mask, interior chunks take no mask, compare or select. The
  same bounds skip key padding (no bias is invented for it).
- The softmax scale is folded into the operand that stays still when
  it is a power of two (exact in bf16: d = 16, 64, 256), else applied to
  the f32 scores; gradients take it once, when they are written.

Masking: causal (bottom-right aligned), an additive per-key bias
[b, s_k] (padding), and segment ids (the LoD ragged-batch equivalent,
layers/sequence.py design) — all fused into the kernels. Per-row
vectors cross the kernel boundary as (bh, blocks, tiles, tile) arrays
so that a tile's row is picked by a sublane index.

Ring/context-parallel attention (parallel/ring_attention.py) reuses
these kernels per shard and merges (out, lse) pairs in log-space.
"""

from __future__ import annotations

import functools
import math
import time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The plan's constants (ROADMAP S6; tools/flash_microbench.py sweeps the
# tile on the chip. PR 25's runs at 32 x 16 x 1024 x 64, traced loops:
# the three kernels take 19.9 ms at 128 x 128, 10.2 at 256 x 256, 7.2 at
# 512 x 512). TILE: the largest
# compute tile, [tile_k, tile_q] f32 scores. Every dot pushes its
# still operand into the MXU as weights again, so a tile has to stream
# enough rows past them to pay for the push; 512 x 512 also gives each
# of the four MXUs a 128-column slice of one dot. RESIDENT: sequences up
# to this many rows sit in VMEM whole; longer ones stream STREAM_BLOCK
# rows a grid step. STEP_SCORES: the scores a grid step should compute
# so that its fixed cost (about 0.35 us) stays small against its work;
# heads are grouped into a step up to it, inside STEP_BYTES of VMEM.
# UNROLL: a resident walk of up to this many tiles a head is written out
# for the compiler to see whole (``_loop``; the same three kernels take
# 5.2 ms written out a head, 4.9 ms written out a step).
TILE = 512
RESIDENT = 2048
STREAM_BLOCK = 1024
STEP_SCORES = 2 << 20
STEP_BYTES = 6 << 20
UNROLL = 8

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def resolve_block_shapes(block_q, block_k):
    """Explicit DMA block sizes, or None for "let :func:`plan_blocks`
    choose from the shape": explicit args win; None falls to the
    ``flash_block_q``/``flash_block_k`` config flags (env
    ``PDTPU_FLASH_BLOCK_Q``/``_K``), and flag 0 to None. Validated here
    so a typo'd env value fails naming the flag instead of as a Mosaic
    tiling error deep in kernel lowering. NOTE: like all shape-affecting
    knobs this is read at TRACE time — set the flag (or env) before the
    first jit compilation of the calling step; already-cached
    executables keep their block shapes."""
    from ..core.config import get_flag
    from ..core.errors import enforce

    if block_q is None:
        block_q = get_flag("flash_block_q") or None
    if block_k is None:
        block_k = get_flag("flash_block_k") or None
    for name, val in (("flash_block_q", block_q), ("flash_block_k", block_k)):
        enforce(val is None or (isinstance(val, int) and val > 0
                                and val % 8 == 0),
                f"{name}: block size must be a positive multiple of 8 "
                f"(TPU sublane tiling), got {val!r}")
    return block_q, block_k


def default_interpret() -> bool:
    """Interpret the kernels only where there is no TPU to compile them
    for (the CPU tests); on a TPU they always lower through Mosaic."""
    return jax.devices()[0].platform == "cpu"


# ---------------------------------------------------------------------------
# the plan: blocks and tiles from what the call can see


class FlashPlan(NamedTuple):
    """What one attention call's three kernels run with."""
    sq: int          # the call's query and key lengths
    sk: int
    d: int
    block_q: int     # rows of Q / K a grid step brings in
    block_k: int
    tile_q: int      # compute tile: scores are [tile_k, tile_q] f32
    tile_k: int
    heads: int       # heads a grid step
    sq_p: int        # lengths padded to whole blocks
    sk_p: int
    causal: bool
    fold_scale: bool  # scale folded into the still operand (power of two)
    tiles_run: int   # compute tiles the forward walk visits ...
    tiles_all: int   # ... of the tiles in the padded score rectangle
    dv: int = 0      # width of a value and of an output row (plan_blocks sets it)


def _round_up(n, m):
    return -(-n // m) * m


def _axis_plan(s, block, unit):
    """(padded length, DMA block, compute tile) of one sequence axis.
    ``block`` explicit or None; ``unit``: what a tile is a multiple of —
    128 for queries (they lie on lanes), 16 for keys (sublanes, bf16
    packs 16 rows a register)."""
    if block is not None:
        block = min(block, _round_up(s, 8))
        return (_round_up(s, block), block,
                TILE if block % TILE == 0 else block)
    if s <= unit:
        s_p = _round_up(s, 8 if unit == 128 else 16)
        return s_p, s_p, s_p
    if s > RESIDENT:
        return _round_up(s, STREAM_BLOCK), STREAM_BLOCK, TILE
    if s > 2 * TILE:
        s_p = _round_up(s, TILE)
        return s_p, s_p, TILE
    # up to two tiles long: pad to the unit only (896 stays 896) and take
    # the largest tile that divides it, or the sequence whole where only
    # slivers do (896 = 7 x 128 queries: one tile of 896)
    s_p = _round_up(s, unit)
    tile = max(t for t in range(unit, min(TILE, s_p) + 1, unit)
               if s_p % t == 0)
    return s_p, s_p, tile if 2 * tile >= min(TILE, s_p) else s_p


def _clip(v, lo, hi):
    """``clip`` on python ints (the plan's count, a walk the compiler
    sees whole) or on traced scalars."""
    if any(isinstance(x, jax.Array) for x in (v, lo, hi)):
        return jnp.clip(v, lo, hi)
    return max(lo, min(v, hi))


def _chunk_bounds(r0, tile_q, c_base, n_chunks, tile_k, *, causal, offset,
                  sk, sk_p):
    """Of the ``n_chunks`` key chunks that start at column ``c_base``,
    which ones the query tile at rows ``r0 .. r0 + tile_q`` visits:
    chunks ``[0, n_plain)`` need no mask, ``[n_plain, n_end)`` cross the
    causal diagonal or hold padded keys, the rest are not visited. Works
    on python ints and on traced scalars."""
    n_plain = n_end = n_chunks
    if causal:
        # keys a row r sees: c <= r + offset
        n_plain = _clip((r0 + offset + 1 - c_base) // tile_k, 0, n_chunks)
        n_end = _clip((r0 + tile_q + offset - c_base + tile_k - 1) // tile_k,
                      0, n_chunks)
    if sk != sk_p:  # chunks before the first padded key
        n_plain = _clip(n_plain, 0, _clip((sk - c_base) // tile_k, 0, n_chunks))
    return n_plain, n_end


def plan_blocks(sq, sk, d, dtype=jnp.bfloat16, causal=False,
                have_bias=False, have_seg=False, block_q=None, block_k=None,
                bh=1, dv=None, scale=None) -> FlashPlan:
    """Blocks, compute tile and heads a step for one attention call, from
    what the call can see. One rule for every shape: pad each axis to
    whole registers (128 queries, 16 keys; no further: 896 stays 896),
    keep a sequence of up to ``RESIDENT`` rows in VMEM whole and stream
    longer ones in ``STREAM_BLOCK`` rows, compute in tiles of up to
    ``TILE x TILE`` (:func:`_axis_plan`), and group heads until a grid step
    computes ``STEP_SCORES`` scores. Explicit ``block_q``/``block_k`` win
    (the compute tile then follows the block). ``have_bias``/``have_seg`` do
    not change the blocks today; they are part of what a plan may depend
    on. ``d`` is the width the scores contract over and ``dv`` that of a
    value (latent attention: 192 and 128); ``scale`` is the softmax scale
    where it is not ``d ** -0.5``."""
    del dtype, have_bias, have_seg
    dv = d if dv is None else dv
    sq_p, block_q, tile_q = _axis_plan(sq, block_q, 128)
    sk_p, block_k, tile_k = _axis_plan(sk, block_k, 16)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    fold = math.frexp(scale)[0] == 0.5

    # the forward walk's executed share of the score rectangle
    offset = sk - sq
    run = 0
    for r0 in range(0, sq_p, tile_q):
        _, n_end = _chunk_bounds(r0, tile_q, 0, sk_p // tile_k, tile_k,
                                 causal=causal, offset=offset, sk=sk,
                                 sk_p=sk_p)
        run += n_end
    tiles_all = (sq_p // tile_q) * (sk_p // tile_k)

    # heads a step: until the step computes STEP_SCORES scores, inside
    # STEP_BYTES of VMEM (about a dozen double-buffered row blocks a head)
    share = run / tiles_all if (sq_p, sk_p) == (block_q, block_k) else 1.0
    step_scores = block_q * block_k * share
    step_bytes = (6 * max(block_q, block_k)
                  * (_round_up(d, 128) + _round_up(dv, 128)) * 2)
    heads = max(g for g in range(1, bh + 1) if bh % g == 0 and (
        g == 1 or (g * step_scores <= STEP_SCORES
                   and g * step_bytes <= STEP_BYTES)))
    return FlashPlan(sq, sk, d, block_q, block_k, tile_q, tile_k, heads,
                     sq_p, sk_p, causal, fold, run, tiles_all, dv)


def _record_plan(p: FlashPlan):
    """One span in the program's ring for each attention traced: which
    plan the call got, and how far the causal skip engages."""
    from ..core import profiler

    profiler.record_span(
        "flash.plan", time.time_ns(), 0, sq=p.sq, sk=p.sk, d=p.d, dv=p.dv,
        block_q=p.block_q, block_k=p.block_k, tile_q=p.tile_q,
        tile_k=p.tile_k, heads=p.heads, causal=p.causal,
        tiles_run=p.tiles_run, tiles_all=p.tiles_all)


# ---------------------------------------------------------------------------
# what the three kernels share


class _Walk(NamedTuple):
    """The static half of a kernel: the plan plus what the call masks."""
    plan: FlashPlan
    scale: float
    offset: int       # sk - sq: causal is bottom-right aligned
    have_bias: bool
    have_seg: bool

    @property
    def nqt(self):
        return self.plan.block_q // self.plan.tile_q

    @property
    def nkt(self):
        return self.plan.block_k // self.plan.tile_k

    @property
    def nq(self):
        return self.plan.sq_p // self.plan.block_q

    @property
    def nk(self):
        return self.plan.sk_p // self.plan.block_k

    @property
    def written_out(self):
        """Is a head's walk short enough to be written out when the
        kernel is traced (a resident sequence of a few tiles)? Else its
        loops are traced."""
        return self.nq == self.nk == 1 and self.nqt * self.nkt <= UNROLL


def _scores(keys, queries, r0, c0, w: _Walk, *, masked, bias_col, segq_row,
            segk_col):
    """One tile of scores, transposed: ``[tile_k, tile_q]`` f32 from
    ``keys [tile_k, d]`` and ``queries [tile_q, d]`` in their input dtype
    (bf16 in: one MXU-native pass with f32 accumulation). One definition
    for the three kernels, so they can never desynchronize. ``masked``
    (static) is whether this tile can cross the causal diagonal or hold
    padded keys; bias and segment masks apply whenever the operand is
    there."""
    s = jax.lax.dot_general(keys, queries, _NT,
                            preferred_element_type=jnp.float32)
    if not w.plan.fold_scale:
        s = s * w.scale
    if bias_col is not None:
        s = s + bias_col
    keep = None
    if masked:
        c = c0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if w.plan.causal:
            r = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = r + w.offset >= c
        if w.plan.sk != w.plan.sk_p:
            pad = c < w.plan.sk
            keep = pad if keep is None else keep & pad
    if segq_row is not None:
        same = segk_col == segq_row
        keep = same if keep is None else keep & same
    return s if keep is None else jnp.where(keep, s, NEG_INF)


def _still(x, w: _Walk):
    """The operand a walk holds still, with the scale folded in where
    that is exact."""
    return x * jnp.asarray(w.scale, x.dtype) if w.plan.fold_scale else x


def _col(ref, g, j):
    """Tile ``j`` of a per-key row vector as a ``[tile_k, 1]`` column."""
    return ref[g, 0, j, :][:, None]


def _loop(lo, hi, body, carry):
    """``fori_loop``, or the iterations written out where the bounds are
    python ints: the compiler schedules a loop body alone, so only a walk
    it sees whole lets one tile's matmuls run under its neighbour's
    softmax. A sequence that is resident whole has such a walk (its
    triangle is known when the kernel is traced); one that streams has
    traced bounds."""
    if isinstance(lo, int) and isinstance(hi, int):
        for j in range(lo, hi):
            carry = body(j, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _two_loops(n_plain, n_end, make_body, carry, w: _Walk):
    """Run ``make_body(masked)`` over the plain chunks ``[0, n_plain)``
    and the masked ones ``[n_plain, n_end)``: two straight code paths,
    chosen per chunk by the loop it is in. A walk nothing can mask has
    one loop."""
    carry = _loop(0, n_plain, make_body(False), carry)
    if w.plan.causal or w.plan.sk != w.plan.sk_p:
        carry = _loop(n_plain, n_end, make_body(True), carry)
    return carry


def _at(j, tile):
    """Row offset of tile ``j``, with its alignment where ``j`` is traced."""
    return j * tile if isinstance(j, int) else pl.multiple_of(j * tile, tile)


def _grid_index(axis, size):
    """This step's index on a grid axis: 0, as a python int, where the
    axis has one step."""
    return pl.program_id(axis) if size > 1 else 0


def _block_runs(qb, kb, w: _Walk):
    """Does q block ``qb`` see any key of key block ``kb``?"""
    if not w.plan.causal:
        return True
    return kb * w.plan.block_k < (qb + 1) * w.plan.block_q + w.offset


def _kj_clamp(causal, block_q, block_k, nk, offset):
    """Index clamp for K/V-side blocks in causal kernels: grid steps
    past a q block's last useful key block keep requesting the SAME
    block index, and Pallas's pipelining skips the HBM→VMEM DMA when the
    index does not change — the compute for those steps is already
    gated off, so without this the skipped upper-triangle blocks still
    paid their K/V fetch bandwidth. Last useful kj for q block qi:
    floor(((qi+1)·bq + offset − 1)/bk), clamped to [0, nk−1]."""
    if not causal:
        return lambda kk, j: kk

    def clamp(kk, j):
        last = ((j + 1) * block_q + offset - 1) // block_k
        return jnp.minimum(kk, jnp.clip(last, 0, nk - 1))
    return clamp


def _qi_clamp(causal, block_q, block_k, nq, offset):
    """Mirror of :func:`_kj_clamp` for the dkv kernel's Q-side blocks:
    steps before a key block's first useful q block re-request the
    first useful block. First useful qi for key block kj:
    max(0, floor((kj·bk − offset)/bq))."""
    if not causal:
        return lambda kk, j: kk

    def clamp(kk, j):
        first = jnp.clip((j * block_k - offset) // block_q, 0, nq - 1)
        return jnp.maximum(kk, first)
    return clamp


def _pad_seq(x, target, axis, value=0.0):
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _rows(x, bh, blocks, tiles, tile):
    """A per-row vector [b, h, s] or [bh, s] as (bh, blocks, tiles, tile):
    a tile's row is then one sublane index inside a block."""
    return x.reshape(bh, blocks, tiles, tile)


class _Operands(NamedTuple):
    """A call's arrays in kernel form, padded to the plan."""
    q: jax.Array
    k: jax.Array
    v: jax.Array
    bias: Optional[jax.Array]
    segq: Optional[jax.Array]
    segk: Optional[jax.Array]


def _prepare(q, k, v, bias, seg_q, seg_k, p: FlashPlan):
    """Pad the sequence axes to the plan and flatten heads. Padded keys
    are masked by their index inside the kernels (no bias is invented);
    padded q/k segment ids get distinct negative ids so they never
    match."""
    b, h, _, d = q.shape
    bh = b * h
    nq, nk = p.sq_p // p.block_q, p.sk_p // p.block_k
    nqt, nkt = p.block_q // p.tile_q, p.block_k // p.tile_k
    q = _pad_seq(q, p.sq_p, 2).reshape(bh, p.sq_p, d)
    k = _pad_seq(k, p.sk_p, 2).reshape(bh, p.sk_p, d)
    v = _pad_seq(v, p.sk_p, 2).reshape(bh, p.sk_p, p.dv)

    def per_head(x, s_p, value, dtype):
        x = _pad_seq(x.astype(dtype), s_p, 1, value)
        return jnp.broadcast_to(x[:, None, :], (b, h, s_p))

    if bias is not None:
        bias = _rows(per_head(bias, p.sk_p, 0.0, jnp.float32),
                     bh, nk, nkt, p.tile_k)
    if seg_q is not None:
        seg_q = _rows(per_head(seg_q, p.sq_p, -1, jnp.int32),
                      bh, nq, nqt, p.tile_q)
        seg_k = _rows(per_head(seg_k, p.sk_p, -2, jnp.int32),
                      bh, nk, nkt, p.tile_k)
    return _Operands(q, k, v, bias, seg_q, seg_k)


def _mask_specs(ops: _Operands, p: FlashPlan, q_map, k_map):
    """BlockSpecs and arrays of the optional mask operands, in the order
    the kernels take them: bias, segq, segk."""
    g, nqt, nkt = p.heads, p.block_q // p.tile_q, p.block_k // p.tile_k
    specs, args = [], []
    if ops.bias is not None:
        specs.append(pl.BlockSpec((g, 1, nkt, p.tile_k), k_map))
        args.append(ops.bias)
    if ops.segq is not None:
        specs.append(pl.BlockSpec((g, 1, nqt, p.tile_q), q_map))
        specs.append(pl.BlockSpec((g, 1, nkt, p.tile_k), k_map))
        args += [ops.segq, ops.segk]
    return specs, args


def _split_refs(refs, w: _Walk, n_in, n_out):
    """(q, k, v, bias, segq, segk, rest-of-inputs, outputs, scratch) from
    a kernel's positional refs."""
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    b_ref = next(it) if w.have_bias else None
    sq_ref = next(it) if w.have_seg else None
    sk_ref = next(it) if w.have_seg else None
    rest = list(it)
    return (q_ref, k_ref, v_ref, b_ref, sq_ref, sk_ref, rest[:n_in],
            rest[n_in:n_in + n_out], rest[n_in + n_out:])


# ---------------------------------------------------------------------------
# forward


def _over_tiles(n_tiles, tile_body, w: _Walk):
    """Run ``tile_body(g, t)`` for every head ``g`` of the step and every
    still tile ``t`` of the block. Where the walk is written out
    (:attr:`_Walk.written_out`) the tiles are python iterations, and the
    heads too while the step stays within ``UNROLL`` tiles; else traced
    loops."""
    heads = w.plan.heads

    def head(g, carry=0):
        def tile(t, c):
            tile_body(g, t)
            return c
        if w.written_out:
            _loop(0, n_tiles, tile, 0)
        else:
            jax.lax.fori_loop(0, n_tiles, tile, 0)
        return carry

    if w.written_out and heads * w.nqt * w.nkt <= UNROLL:
        for g in range(heads):
            head(g)
    else:
        jax.lax.fori_loop(0, heads, head, 0)


def _fwd_kernel(*refs, w: _Walk):
    (q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref, _,
     (o_ref, lse_ref), (m_scr, l_scr, acc_scr)) = _split_refs(refs, w, 0, 2)
    p = w.plan
    qb, kv = _grid_index(1, w.nq), _grid_index(2, w.nk)
    last_kv = w.nk - 1
    tq, tk = p.tile_q, p.tile_k

    @pl.when(kv == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    c_base = kv * p.block_k

    def q_tile(g, qt):
        q0 = _at(qt, tq)
        r0 = qb * p.block_q + q0
        q = _still(q_ref[g, pl.ds(q0, tq), :], w)
        segq = segq_ref[g, 0, pl.ds(qt, 1), :] if w.have_seg else None
        n_plain, n_end = _chunk_bounds(r0, tq, c_base, w.nkt, tk,
                                       causal=p.causal, offset=w.offset,
                                       sk=p.sk, sk_p=p.sk_p)

        def chunk(masked):
            def body(j, carry):
                m, l, acc = carry            # [1, tq], [1, tq], [d, tq]
                k0 = _at(j, tk)
                vb = v_ref[g, pl.ds(k0, tk), :]
                s = _scores(
                    k_ref[g, pl.ds(k0, tk), :], q, r0, c_base + k0, w,
                    masked=masked,
                    bias_col=_col(bias_ref, g, j) if w.have_bias else None,
                    segq_row=segq,
                    segk_col=_col(segk_ref, g, j) if w.have_seg else None)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                # a query every key so far is masked for keeps m at
                # NEG_INF: its exp(s - m) would be exp(0) = 1, so the
                # exponent's max is held above the masked scores
                prob = jnp.exp(s - jnp.maximum(m_new, NEG_INF / 2))
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(prob, axis=0, keepdims=True)
                # prob rounded to the input dtype for the MXU pass;
                # accumulator f32. v.T @ prob.T = (prob @ v).T
                acc = acc * alpha + jax.lax.dot_general(
                    vb, prob.astype(vb.dtype), _TN,
                    preferred_element_type=jnp.float32)
                return m_new, l, acc
            return body

        row = (g, pl.ds(qt, 1))
        m, l, acc = _two_loops(
            n_plain, n_end, chunk,
            (m_scr[row], l_scr[row], acc_scr[g, qt]), w)
        m_scr[row], l_scr[row], acc_scr[g, qt] = m, l, acc

        @pl.when(kv == last_kv)
        def _finalize():
            l_safe = jnp.maximum(l, 1e-30)
            o_ref[g, pl.ds(q0, tq), :] = (
                acc * (1.0 / l_safe)).T.astype(o_ref.dtype)
            lse_ref[g, 0, pl.ds(qt, 1), :] = m + jnp.log(l_safe)

    # causal: a key block strictly above the (offset) diagonal is only
    # entered to write the result, if it is the last
    @pl.when(_block_runs(qb, kv, w) | (kv == last_kv))
    def _step():
        _over_tiles(w.nqt, q_tile, w)


def _grid_maps(p: FlashPlan, offset):
    """Index maps of the (bh/heads, nq, nk) grid the forward and dq
    kernels share: q-side blocks follow j, k-side blocks follow the
    clamped kk (:func:`_kj_clamp`)."""
    nk = p.sk_p // p.block_k
    ck = _kj_clamp(p.causal, p.block_q, p.block_k, nk, offset)
    return (lambda i, j, kk: (i, j, 0), lambda i, j, kk: (i, ck(kk, j), 0),
            lambda i, j, kk: (i, j, 0, 0),
            lambda i, j, kk: (i, ck(kk, j), 0, 0))


def _planned(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
             scale=None):
    """(plan, walk, operands) of one call."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    p = plan_blocks(sq, sk, d, q.dtype, causal, bias is not None,
                    seg_q is not None, block_q, block_k, bh=b * h,
                    dv=v.shape[-1], scale=scale)
    w = _Walk(p, scale, sk - sq, bias is not None, seg_q is not None)
    return p, w, _prepare(q, k, v, bias, seg_q, seg_k, p)


def _flash_fwd(q, k, v, bias, seg_q, seg_k, causal: bool,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, scale: Optional[float] = None):
    """``q`` and ``k`` are ``[b, h, s, d]`` and ``v`` ``[b, h, s_k, dv]``:
    the scores contract over ``d``, the output rows are ``dv`` wide (the
    kernel reads both from its blocks' shapes)."""
    b, h, sq, d = q.shape
    dv = v.shape[-1]
    bh = b * h
    p, w, ops = _planned(q, k, v, bias, seg_q, seg_k, causal, block_q,
                         block_k, scale)
    _record_plan(p)
    g, nq, nk = p.heads, w.nq, w.nk
    q_map, k_map, qrow_map, krow_map = _grid_maps(p, w.offset)
    mask_specs, mask_args = _mask_specs(ops, p, qrow_map, krow_map)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, w=w),
        name="flash_fwd",
        grid=(bh // g, nq, nk),
        in_specs=[pl.BlockSpec((g, p.block_q, d), q_map),
                  pl.BlockSpec((g, p.block_k, d), k_map),
                  pl.BlockSpec((g, p.block_k, dv), k_map)] + mask_specs,
        out_specs=[pl.BlockSpec((g, p.block_q, dv), q_map),
                   pl.BlockSpec((g, 1, w.nqt, p.tile_q), qrow_map)],
        out_shape=[jax.ShapeDtypeStruct((bh, p.sq_p, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, nq, w.nqt, p.tile_q),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, w.nqt, p.tile_q), jnp.float32),
                        pltpu.VMEM((g, w.nqt, p.tile_q), jnp.float32),
                        pltpu.VMEM((g, w.nqt, dv, p.tile_q), jnp.float32)],
        interpret=interpret,
    )(ops.q, ops.k, ops.v, *mask_args)
    out = out.reshape(b, h, p.sq_p, dv)[:, :, :sq]
    lse = lse.reshape(b, h, p.sq_p)[:, :, :sq]
    return out, lse


# ---------------------------------------------------------------------------
# backward (two pallas passes, flash-attention-2 style)


def _probs(s, lse_row):
    """exp(s - lse), with lse held above the masked scores: a query with
    every key masked has lse near NEG_INF, and exp(s - lse) of its masked
    scores would be exp(0) = 1 — they must contribute 0."""
    return jnp.exp(s - jnp.maximum(lse_row, NEG_INF / 2))


def _dq_kernel(*refs, w: _Walk):
    (q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
     (g_ref, lse_ref, delta_ref), (dq_ref,), (dq_scr,)) = _split_refs(
         refs, w, 3, 1)
    p = w.plan
    qb, kv = _grid_index(1, w.nq), _grid_index(2, w.nk)
    last_kv = w.nk - 1
    tq, tk = p.tile_q, p.tile_k

    @pl.when(kv == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    c_base = kv * p.block_k

    def q_tile(g, qt):
        q0 = _at(qt, tq)
        r0 = qb * p.block_q + q0
        row = (g, 0, pl.ds(qt, 1))
        q = _still(q_ref[g, pl.ds(q0, tq), :], w)
        do = g_ref[g, pl.ds(q0, tq), :]
        lse, delta = lse_ref[row], delta_ref[row]
        segq = segq_ref[row] if w.have_seg else None
        n_plain, n_end = _chunk_bounds(r0, tq, c_base, w.nkt, tk,
                                       causal=p.causal, offset=w.offset,
                                       sk=p.sk, sk_p=p.sk_p)

        def chunk(masked):
            def body(j, dq_t):               # [d, tq]
                k0 = _at(j, tk)
                kb = k_ref[g, pl.ds(k0, tk), :]
                vb = v_ref[g, pl.ds(k0, tk), :]
                s = _scores(
                    kb, q, r0, c_base + k0, w, masked=masked,
                    bias_col=_col(bias_ref, g, j) if w.have_bias else None,
                    segq_row=segq,
                    segk_col=_col(segk_ref, g, j) if w.have_seg else None)
                dp = jax.lax.dot_general(vb, do, _NT,
                                         preferred_element_type=jnp.float32)
                ds = _probs(s, lse) * (dp - delta)
                # k.T @ ds.T = (ds @ k).T; ds rounded to the input dtype
                return dq_t + jax.lax.dot_general(
                    kb, ds.astype(kb.dtype), _TN,
                    preferred_element_type=jnp.float32)
            return body

        dq_t = _two_loops(n_plain, n_end, chunk, dq_scr[g, qt], w)
        dq_scr[g, qt] = dq_t

        @pl.when(kv == last_kv)
        def _finalize():
            dq_ref[g, pl.ds(q0, tq), :] = (dq_t * w.scale).T.astype(
                dq_ref.dtype)

    @pl.when(_block_runs(qb, kv, w) | (kv == last_kv))
    def _step():
        _over_tiles(w.nqt, q_tile, w)


def _dkv_kernel(*refs, w: _Walk):
    (q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
     (g_ref, lse_ref, delta_ref), (dk_ref, dv_ref),
     (dk_scr, dv_scr)) = _split_refs(refs, w, 3, 2)
    p = w.plan
    kb_i, qb = _grid_index(1, w.nk), _grid_index(2, w.nq)
    last_q = w.nq - 1
    tq, tk = p.tile_q, p.tile_k

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    r_base = qb * p.block_q

    def k_tile(g, kt):
        k0 = _at(kt, tk)
        c0 = kb_i * p.block_k + k0
        rows = (g, pl.ds(k0, tk))
        ks = _still(k_ref[rows], w)
        vb = v_ref[rows]
        bias_col = _col(bias_ref, g, kt) if w.have_bias else None
        segk_col = _col(segk_ref, g, kt) if w.have_seg else None
        # query chunks of this block, ascending: those before n_lo see
        # none of the tile's keys, [n_lo, n_plain) cross the diagonal,
        # [n_plain, nqt) see all of them. Padded keys need no mask here:
        # they only fill their own (sliced-off) dk/dv rows.
        n_lo = n_plain = 0
        if p.causal:
            n_lo = _clip((c0 - w.offset - r_base) // tq, 0, w.nqt)
            n_plain = _clip(
                (c0 + tk - 1 - w.offset - r_base + tq - 1) // tq, 0, w.nqt)

        def chunk(masked):
            def body(i, carry):
                dk, dv = carry               # [tk, d] each
                q0 = _at(i, tq)
                row = (g, 0, pl.ds(i, 1))
                qc = q_ref[g, pl.ds(q0, tq), :]
                do = g_ref[g, pl.ds(q0, tq), :]
                s = _scores(ks, qc, r_base + q0, c0, w, masked=masked,
                            bias_col=bias_col,
                            segq_row=segq_ref[row] if w.have_seg else None,
                            segk_col=segk_col)
                prob = _probs(s, lse_ref[row])
                dv = dv + jax.lax.dot_general(
                    prob.astype(do.dtype), do, _NN,
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(vb, do, _NT,
                                         preferred_element_type=jnp.float32)
                ds = prob * (dp - delta_ref[row])
                dk = dk + jax.lax.dot_general(
                    ds.astype(qc.dtype), qc, _NN,
                    preferred_element_type=jnp.float32)
                return dk, dv
            return body

        carry = _loop(n_lo, n_plain, chunk(True), (dk_scr[rows], dv_scr[rows]))
        dk, dv = _loop(n_plain, w.nqt, chunk(False), carry)
        dk_scr[rows], dv_scr[rows] = dk, dv

        @pl.when(qb == last_q)
        def _finalize():
            dk_ref[rows] = (dk * w.scale).astype(dk_ref.dtype)
            dv_ref[rows] = dv.astype(dv_ref.dtype)

    @pl.when(_block_runs(qb, kb_i, w) | (qb == last_q))
    def _step():
        _over_tiles(w.nkt, k_tile, w)


def _flash_bwd(q, k, v, bias, seg_q, seg_k, causal, out, lse, g,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, delta=None, scale: Optional[float] = None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    if v.shape[-1] != d:
        # the dq and dkv kernels hold dO, K and V in blocks of one width
        raise NotImplementedError(
            f"flash_attention: no backward pass for values {v.shape[-1]} "
            f"wide under scores that contract over {d}; the forward takes "
            f"unequal widths, the dq and dkv kernels do not yet")
    if delta is None:
        delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    p, w, ops = _planned(q, k, v, bias, seg_q, seg_k, causal, block_q,
                         block_k, scale)
    hg, nq, nk = p.heads, w.nq, w.nk

    # padded q rows: g/delta 0 and lse huge, so p=exp(s-lse)=0 — they
    # contribute nothing to dk/dv, and their dq rows are sliced off
    g_r = _pad_seq(g, p.sq_p, 2).reshape(bh, p.sq_p, d)
    lse_r = _rows(_pad_seq(lse, p.sq_p, 2, -NEG_INF), bh, nq, w.nqt, p.tile_q)
    delta_r = _rows(_pad_seq(delta, p.sq_p, 2), bh, nq, w.nqt, p.tile_q)

    # ---- dq pass: grid (bh/heads, nq, nk), K/V on the inner dim; causal
    # steps past the diagonal re-request the same block so their DMA is
    # skipped (see _kj_clamp)
    q_map, k_map, qrow_map, krow_map = _grid_maps(p, w.offset)
    mask_specs, mask_args = _mask_specs(ops, p, qrow_map, krow_map)
    q_spec = pl.BlockSpec((hg, p.block_q, d), q_map)
    k_spec = pl.BlockSpec((hg, p.block_k, d), k_map)
    row_spec = pl.BlockSpec((hg, 1, w.nqt, p.tile_q), qrow_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, w=w),
        name="flash_dq",
        grid=(bh // hg, nq, nk),
        in_specs=[q_spec, k_spec, k_spec] + mask_specs
        + [q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, p.sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((hg, w.nqt, d, p.tile_q), jnp.float32)],
        interpret=interpret,
    )(ops.q, ops.k, ops.v, *mask_args, g_r, lse_r, delta_r)

    # ---- dk/dv pass: grid (bh/heads, nk, nq), Q/dO on the inner dim;
    # causal steps before a key block's first useful q block re-request
    # that first block (DMA skipped, see _qi_clamp)
    cq = _qi_clamp(causal, p.block_q, p.block_k, nq, w.offset)
    q_map = lambda i, j, kk: (i, cq(kk, j), 0)
    k_map = lambda i, j, kk: (i, j, 0)
    qrow_map = lambda i, j, kk: (i, cq(kk, j), 0, 0)
    krow_map = lambda i, j, kk: (i, j, 0, 0)
    mask_specs, mask_args = _mask_specs(ops, p, qrow_map, krow_map)
    q_spec = pl.BlockSpec((hg, p.block_q, d), q_map)
    k_spec = pl.BlockSpec((hg, p.block_k, d), k_map)
    row_spec = pl.BlockSpec((hg, 1, w.nqt, p.tile_q), qrow_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, w=w),
        name="flash_dkv",
        grid=(bh // hg, nk, nq),
        in_specs=[q_spec, k_spec, k_spec] + mask_specs
        + [q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, p.sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, p.sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((hg, p.block_k, d), jnp.float32),
                        pltpu.VMEM((hg, p.block_k, d), jnp.float32)],
        interpret=interpret,
    )(ops.q, ops.k, ops.v, *mask_args, g_r, lse_r, delta_r)

    return (dq.reshape(b, h, p.sq_p, d)[:, :, :sq],
            dk.reshape(b, h, p.sk_p, d)[:, :, :sk],
            dv.reshape(b, h, p.sk_p, d)[:, :, :sk])


# ---------------------------------------------------------------------------
# custom VJP plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_core(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
                interpret, scale=None):
    out, _ = _flash_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q,
                        block_k, interpret, scale)
    return out


def _flash_core_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
                    interpret, scale=None):
    out, lse = _flash_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q,
                          block_k, interpret, scale)
    return out, (q, k, v, bias, seg_q, seg_k, out, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, scale, res, g):
    q, k, v, bias, seg_q, seg_k, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, seg_q, seg_k, causal, out, lse, g,
                            block_q, block_k, interpret, scale=scale)
    return dq, dk, dv, None, None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q, k, v,
    causal: bool = False,
    attn_mask: Optional[jax.Array] = None,
    key_bias: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
    scale: Optional[float] = None,
):
    """Flash attention over ``q``, ``k`` [b, h, s, d] and ``v``
    [b, h, s_k, dv]; the output is [b, h, s_q, dv]. ``dv`` may differ
    from ``d`` in the forward pass (latent attention scores over 192 and
    sums values 128 wide); the backward pass raises for unequal widths.

    - ``key_bias``: additive [b, s_k] (padding mask).
    - ``segment_ids`` / ``kv_segment_ids``: int [b, s] ragged-batch ids
      (LoD analog); attention is masked across segment boundaries. When
      only ``segment_ids`` is given it is used for both sides (self
      attention).
    - ``attn_mask``: a [b,1,1,s_k] additive mask is converted to a key
      bias; any other dense mask falls back to the XLA composition.
    - ``block_q``/``block_k``: rows of Q / K a grid step brings in. None
      resolves the ``flash_block_q``/``_k`` config flags, and unset flags
      leave the choice to :func:`plan_blocks` (read at trace time).
    - ``return_lse``: also return the per-query logsumexp [b, h, s_q]
      (forward only — used by ring attention to merge shards).
    - ``scale``: the softmax scale; None is ``d ** -0.5``.
    """
    from ..core.errors import enforce

    block_q, block_k = resolve_block_shapes(block_q, block_k)
    if interpret is None:
        interpret = default_interpret()
    enforce(kv_segment_ids is None or segment_ids is not None,
            "flash_attention: kv_segment_ids requires segment_ids (the "
            "query-side ids) as well")
    if attn_mask is not None:
        if attn_mask.ndim == 4 and attn_mask.shape[1] == 1 and attn_mask.shape[2] == 1:
            key_bias = attn_mask[:, 0, 0, :] if key_bias is None \
                else key_bias + attn_mask[:, 0, 0, :]
        else:
            # general dense mask: XLA path, with bias/segment masking
            # folded in so nothing is silently dropped
            warnings.warn(
                f"flash_attention: a dense attn_mask of shape "
                f"{tuple(attn_mask.shape)} cannot ride the kernel (only "
                f"[b,1,1,s_k] can, as a key bias); this call traces the "
                f"dense O(s^2) XLA composition", stacklevel=2)
            mask = attn_mask
            if key_bias is not None:
                mask = mask + key_bias[:, None, None, :]
            if segment_ids is not None:
                seg_k_ = kv_segment_ids if kv_segment_ids is not None else segment_ids
                same = segment_ids[:, None, :, None] == seg_k_[:, None, None, :]
                mask = jnp.where(same, mask, NEG_INF)
            return _mask_fallback(q, k, v, mask, causal, scale)
    seg_q = segment_ids
    seg_k = kv_segment_ids if kv_segment_ids is not None else segment_ids
    bias = None if key_bias is None else key_bias.astype(jnp.float32)
    if return_lse:
        return _flash_fwd(q, k, v, bias, seg_q, seg_k, causal,
                          block_q, block_k, interpret, scale)
    return _flash_core(q, k, v, bias, seg_q, seg_k, causal,
                       block_q, block_k, interpret, scale)


def _mask_fallback(q, k, v, attn_mask, causal, scale=None):
    from .attention_scores import scores_mxu
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    s = scores_mxu(q, k, scale)
    s = s + attn_mask
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        s = jnp.where(cm, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
