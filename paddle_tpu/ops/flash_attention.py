"""Flash attention — pallas TPU kernels (forward AND backward).

New first-class component per SURVEY §5/§7: the reference has no
attention kernels at all (attention was composed from mul/softmax ops in
models, e.g. benchmark/fluid/models/machine_translation.py), and no
answer to long sequences beyond LoD ragged batching. This supplies
O(seq) -memory attention on TPU: a forward kernel and a backward that
recomputes probabilities from the saved logsumexp, the
flash-attention-2 decomposition. The backward is one kernel or two, by
the plan (``FlashPlan.backward``, :func:`_backward`):

- ``fused``, where both sequences sit in one grid step (up to
  ``RESIDENT`` rows, within ``FUSED_BYTES`` of VMEM): ``flash_bwd`` walks
  q and dO chunks past a still K/V tile, computes the tile's scores,
  probabilities and ``ds`` once, carries dk and dv, and adds
  ``ds^T k`` into a float32 scratch that holds the step's whole dq. Five
  products a tile and one exponential.
- ``split``, where a sequence streams: dq is a sum over key blocks and
  dk / dv are sums over query blocks, and those arrive in different grid
  steps, so each sum gets a kernel whose grid ends on its own axis
  (``flash_dq``; ``flash_dkv``, the same body as ``flash_bwd`` without the
  dq scratch) and the scores, ``dp`` and ``ds`` are computed in both:
  seven products a tile and two exponentials. A whole dq of a streamed
  call does not fit VMEM (a float32 row a query, all heads of a step),
  and writing partial sums to HBM for every key block would cost more
  than the two products.

The tile walk, shared by the kernels:

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
  for that (measured: see ``TILE``). What the kernel can know of the
  walk when it is traced, it writes out (``_loop``): the compiler
  schedules a loop body alone, and only a walk it sees whole lets one
  tile's matmuls run under the next one's softmax. A sequence that is
  resident whole and short has every bound a python int. A forward call
  whose keys stream (:func:`_written`) knows every key block's bounds
  where one q block meets them under a static offset (a window's piece
  against its ring: each block a written-out body of its own, picked by
  the grid's key index), and under a diagonal that a traced scalar places
  it knows the blocks that lie wholly below it, whose tiles take no mask
  and no bound (a later piece against the whole cache: one written-out
  body, picked by one scalar predicate); the blocks a traced diagonal
  crosses, and the backward kernels' streamed walks, keep traced loops.
  In a written-out streamed block the order is written too: a tile's
  scores are made before its neighbour's softmax (``_fwd_kernel``).
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

Which layout a call gets, and why. The TPU tiles an array's two minor
dimensions (8, 128): a ``[b, h, s, 64]`` operand fills 64 lanes of every
128 and is held, written and read at twice its size, and a block's
projections leave q, k and v as ``[b, s, h*d]``, so every call on
``[b, h, s, d]`` stood between transposes that wrote and read such padded
arrays (twelve a layer of a remat train step: PERF.md, PR 32). So the
layout follows the call's shape, one tile walk for both:

- rank-4 ``[b, h, s, d]`` operands (ring attention, Ulysses, a windowed
  call): ``bhsd``. A grid step's block is ``(heads, rows, d)`` of the
  flattened ``(b*h, s, d)`` array and a head is a leading index.
- rank-3 ``[b, s, h*d]`` operands with their head count: ``bsd``, where
  :func:`lane_heads` finds whole 128-lane groups (a head 128-multiple
  wide, or ``128 // d`` narrower ones dividing the head count). A step's
  block is ``(1, rows, heads*d)``, whole lane groups of one batch row,
  picked by the index map over the array as it lies; inside, heads that
  share a lane group are told apart where they lie (zeros in the other
  head's lanes of the still operand, whole groups streaming past it:
  :attr:`_Walk.shared_lanes`), and what a walk writes transposed (o, dq)
  is kept a group in scratch and written a group, one transpose and 128
  dense lanes (:meth:`_Walk.write_groups`). The chip measured that form
  faster than lane slices of every operand, and at the ``bhsd`` kernels'
  time (2% over it at 16 x 10 heads). Bias and ids are a batch row's; lse
  and delta stay a head's. A fused self-attention projection ``[b, s, 3*h*d]``
  goes in as ``q`` alone and is passed three times with lane-block
  offsets, because three slices of it would be three copies. Shapes the
  form does not fit (one 192-wide head of scores over 128-wide values, an
  odd count of 64-wide heads) are transposed to ``bhsd`` inside the call.
- a ``bsd`` call with a rotary pair (latent attention's prefill, forward
  only): scores that contract over 128 + 64 are not one 192-wide head,
  which lies astride the lane groups, but two operands. ``q``, ``k``, ``v``
  and the output are ``[b, s, h * 128]``, one head a lane group, as their
  projections leave and take them; ``q_rot [b, s, h * 64]`` holds two
  heads' rotary parts a group and ``k_rot [b, s, 64]`` has no head at all:
  its block is a batch row's and stays while the heads walk past. The two
  parts meet in VMEM: a step lays each head's keys and the row's ``k_rot``
  side by side in scratch once, a query tile's two parts are joined when
  it is taken (the head's rotary part cut out of its lane group,
  :meth:`_Walk.rot_still`), and a tile's scores are one product over 128
  + 64 whose sum forms in the MXU's float32 accumulator, two passes of the
  128 x 128 array as before. In HBM nothing is concatenated, broadcast to
  heads or transposed round the call.

- grouped heads (``kv_heads``, forward only): ``k`` and ``v`` hold one head
  for every ``group`` of ``q``'s, in either layout, and are never repeated
  to ``q``'s count: a step's query heads lie in one group, or are whole
  groups, and its block of ``k`` and ``v`` is the key heads they read, picked
  by the index map where the cache holds them (:func:`_specs`); inside, head
  ``g`` reads head ``g // group`` of that block. A ``bsd`` call needs a head
  to be its own lane group (128 wide).
- a query offset (``q_offset``, forward only): the causal diagonal lies
  where a traced scalar says, not at ``sk - sq``: a later piece of a prompt
  against the whole cache. The scalar reaches the index maps (the clamp
  that keeps blocks past the diagonal from being fetched) and the kernel
  (the tile bounds and the mask) through SMEM.

``flash.plan`` records ``layout``, ``lane_heads``, ``backward``, ``window``,
``rot``, ``kv_heads``, ``q_offset`` and ``tiles_written`` (the tiles of
``tiles_run`` that the forward kernel holds written out) for every call
traced. The forward kernel itself is traced once for each distinct walk
and operand shapes, however often a program calls it and however many
passes trace the program (:func:`_fwd_call`).

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
import operator
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
# WRITTEN: the tile bodies a streamed forward kernel may hold written out
# (:func:`_written`). The largest walk the chip has timed is a 2,048-row
# piece on a 4,096-key window's 6,144 keys: 28 bodies of 512 x 512 for its
# 36 tiles, 12.4 ms a call where the loops take 20.6, for 6.8 s of Mosaic
# compile where the loops take 1.0 (PERF.md section 6, PRs 46 and 47). A
# walk beyond it keeps its loops until a reading says otherwise.
TILE = 512
RESIDENT = 2048
STREAM_BLOCK = 1024
STEP_SCORES = 2 << 20
STEP_BYTES = 6 << 20
UNROLL = 8
WRITTEN = 32
# FUSED_BYTES: the blocks and accumulators one backward kernel may hold a
# grid step (``_backward``). A compile for a described v5e takes 10.0 MB
# at every shape tried (2048 x 128 and two heads of 1024 x 64 in bfloat16)
# and first refuses at 12.8 (float32, 1536 x 128, packed).
FUSED_BYTES = 12 << 20

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
    """What one attention call's kernels run with."""
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
    layout: str = "bhsd"  # the operands' layout: ``bhsd``, or ``bsd`` (packed)
    lane_heads: int = 0   # bsd: heads a 128-lane group of the minor dimension
    backward: str = "split"   # ``fused``: one backward kernel (:func:`_backward`)
    window: int = 0       # keys a causal query sees, itself included; 0: all
    rot: int = 0          # width of a second score operand (q_rot, k_rot); 0: none
    group: int = 1        # query heads that read one key/value head
    tiles_written: int = 0    # of tiles_run, written out in the forward kernel


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


def padded_rows(s: int) -> int:
    """The length a self-attention call of ``s`` rows is padded to inside
    (queries pad furthest: whole lanes, then whole tiles). A caller whose
    operands come out of products can make them that long by padding what
    goes into the products, which may be far narrower, and cut the
    output; rows past ``s`` are zeros, which causal queries never see."""
    return _axis_plan(s, None, 128)[0]


def padded_keys(s: int) -> int:
    """The length the keys of a call over ``s`` keys are padded to inside
    (whole registers of 16, whole tiles, whole blocks once they stream). A
    cache allocated that long goes into the kernel as it is: no padded
    copy of it is made for a call, and a causal query never sees the rows
    past its own."""
    return _axis_plan(s, None, 16)[0]


def _clip(v, lo, hi):
    """``clip`` on python ints (the plan's count, a walk the compiler
    sees whole) or on traced scalars."""
    if any(isinstance(x, jax.Array) for x in (v, lo, hi)):
        return jnp.clip(v, lo, hi)
    return max(lo, min(v, hi))


def _where(cond, a, b):
    """``where`` on a python bool or on a traced one."""
    return jnp.where(cond, a, b) if isinstance(cond, jax.Array) else (
        a if cond else b)


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


def _window_bounds(r0, tile_q, c_base, n_chunks, tile_k, bounds, *, offset,
                   window):
    """:func:`_chunk_bounds` under a sliding window (a causal row ``r``
    sees keys ``r + offset - window < c <= r + offset``): ``(n_start,
    n_lead, n_plain, n_end)``. Chunks before ``n_start`` lie wholly behind
    the first row's window and are not visited, ``[n_start, n_lead)`` are
    cut by the window's edge and ``[n_plain, n_end)`` by the diagonal (or
    hold padded keys): both take the mask; ``[n_lead, n_plain)`` take
    none."""
    n_plain, n_end = bounds
    first = r0 + offset - window + 1 - c_base    # first key of the first row
    n_start = _clip(first // tile_k, 0, n_chunks)
    n_lead = _clip((first + tile_q - 1 + tile_k - 1) // tile_k, 0, n_chunks)
    n_end = _clip(n_end, n_start, n_chunks)
    n_plain = _clip(n_plain, n_start, n_end)
    return n_start, _clip(n_lead, n_start, n_plain), n_plain, n_end


def _interior(qb, kb, p: FlashPlan, offset):
    """Does every query of q block ``qb`` see every key of key block ``kb``:
    the block wholly below the first row's diagonal, wholly inside the last
    row's window, no padded key in it? Such a block's tiles are all visited
    and take no mask. On python ints or on traced scalars."""
    inside = True
    if p.causal:
        inside = (kb + 1) * p.block_k <= qb * p.block_q + offset + 1
        if p.window:
            inside &= (kb * p.block_k
                       >= (qb + 1) * p.block_q + offset - p.window)
    if p.sk != p.sk_p:
        inside &= (kb + 1) * p.block_k <= p.sk
    return inside


def _written(p: FlashPlan, static_offset: bool):
    """``(form, tiles)``: how the forward kernel of a call with plan ``p``
    writes its walk out when it is traced, python iterations where a loop
    would turn, and how many of ``tiles_run`` run so.

    - ``all``: both sequences are one grid step's and a head's walk is up
      to ``UNROLL`` tiles: every bound is a python int.
    - ``blocks``: the keys stream past one q block under a static offset,
      so a key block's bounds are python ints once the grid's key index is
      known: the interior blocks (:func:`_interior`) share one body and
      every other block that runs has its own, while the kernel stays
      within ``WRITTEN`` tile bodies.
    - ``interior``: a sequence streams and the diagonal lies where traced
      scalars say (a ``q_offset``; several q blocks): the interior blocks
      run one written-out body, the blocks that the diagonal, a window's
      edge or padding touch run the traced loops. Counted at offset ``sk -
      sq``, as ``tiles_run`` is (the last piece's, under a ``q_offset``).
    - ``""``: nothing. A resident walk over ``UNROLL``; a streamed block of
      one tile, which has no neighbour to run under; no interior block; a
      body over ``WRITTEN``.

    A packed step's heads are python iterations, so each is a body."""
    nq, nk = p.sq_p // p.block_q, p.sk_p // p.block_k
    block = (p.block_q // p.tile_q) * (p.block_k // p.tile_k)
    if nq == nk == 1:
        return ("all", p.tiles_run) if block <= UNROLL else ("", 0)
    bodies = p.heads if p.layout == "bsd" else 1
    if block == 1 or bodies * block > WRITTEN:
        return "", 0
    inside = block * sum(_interior(qb, kb, p, p.sk - p.sq)
                         for qb in range(nq) for kb in range(nk))
    if static_offset and nq == 1 and bodies * (
            min(inside, block) + p.tiles_run - inside) <= WRITTEN:
        return "blocks", p.tiles_run
    return ("interior", inside) if inside else ("", 0)


def _backward(p: FlashPlan, itemsize) -> str:
    """``fused`` or ``split`` (the module's docstring) for a call with plan
    ``p`` and operands of ``itemsize`` bytes: one kernel where a grid step
    holds both sequences and its blocks (q, dO, dq and k, v, dk, dv, each
    double-buffered, and the three float32 accumulators) leave the
    compiler room in VMEM. They do not for heads 256 wide beyond 1,024
    rows, nor for float32 operands at bfloat16's longest shapes."""
    lanes = p.heads * (p.d if p.layout == "bsd" else _round_up(p.d, 128))
    held = lanes * (p.sq_p * (3 * 2 * itemsize + 4)
                    + p.sk_p * (4 * 2 * itemsize + 2 * 4))
    resident = (p.sq_p, p.sk_p) == (p.block_q, p.block_k)
    return "fused" if resident and held <= FUSED_BYTES else "split"


def lane_heads(d, dv, num_heads) -> int:
    """Heads a 128-lane group of ``[b, s, num_heads * d]`` operands, or 0
    where the kernels cannot read that layout: a head 128-multiple wide
    is its own group, ``128 // d`` narrower ones fill one where they
    divide the head count, and anything else (one 192-wide head of scores
    over 128-wide values, an odd count of 64-wide heads) has a head
    astride a group's edge. Latent attention's 128 + 64 is no such shape
    when its parts come apart: 128-wide heads here, their own groups, and
    the 64 as the call's rotary pair (:func:`rot_lane_heads`)."""
    if num_heads is None or dv != d:
        return 0
    if d % 128 == 0:
        return 1
    return 128 // d if 128 % d == 0 and num_heads % (128 // d) == 0 else 0


def _in_groups(heads, group) -> bool:
    """Do ``heads`` consecutive query heads (from a multiple of ``heads``)
    lie in one group of ``group``, or make whole groups?"""
    return group % heads == 0 or heads % group == 0


def _rot_group(rot) -> int:
    """Heads whose rotary parts fill a 128-lane group of ``q_rot`` (1 where
    a part is whole groups itself)."""
    return max(128 // rot, 1)


def rot_lane_heads(d, dv, num_heads, rot) -> int:
    """Heads a 128-lane group of a rotary operand ``q_rot [b, s, num_heads
    * rot]``, or 0 where the kernel cannot take the pair in place: the
    other operands' heads are their own lane groups (:func:`lane_heads` 1)
    and the rotary parts fill whole groups by the same rule, two a group
    (64 wide) or a part whole groups itself."""
    if lane_heads(d, dv, num_heads) != 1 or rot % 64:
        return 0
    return lane_heads(rot, rot, num_heads)


def plan_blocks(sq, sk, d, dtype=jnp.bfloat16, causal=False,
                have_bias=False, have_seg=False, block_q=None, block_k=None,
                bh=1, dv=None, scale=None, num_heads=None,
                window=0, rot=0, group=1, q_offset=False) -> FlashPlan:
    """Blocks, compute tile and heads a step for one attention call, from
    what the call can see. One rule for every shape: pad each axis to
    whole registers (128 queries, 16 keys; no further: 896 stays 896),
    keep a sequence of up to ``RESIDENT`` rows in VMEM whole and stream
    longer ones in ``STREAM_BLOCK`` rows, compute in tiles of up to
    ``TILE x TILE`` (:func:`_axis_plan`), and group heads until a grid step
    computes ``STEP_SCORES`` scores. Explicit ``block_q``/``block_k`` win
    (the compute tile then follows the block). ``have_bias``/``have_seg`` do
    not change the blocks today; they are part of what a plan may depend
    on, and ``dtype`` decides with the shape whether the backward is one
    kernel (:func:`_backward`). ``d`` is the width the scores contract
    over and ``dv`` that of a value (they may differ: 192 and 128);
    ``scale`` is the softmax scale where it is not ``d ** -0.5``.

    ``num_heads`` says the call's operands are ``[b, s, num_heads * d]``,
    as a projection leaves them. The plan keeps that layout (``bsd``)
    where a step's block of the minor dimension can be whole 128-lane
    groups (:func:`lane_heads`); a step then holds whole groups of one
    batch row. Else the call is laid out ``[b, h, s, d]`` first
    (``bhsd``), which is also what a rank-4 call is.

    ``window`` (causal calls, forward only): a query sees its own key and
    the ``window - 1`` before it. The walk skips the key tiles that lie
    wholly behind a query tile's window and masks the tile its edge cuts;
    ``tiles_run`` counts what is left. 0: no window.

    ``rot`` (``bsd`` calls, forward only): the width of a second score
    operand, ``q_rot [b, s, num_heads * rot]`` against one ``k_rot [b, s,
    rot]`` that every head shares (latent attention's rotary 64 beside its
    128). The walk and the tiles are the call's without it; a step holds
    one head, or as many as start a lane group of ``q_rot``
    (:func:`rot_lane_heads`). 0: none.

    ``group`` (forward only): query heads that read one key/value head
    (grouped-query attention; ``k`` and ``v`` hold ``1 / group`` of the
    heads, head ``h`` reads ``h // group``). A step's heads then lie in one
    group or are whole groups, so that its block of ``k`` and ``v`` is the
    key heads they read, as the cache holds them; a ``bsd`` call needs a
    head to be its own lane group. 1: every head its own.

    ``q_offset`` (forward only): the causal diagonal lies where a traced
    scalar says. Blocks and tiles are the call's without it; what its
    kernel can write out of the walk is less (``tiles_written``,
    :func:`_written`)."""
    del have_bias, have_seg
    dv = d if dv is None else dv
    packed = lane_heads(d, dv, num_heads)
    sq_p, block_q, tile_q = _axis_plan(sq, block_q, 128)
    sk_p, block_k, tile_k = _axis_plan(sk, block_k, 16)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    fold = math.frexp(scale)[0] == 0.5

    # the forward walk's executed share of the score rectangle
    offset = sk - sq
    run = 0
    for r0 in range(0, sq_p, tile_q):
        bounds = _chunk_bounds(r0, tile_q, 0, sk_p // tile_k, tile_k,
                               causal=causal, offset=offset, sk=sk,
                               sk_p=sk_p)
        n_start, n_end = 0, bounds[1]
        if window:
            n_start, _, _, n_end = _window_bounds(
                r0, tile_q, 0, sk_p // tile_k, tile_k, bounds, offset=offset,
                window=window)
        run += n_end - n_start
    tiles_all = (sq_p // tile_q) * (sk_p // tile_k)

    # heads a step: until the step computes STEP_SCORES scores, inside
    # STEP_BYTES of VMEM (about a dozen double-buffered row blocks a head)
    share = run / tiles_all if (sq_p, sk_p) == (block_q, block_k) else 1.0
    step_scores = block_q * block_k * share
    if packed:
        # whole lane groups of one batch row, each head's walk written
        # out (a lane offset is static): as many as keep the step's code
        # within UNROLL tile bodies, or head bodies where tiles loop
        written = (sq_p, sk_p) == (block_q, block_k) and tiles_all <= UNROLL
        step_bytes = 6 * max(block_q, block_k) * (d + dv + rot) * 2
        rot_heads = _rot_group(rot) if rot else 1
        heads = max(g for g in range(packed, num_heads + 1, packed)
                    if num_heads % g == 0 and (
                        g == packed or (
                            g % rot_heads == 0 and _in_groups(g, group)
                            and g * step_scores <= STEP_SCORES
                            and g * step_bytes <= STEP_BYTES
                            and g * (tiles_all if written else 1) <= UNROLL)))
        layout = ("bsd", packed)
    else:
        step_bytes = (6 * max(block_q, block_k)
                      * (_round_up(d, 128) + _round_up(dv, 128)) * 2)
        heads = max(g for g in range(1, bh + 1) if bh % g == 0 and (
            g == 1 or (_in_groups(g, group)
                       and g * step_scores <= STEP_SCORES
                       and g * step_bytes <= STEP_BYTES)))
        layout = ()
    plan = FlashPlan(sq, sk, d, block_q, block_k, tile_q, tile_k, heads,
                     sq_p, sk_p, causal, fold, run, tiles_all, dv, *layout)
    plan = plan._replace(
        backward=_backward(plan, jnp.dtype(dtype).itemsize), window=window,
        rot=rot, group=group)
    return plan._replace(tiles_written=_written(plan, not q_offset)[1])


def _record_plan(p: FlashPlan, h: int, q_offset: bool = False):
    """One span in the program's ring for each attention traced: which
    plan the call got, and how far the causal skip engages (under a traced
    ``q_offset`` the count is the last piece's, the most a call walks)."""
    from ..core import profiler

    profiler.record_span(
        "flash.plan", time.time_ns(), 0, sq=p.sq, sk=p.sk, d=p.d, dv=p.dv,
        block_q=p.block_q, block_k=p.block_k, tile_q=p.tile_q,
        tile_k=p.tile_k, heads=p.heads, causal=p.causal,
        tiles_run=p.tiles_run, tiles_all=p.tiles_all, layout=p.layout,
        lane_heads=p.lane_heads, backward=p.backward, window=p.window,
        rot=p.rot, kv_heads=h // p.group, q_offset=q_offset,
        tiles_written=p.tiles_written)


# ---------------------------------------------------------------------------
# what the kernels share


class _Walk(NamedTuple):
    """The static half of a kernel: the plan plus what the call masks."""
    plan: FlashPlan
    scale: float
    offset: int       # sk - sq: causal is bottom-right aligned; or the
                      # call's ``q_offset``, read from SMEM inside a kernel
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
        loops are traced (the backward kernels know no other form)."""
        return self.written == "all"

    @property
    def written(self):
        """The form of the forward walk (:func:`_written`): ``all``,
        ``blocks``, ``interior`` or ``""``. The offset is static where it
        is the walk's own and not read from SMEM."""
        return _written(self.plan, isinstance(self.offset, int))[0]

    @property
    def packed(self):
        return self.plan.layout == "bsd"

    def kv(self, g):
        """The head of the step's ``k`` and ``v`` blocks that query head
        ``g`` of the step reads (grouped heads: ``g // group``; a step
        within one group holds that group's one key head)."""
        return g if self.plan.group == 1 else g // self.plan.group

    def head(self, g, rows):
        """Where head ``g`` of the step has ``rows`` in a block of q, k,
        v, o or their gradients: its own leading index of a
        ``(heads, rows, d)`` block, or its lanes of a ``(1, rows,
        heads * d)`` one (``g`` is then a python int: a lane offset is
        static)."""
        if self.packed:
            return (0, rows, pl.ds(g * self.plan.d, self.plan.d))
        return (g, rows, slice(None))

    @property
    def shared_lanes(self):
        """Do several heads share a 128-lane group (64-wide heads: two)?
        A head is then picked out of the group's lanes where it lies, not
        brought to lane 0 first: in a product that contracts over lanes
        (scores, dp) by zeros in the other heads' lanes of the operand a
        walk holds still (:meth:`still`, once a tile) against the whole
        group of the one that streams (:meth:`moving`); in dk / dv, whose
        lanes are the streaming operand's, by keeping a group's worth and
        writing the head's (:meth:`own_lanes`). Only a product that
        contracts over rows has to cut the lanes out (:meth:`rows_dot`).
        Measured against lane slices of every operand: PERF.md §6, PR 32."""
        return self.packed and self.plan.lane_heads > 1

    def lanes(self, g):
        """(first lane, width) of head ``g`` inside its 128-lane group."""
        return g % self.plan.lane_heads * self.plan.d, self.plan.d

    def moving(self, ref, g, rows):
        """``rows`` of the operand that streams past the still one: head
        ``g``'s own lanes, or its whole lane group where heads share one
        (the still operand's zeros keep the others out of a product that
        contracts over lanes; a product's other lanes are dropped)."""
        if not self.shared_lanes:
            return ref[self.head(g, rows)]
        return ref[0, rows, pl.ds(g // self.plan.lane_heads * 128, 128)]

    def still(self, ref, g, rows):
        """``rows`` of the operand a walk holds still, for head ``g``:
        its lanes, the others' zeroed where heads share a group."""
        x = self.moving(ref, g, rows)
        if self.shared_lanes:
            lo, width = self.lanes(g)
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            x = jnp.where((lane >= lo) & (lane < lo + width), x,
                          jnp.zeros_like(x))
        return x

    def rows_dot(self, x, y, g):
        """``x.T @ y`` for head ``g``: ``[d, n]`` from the moving operand
        ``x [rows, lanes]`` and ``y [rows, n]``. A product that contracts
        over rows cannot mask lanes out, so the head's lanes of a shared
        group are cut out here (a lane rotate for all but the first)."""
        return jax.lax.dot_general(self.own_lanes(x, g), y, _TN,
                                   preferred_element_type=jnp.float32)

    def own_lanes(self, x, g):
        """Head ``g``'s lanes of a value as wide as :meth:`moving`'s."""
        if not self.shared_lanes:
            return x
        lo, width = self.lanes(g)
        return x[:, lo:lo + width]

    def acc_at(self, g, t):
        """Where head ``g`` keeps its ``[d, tile]`` accumulator of still
        tile ``t`` in a scratch of :meth:`acc_shape`."""
        if not self.shared_lanes:
            return (g, t)
        n, d = self.plan.lane_heads, self.plan.d
        return (g // n, t, pl.ds(g % n * d, d))

    def acc_shape(self, tiles, tile):
        """Scratch for a ``[d, tile]`` accumulator a head and still tile.
        Heads that share a lane group lie one under another, ``[128,
        tile]`` a group: the group's result is then transposed and
        written whole (:meth:`write_groups`), dense over its 128 lanes,
        where a head's own would be rotated to its lanes and written
        under a mask."""
        p = self.plan
        if not self.shared_lanes:
            return (p.heads, tiles, p.dv, tile)
        return (p.heads // p.lane_heads, tiles, 128, tile)

    def write_groups(self, scr, ref, tiles, tile, scale=None):
        """Every lane group's ``[128, tile]`` results in ``scr`` (times
        ``scale``), transposed, to their rows and lanes of ``ref``."""
        for grp in range(self.plan.heads // self.plan.lane_heads):
            for t in range(tiles):
                x = scr[grp, t] if scale is None else scr[grp, t] * scale
                ref[0, pl.ds(t * tile, tile), pl.ds(grp * 128, 128)] = (
                    x.T.astype(ref.dtype))

    def mask_row(self, g):
        """Head ``g``'s leading index in a block of key bias or segment
        ids: they are a batch row's, which a packed step has one of."""
        return 0 if self.packed else g

    @property
    def rot_group(self):
        """Heads a 128-lane group of ``q_rot`` holds where that is more
        than the step's: the step's block is then the whole group and the
        grid index says which part is its own (:meth:`rot_still`). Else 0:
        the step's heads start a group and a head's lanes are static."""
        n = _rot_group(self.plan.rot)
        return 0 if self.plan.heads % n == 0 else n

    def rot_still(self, ref, g, rows, step):
        """Head ``g``'s ``rows`` of the step's block of ``q_rot``, ``[rows,
        rot]``, cut out of its lane group once a query tile to stand
        beside the head's ``q``. ``step``: the grid's first index, asked
        for only under :attr:`rot_group`."""
        r, n = self.plan.rot, self.rot_group
        if not n:
            return ref[0, rows, pl.ds(g * r, r)]
        group = ref[0, rows, :]      # two heads' parts: n is 2
        part = (step * self.plan.heads + g) % n
        first = group[:, :r]
        return jnp.where(part == 1, group[:, r:], first)


def _scores(keys, queries, r0, c0, w: _Walk, *, masked, bias_col, segq_row,
            segk_col):
    """One tile of scores, transposed: ``[tile_k, tile_q]`` f32 from
    ``keys [tile_k, d]`` and ``queries [tile_q, d]`` in their input dtype
    (bf16 in: one MXU-native pass with f32 accumulation). One definition
    for every kernel, so they can never desynchronize. ``masked``
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
            if w.plan.window:
                keep &= r + w.offset - w.plan.window < c
        if w.plan.sk != w.plan.sk_p:
            pad = c < w.plan.sk
            keep = pad if keep is None else keep & pad
    if segq_row is not None:
        same = segk_col == segq_row
        keep = same if keep is None else keep & same
    return s if keep is None else jnp.where(keep, s, NEG_INF)


def _fold_scale(x, w: _Walk):
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
    traced bounds but for the forward's key blocks that :func:`_written`
    finds."""
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
    runs = kb * w.plan.block_k < (qb + 1) * w.plan.block_q + w.offset
    if w.plan.window:   # the block's last key is in the first row's window
        runs &= ((kb + 1) * w.plan.block_k
                 > qb * w.plan.block_q + w.offset - w.plan.window + 1)
    return runs


def _kj_clamp(causal, block_q, block_k, nk, offset, window=0):
    """Index clamp for K/V-side blocks in causal kernels: grid steps
    past a q block's last useful key block keep requesting the SAME
    block index, and Pallas's pipelining skips the HBM→VMEM DMA when the
    index does not change — the compute for those steps is already
    gated off, so without this the skipped upper-triangle blocks still
    paid their K/V fetch bandwidth. Last useful kj for q block qi:
    floor(((qi+1)·bq + offset − 1)/bk), clamped to [0, nk−1]. Under a
    ``window`` the steps before the first useful key block, the one that
    holds key qi·bq + offset − window + 1, request that block."""
    if not causal:
        return lambda kk, j: kk

    def clamp(kk, j):
        last = ((j + 1) * block_q + offset - 1) // block_k
        kk = jnp.minimum(kk, jnp.clip(last, 0, nk - 1))
        if window:
            first = (j * block_q + offset - window + 1) // block_k
            kk = jnp.maximum(kk, jnp.clip(first, 0, nk - 1))
        return kk
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
    rot: tuple = ()     # (q_rot, k_rot) where the call has the pair


def _kernel_form(x, s_p, p: FlashPlan):
    """q, k, v, dO as the kernels take them: the sequence padded to the
    plan; ``[b, h, s, d]`` flattened to ``(bh, s, d)``, ``[b, s, h * d]``
    as it is."""
    if p.layout == "bsd":
        return _pad_seq(x, s_p, 1)
    x = _pad_seq(x, s_p, 2)
    return x.reshape((-1,) + x.shape[2:])


def _user_form(x, s, like, p: FlashPlan):
    """A kernel's o, dq, dk or dv in the layout of the operand ``like``,
    the padded rows cut off."""
    if p.layout == "bsd":
        return x[:, :s]
    return x.reshape(like.shape[:2] + x.shape[1:])[:, :, :s]


def _prepare(q, k, v, bias, seg_q, seg_k, p: FlashPlan, b, h, rot=()):
    """Pad the sequence axes to the plan and put the operands in kernel
    form. Padded keys are masked by their index inside the kernels (no
    bias is invented); padded q/k segment ids get distinct negative ids
    so they never match. A ``bhsd`` step's heads may be of several batch
    rows, so each head gets its own copy of a batch row's bias and ids;
    a ``bsd`` step reads the one of its batch row."""
    nq, nk = p.sq_p // p.block_q, p.sk_p // p.block_k
    nqt, nkt = p.block_q // p.tile_q, p.block_k // p.tile_k
    if k is q:
        # q, k and v fused in one array (:func:`_planned`): rows for the
        # blocks of both axes (queries pad to 8 rows, keys to 16), zeros
        # beyond the sequence as every padded key and value is
        q = k = v = _kernel_form(q, max(p.sq_p, p.sk_p), p)
    else:
        q = _kernel_form(q, p.sq_p, p)
        k, v = (_kernel_form(x, p.sk_p, p) for x in (k, v))

    def per_head(x, s_p, value, dtype):
        x = _pad_seq(x.astype(dtype), s_p, 1, value)
        if p.layout == "bsd":
            return x
        return jnp.broadcast_to(x[:, None, :], (b, h, s_p))

    lead = b if p.layout == "bsd" else b * h
    if bias is not None:
        bias = _rows(per_head(bias, p.sk_p, 0.0, jnp.float32),
                     lead, nk, nkt, p.tile_k)
    if seg_q is not None:
        seg_q = _rows(per_head(seg_q, p.sq_p, -1, jnp.int32),
                      lead, nq, nqt, p.tile_q)
        seg_k = _rows(per_head(seg_k, p.sk_p, -2, jnp.int32),
                      lead, nk, nkt, p.tile_k)
    rot = tuple(_kernel_form(x, s_p, p)
                for x, s_p in zip(rot, (p.sq_p, p.sk_p)))
    return _Operands(q, k, v, bias, seg_q, seg_k, rot)


def _mask_specs(ops: _Operands, p: FlashPlan, q_map, k_map):
    """BlockSpecs and arrays of the optional mask operands, in the order
    the kernels take them: bias, segq, segk."""
    g = 1 if p.layout == "bsd" else p.heads
    nqt, nkt = p.block_q // p.tile_q, p.block_k // p.tile_k
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
    loops. A packed step's heads are always python iterations (the plan
    keeps them few): a head is picked by its lanes."""
    def head(g):
        def tile(t, c):
            tile_body(g, t)
            return c
        if w.written_out:
            _loop(0, n_tiles, tile, 0)
        else:
            jax.lax.fori_loop(0, n_tiles, tile, 0)

    _over_heads(head, w, w.written_out
                and w.plan.heads * w.nqt * w.nkt <= UNROLL)


def _over_heads(head_body, w: _Walk, written=False):
    """Run ``head_body(g)`` for every head ``g`` of the step: python
    iterations for a packed step and for a ``written`` one, else a traced
    loop."""
    if w.packed or written:
        for g in range(w.plan.heads):
            head_body(g)
    else:
        jax.lax.fori_loop(0, w.plan.heads,
                          lambda g, carry: (head_body(g), carry)[1], 0)


class _QTile(NamedTuple):
    """A query tile of the forward walk as it meets one key block: python
    ints where the walk is written out, traced scalars in a loop."""
    g: object       # head of the step
    qt: object      # tile of the q block
    kb: object      # key block
    q0: object      # the tile's first row in the q block ...
    r0: object      # ... and among the call's queries
    mg: object      # the head's row of the mask operands
    q: jax.Array    # the still operand, the scale folded in
    segq: Optional[jax.Array]


def _fwd_kernel(*refs, w: _Walk):
    p = w.plan
    (q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref, rot_refs,
     (o_ref, lse_ref), (m_scr, l_scr, acc_scr, *rest)) = _split_refs(
         refs, w, 2 if p.rot else 0, 2)
    (qrot_ref, krot_ref), (keys_scr,) = (rot_refs, rest) if p.rot else (
        (None, None), (None,))
    qb, kv = _grid_index(1, w.nq), _grid_index(2, w.nk)
    last_kv = w.nk - 1
    tq, tk = p.tile_q, p.tile_k

    @pl.when(kv == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    rot_step = pl.program_id(0) if p.rot and w.rot_group else None

    def tile_of(g, qt, kb):
        """Query tile ``qt`` of head ``g`` as it meets key block ``kb``
        (``kv``, or the python int it is known to be)."""
        q0 = _at(qt, tq)
        r0, mg = qb * p.block_q + q0, w.mask_row(g)
        q = w.still(q_ref, g, pl.ds(q0, tq))
        if p.rot:       # the tile's two score operands side by side, in VMEM
            q = jnp.concatenate(
                [q, w.rot_still(qrot_ref, g, pl.ds(q0, tq), rot_step)],
                axis=1)
        return _QTile(g, qt, kb, q0, r0, mg, _fold_scale(q, w),
                      segq_ref[mg, 0, pl.ds(qt, 1), :] if w.have_seg else None)

    def to_diagonal(t):
        """``(n_plain, n_end)`` of the block's key chunks for tile ``t``."""
        return _chunk_bounds(t.r0, tq, t.kb * p.block_k, w.nkt, tk,
                             causal=p.causal, offset=w.offset, sk=p.sk,
                             sk_p=p.sk_p)

    def in_window(t, plain_end):
        """``(n_start, n_lead, n_plain, n_end)`` under the call's window."""
        return _window_bounds(t.r0, tq, t.kb * p.block_k, w.nkt, tk, plain_end,
                              offset=w.offset, window=p.window)

    def values(t, k0):
        return w.moving(v_ref, w.kv(t.g), pl.ds(k0, tk))

    def scores(t, j, k0, masked):
        """Tile ``t``'s scores on key chunk ``j`` of the block, rows ``k0 ..``."""
        return _scores(
            (keys_scr[t.g, pl.ds(k0, tk), :] if p.rot
             else w.moving(k_ref, w.kv(t.g), pl.ds(k0, tk))),
            t.q, t.r0, t.kb * p.block_k + k0, w, masked=masked,
            bias_col=_col(bias_ref, t.mg, j) if w.have_bias else None,
            segq_row=t.segq,
            segk_col=_col(segk_ref, t.mg, j) if w.have_seg else None)

    def reduced(t, s, vb, carry):
        """The running maximum, sum and output of tile ``t`` with one more
        chunk's scores ``s`` and values ``vb`` in them."""
        m, l, acc = carry            # [1, tq], [1, tq], [d, tq]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        # a query every key so far is masked for keeps m at NEG_INF: its
        # exp(s - m) would be exp(0) = 1, so the exponent's max is held
        # above the masked scores
        prob = jnp.exp(s - jnp.maximum(m_new, NEG_INF / 2))
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(prob, axis=0, keepdims=True)
        # prob rounded to the input dtype for the MXU pass; accumulator
        # f32. v.T @ prob.T = (prob @ v).T
        acc = acc * alpha + w.rows_dot(vb, prob.astype(vb.dtype), t.g)
        return m_new, l, acc

    def held(t):
        row = (t.g, pl.ds(t.qt, 1))
        return m_scr[row], l_scr[row], acc_scr[w.acc_at(t.g, t.qt)]

    def keep(t, carry):
        """Tile ``t``'s state back in scratch; after the last key block,
        its rows of the result."""
        m, l, acc = carry
        row, at = (t.g, pl.ds(t.qt, 1)), w.acc_at(t.g, t.qt)
        m_scr[row], l_scr[row], acc_scr[at] = m, l, acc

        @pl.when(t.kb == last_kv)
        def _finalize():
            l_safe = jnp.maximum(l, 1e-30)
            out = acc * (1.0 / l_safe)
            if w.shared_lanes:      # written with its lane group, below
                acc_scr[at] = out
            else:
                o_ref[w.head(t.g, pl.ds(t.q0, tq))] = out.T.astype(o_ref.dtype)
            lse_ref[t.g, 0, pl.ds(t.qt, 1), :] = m + jnp.log(l_safe)

    def q_tile(g, qt):
        """One query tile past the block's chunks, a chunk at a time: the
        traced walk, and the resident one that is written out whole."""
        t = tile_of(g, qt, kv)
        n_plain, n_end = to_diagonal(t)

        def chunk(masked):
            def body(j, carry):
                k0 = _at(j, tk)
                vb = values(t, k0)
                return reduced(t, scores(t, j, k0, masked), vb, carry)
            return body

        carry = held(t)
        if p.window:
            n_start, n_lead, n_plain, n_end = in_window(t, (n_plain, n_end))
            carry = _loop(n_start, n_lead, chunk(True), carry)
            carry = _loop(n_lead, n_plain, chunk(False), carry)
            carry = _loop(n_plain, n_end, chunk(True), carry)
        else:
            carry = _two_loops(n_plain, n_end, chunk, carry, w)
        keep(t, carry)

    def head_ahead(g, kb, inside):
        """Head ``g``'s walk of a streamed block whose bounds are python
        ints (``inside``: the block is interior, every chunk of it plain),
        written out, with every tile's scores made one tile ahead: the
        compiler keeps near the order it is given, and a tile's score
        product next to its neighbour's softmax is what lets the MXU run
        under the vector unit's stores (written out in the loops' order
        the window's call takes 14.4 ms, so 12.4: PERF.md section 6, PR
        46). The same tiles meet each carry in the same order."""
        walk = []
        for t in (tile_of(g, qt, kb) for qt in range(w.nqt)):
            n_start, n_lead, n_plain, n_end = 0, 0, w.nkt, w.nkt
            if not inside:
                n_plain, n_end = to_diagonal(t)
                if p.window:
                    n_start, n_lead, n_plain, n_end = in_window(
                        t, (n_plain, n_end))
            if n_start == n_end and kb == last_kv:  # only its result's write
                keep(t, held(t))
            walk += [(t, j, _at(j, tk), not n_lead <= j < n_plain)
                     for j in range(n_start, n_end)]
        s_next = scores(*walk[0]) if walk else None
        for i, (t, _, k0, _) in enumerate(walk):
            s, last = s_next, i + 1 == len(walk)
            if not last:
                s_next = scores(*walk[i + 1])
            if i == 0 or walk[i - 1][0] is not t:
                carry = held(t)
            carry = reduced(t, s, values(t, k0), carry)
            if last or walk[i + 1][0] is not t:
                keep(t, carry)

    def step(kb=kv, inside=False, ahead=False):
        if p.rot:
            # each head's keys with the batch row's one k_rot beside them,
            # once a step: a tile's scores are then one product over d +
            # rot, the sum of the two parts formed in the MXU's float32
            # accumulator as a [.., d + rot] operand in HBM had it formed
            for g in range(p.heads):
                keys_scr[g, :, pl.ds(0, p.d)] = k_ref[w.head(g, slice(None))]
                keys_scr[g, :, pl.ds(p.d, p.rot)] = krot_ref[0]
        if ahead:
            _over_heads(lambda g: head_ahead(g, kb, inside), w)
        else:
            _over_tiles(w.nqt, q_tile, w)
        if w.shared_lanes:
            pl.when(kb == last_kv)(
                lambda: w.write_groups(acc_scr, o_ref, w.nqt, tq))

    # causal: a key block strictly above the (offset) diagonal is only
    # entered to write the result, if it is the last
    runs, form = _block_runs(qb, kv, w) | (kv == last_kv), w.written
    if form == "blocks":
        # one q block under a static offset: what each key block's tiles
        # do is known now. The interior blocks share a body, every other
        # block that runs has its own, its bounds python ints
        inside = [kb for kb in range(w.nk) if _interior(0, kb, p, w.offset)]
        if inside:
            pl.when(functools.reduce(operator.or_, (kv == kb for kb in inside)))(
                functools.partial(step, kv, True, True))
        for kb in range(w.nk):
            if kb not in inside and (_block_runs(0, kb, w) or kb == last_kv):
                pl.when(kv == kb)(functools.partial(step, kb, False, True))
    elif form == "interior":
        # the diagonal lies where traced scalars say: the blocks every
        # query of the step sees whole run written out, the others traced
        inside = _interior(qb, kv, p, w.offset)
        if inside is True:      # nothing masks a key
            step(kv, True, True)
        else:
            pl.when(inside)(functools.partial(step, kv, True, True))
            pl.when(jnp.logical_not(inside) & runs)(step)
    else:
        pl.when(runs)(step)


class _Specs(NamedTuple):
    """The BlockSpecs of one kernel's grid."""
    q: pl.BlockSpec       # block_q rows of the step's heads
    k: pl.BlockSpec
    v: pl.BlockSpec
    o: pl.BlockSpec       # o; dO and dq, which are as wide as q
    dk: pl.BlockSpec      # dk, dv
    qrow: pl.BlockSpec    # lse, delta: a row a head
    masks: list           # specs and arrays of bias, segq, segk
    mask_args: list
    rot: list             # specs of q_rot and k_rot, or none


def _specs(ops: _Operands, p: FlashPlan, w: _Walk, h, dkv=False):
    """BlockSpecs over a (b * h / heads, nq, nk) grid (forward and dq),
    or (b * h / heads, nk, nq) (dk/dv): grid step ``i`` holds ``heads``
    consecutive heads. The q side follows ``j`` and the k side the
    causally clamped ``kk`` (:func:`_kj_clamp`; mirrored for dk/dv,
    :func:`_qi_clamp`). ``bhsd`` operands are ``(bh, s, d)`` and step
    ``i`` is their block ``i``; ``bsd`` operands are ``[b, s, h * d]``
    and step ``i`` is batch row ``i // per_row``, lane block
    ``i % per_row``; where q, k and v are one fused ``[b, s, 3 * h * d]``
    array (a projection's output, passed three times), k's and v's blocks
    lie ``per_row`` and ``2 * per_row`` lane blocks further on. The
    per-query rows (lse, delta) are a head's in both layouts. A rotary
    pair's ``q_rot [b, s, h * rot]`` goes by whole lane groups, the one a
    step's heads start or, where they are fewer than a group's, lie in;
    ``k_rot [b, s, rot]`` is a batch row's and stays while its heads walk
    past (a block whose index does not change is not fetched again)."""
    g, packed = p.heads, p.layout == "bsd"
    per_row = h // g
    fused = packed and ops.q.shape[-1] == 3 * h * p.d
    # ``s``: the scalar-prefetch refs of a call with a traced ``q_offset``
    # (the offset is then theirs, not the walk's), else nothing
    if dkv:
        cq = _qi_clamp(p.causal, p.block_q, p.block_k, w.nq, w.offset)
        q_at, k_at = (lambda j, kk, *s: cq(kk, j)), (lambda j, kk, *s: j)
    else:
        def k_at(j, kk, *s):
            return _kj_clamp(p.causal, p.block_q, p.block_k, w.nk,
                             s[0][0] if s else w.offset, p.window)(kk, j)

        q_at = lambda j, kk, *s: j
    # grouped heads: the step's block of k and v holds the ``gk`` key heads
    # its ``g`` query heads read, block ``step * g // (group * gk)``
    gk = max(g // p.group, 1)

    def block(rows, width, at, nth=0, kv=False):
        n = gk if kv else g
        first = (lambda step: step * g // (p.group * gk)) if (
            kv and p.group > 1) else (lambda step: step)
        if packed:
            off = nth * per_row if fused else 0
            return pl.BlockSpec(
                (1, rows, n * width),
                lambda i, j, kk, *s: (i // per_row, at(j, kk, *s),
                                      first(i % per_row) + off))
        return pl.BlockSpec((n, rows, width),
                            lambda i, j, kk, *s: (first(i), at(j, kk, *s), 0))

    def mask(at):
        if packed:
            return lambda i, j, kk, *s: (i // per_row, at(j, kk, *s), 0, 0)
        return lambda i, j, kk, *s: (i, at(j, kk, *s), 0, 0)

    masks, mask_args = _mask_specs(ops, p, mask(q_at), mask(k_at))
    rot = []
    if p.rot:
        span = max(g, _rot_group(p.rot))    # heads the q_rot block holds
        rot = [pl.BlockSpec((1, p.block_q, span * p.rot),
                            lambda i, j, kk: (i // per_row, q_at(j, kk),
                                              i % per_row * g // span)),
               pl.BlockSpec((1, p.block_k, p.rot),
                            lambda i, j, kk: (i // per_row, k_at(j, kk), 0))]
    return _Specs(
        q=block(p.block_q, p.d, q_at), k=block(p.block_k, p.d, k_at, 1, True),
        v=block(p.block_k, p.dv, k_at, 2, True),
        o=block(p.block_q, p.dv, q_at), dk=block(p.block_k, p.d, k_at),
        qrow=pl.BlockSpec((g, 1, w.nqt, p.tile_q),
                          lambda i, j, kk, *s: (i, q_at(j, kk, *s), 0, 0)),
        masks=masks, mask_args=mask_args, rot=rot)


def _planned(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
             scale=None, num_heads=None, window=0, rot=(), kv_heads=None,
             q_offset=False):
    """(plan, walk, operands, b, h) of one call: ``[b, h, s, d]`` operands,
    ``[b, s, num_heads * d]`` ones that :func:`lane_heads` admits, or,
    with ``k`` and ``v`` None, ``q`` as the three of them fused,
    ``[b, s, 3 * num_heads * d]``; ``rot``: the ``(q_rot, k_rot)`` of a
    ``[b, s, num_heads * d]`` call that :func:`rot_lane_heads` admits;
    ``kv_heads``: the heads ``k`` and ``v`` hold where they are fewer than
    ``q``'s (a rank-4 call's are read off ``k``); ``q_offset``: a traced
    scalar places the diagonal."""
    if num_heads is None:
        b, h, sq, d = q.shape
        sk, dv, kv_heads = k.shape[2], v.shape[-1], k.shape[1]
    else:
        (b, sq, width), h = q.shape, num_heads
        if k is None:
            k, v, width = q, q, width // 3
        sk, d = k.shape[1], width // h
        dv = d
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    p = plan_blocks(sq, sk, d, q.dtype, causal, bias is not None,
                    seg_q is not None, block_q, block_k, bh=b * h,
                    dv=dv, scale=scale, num_heads=num_heads, window=window,
                    rot=rot[1].shape[-1] if rot else 0,
                    group=h // (kv_heads or h), q_offset=q_offset)
    w = _Walk(p, scale, sk - sq, bias is not None, seg_q is not None)
    return p, w, _prepare(q, k, v, bias, seg_q, seg_k, p, b, h, rot), b, h


@functools.partial(jax.jit, static_argnames=("w", "h", "interpret"),
                   inline=True)
def _fwd_call(ops: _Operands, q_offset, *, w: _Walk, h: int, interpret: bool):
    """The forward kernel over a call's prepared operands: ``(out, lse)`` in
    kernel form. Jitted so that jit's cache remembers the trace: the kernel
    body is python that writes tiles out (28 bodies for a window's piece),
    ``pallas_call`` traces it anew whenever it is reached, a generator
    reaches it once a layer, and a program is traced in several passes
    before it runs (export, lowering, warm-up). Calls with equal operand
    shapes and an equal walk share one traced kernel within a trace and
    across the passes of a process; ``inline`` puts the remembered
    equations where the call stands, so the program that is lowered holds
    the ``pallas_call`` as if it had been traced there, under the caller's
    scopes. ``q_offset``: the int32 ``[1]`` a traced diagonal is read from,
    or None."""
    p = w.plan
    lead, g = ops.q.shape[0], p.heads   # batch rows (``bsd``), or b * h
    bh = lead * h if w.packed else lead
    sp = _specs(ops, p, w, h)
    grid = dict(
        grid=(bh // g, w.nq, w.nk),
        in_specs=[sp.q, sp.k, sp.v] + sp.masks + sp.rot,
        out_specs=[sp.o, sp.qrow],
        scratch_shapes=[pltpu.VMEM((g, w.nqt, p.tile_q), jnp.float32),
                        pltpu.VMEM((g, w.nqt, p.tile_q), jnp.float32),
                        pltpu.VMEM(w.acc_shape(w.nqt, p.tile_q), jnp.float32)]
        + ([pltpu.VMEM((g, p.block_k, p.d + p.rot), ops.q.dtype)]
           if p.rot else []))
    kernel, prefetch = functools.partial(_fwd_kernel, w=w), ()
    if q_offset is not None:
        def kernel(off_ref, *refs):
            _fwd_kernel(*refs, w=w._replace(offset=off_ref[0]))

        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **grid))
        prefetch = (q_offset,)
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        out_shape=[jax.ShapeDtypeStruct(
                       (lead, p.sq_p, (h if w.packed else 1) * p.dv),
                       ops.q.dtype),
                   jax.ShapeDtypeStruct((bh, w.nq, w.nqt, p.tile_q),
                                        jnp.float32)],
        interpret=interpret,
        **grid,
    )(*prefetch, ops.q, ops.k, ops.v, *sp.mask_args, *ops.rot)


def _flash_fwd(q, k, v, bias, seg_q, seg_k, causal: bool,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, scale: Optional[float] = None,
               num_heads: Optional[int] = None, window: int = 0, rot=(),
               kv_heads: Optional[int] = None, q_offset=None):
    """``q`` and ``k`` are ``[b, h, s, d]`` and ``v`` ``[b, h, s_k, dv]``:
    the scores contract over ``d``, the output rows are ``dv`` wide. With
    ``num_heads`` they are ``[b, s, num_heads * d]`` (or fused in ``q``,
    :func:`_planned`) and so is the output; lse is ``[b, h, s_q]`` for
    both. ``rot``: such a call's ``(q_rot [b, s, num_heads * rot], k_rot
    [b, s_k, rot])``, the scores' further ``rot`` columns. ``kv_heads``:
    ``k`` and ``v`` are ``[b, s_k, kv_heads * d]`` (rank-4: ``[b, kv_heads,
    s_k, d]``, read off them). ``q_offset`` (a traced int32 scalar): the
    key index of the first query's own key, where that is not ``s_k -
    s_q``; it reaches the kernel and its index maps through SMEM. A plan
    is made and recorded at every call; the kernel is traced once a walk
    (:func:`_fwd_call`)."""
    p, w, ops, b, h = _planned(q, k, v, bias, seg_q, seg_k, causal, block_q,
                               block_k, scale, num_heads, window, rot,
                               kv_heads, q_offset is not None)
    _record_plan(p, h, q_offset is not None)
    if q_offset is not None:
        q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)
    out, lse = _fwd_call(ops, q_offset, w=w, h=h, interpret=interpret)
    out = _user_form(out, p.sq, q, p)
    lse = lse.reshape(b, h, p.sq_p)[:, :, :p.sq]
    return out, lse


# ---------------------------------------------------------------------------
# backward (flash-attention-2 style: one pallas pass where both sequences
# are a grid step's, two where one streams; ``FlashPlan.backward``)


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
        mg = w.mask_row(g)
        q = _fold_scale(w.still(q_ref, g, pl.ds(q0, tq)), w)
        do = w.still(g_ref, g, pl.ds(q0, tq))
        lse, delta = lse_ref[row], delta_ref[row]
        segq = segq_ref[mg, 0, pl.ds(qt, 1)] if w.have_seg else None
        n_plain, n_end = _chunk_bounds(r0, tq, c_base, w.nkt, tk,
                                       causal=p.causal, offset=w.offset,
                                       sk=p.sk, sk_p=p.sk_p)

        def chunk(masked):
            def body(j, dq_t):               # [d, tq]
                k0 = _at(j, tk)
                kb = w.moving(k_ref, g, pl.ds(k0, tk))
                vb = w.moving(v_ref, g, pl.ds(k0, tk))
                s = _scores(
                    kb, q, r0, c_base + k0, w, masked=masked,
                    bias_col=_col(bias_ref, mg, j) if w.have_bias else None,
                    segq_row=segq,
                    segk_col=_col(segk_ref, mg, j) if w.have_seg else None)
                dp = jax.lax.dot_general(vb, do, _NT,
                                         preferred_element_type=jnp.float32)
                ds = _probs(s, lse) * (dp - delta)
                # k.T @ ds.T = (ds @ k).T; ds rounded to the input dtype
                return dq_t + w.rows_dot(kb, ds.astype(kb.dtype), g)
            return body

        at = w.acc_at(g, qt)
        dq_t = _two_loops(n_plain, n_end, chunk, dq_scr[at], w)
        dq_scr[at] = dq_t

        if not w.shared_lanes:      # else written with its lane group, below
            @pl.when(kv == last_kv)
            def _finalize():
                dq_ref[w.head(g, pl.ds(q0, tq))] = (dq_t * w.scale).T.astype(
                    dq_ref.dtype)

    @pl.when(_block_runs(qb, kv, w) | (kv == last_kv))
    def _step():
        _over_tiles(w.nqt, q_tile, w)
        if w.shared_lanes:      # a lane group's heads written together
            pl.when(kv == last_kv)(
                lambda: w.write_groups(dq_scr, dq_ref, w.nqt, tq, w.scale))


def _dkv_kernel(*refs, w: _Walk):
    """dk and dv of a step's K/V block: a K/V tile held still, the q and
    dO chunks that see it walked past it. Where the plan's backward is
    ``fused`` the step holds every query too, so the chunk's ``ds`` also
    goes into dq, which the step keeps whole in scratch (``flash_bwd``:
    each tile's scores, probabilities and ``ds`` computed once); where a
    sequence streams, :func:`_dq_kernel` computes them again for dq."""
    fused = w.plan.backward == "fused"
    (q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
     (g_ref, lse_ref, delta_ref), outs, scr) = _split_refs(
         refs, w, 3, 3 if fused else 2)
    dq_ref, dk_ref, dv_ref = outs if fused else [None] + outs
    dq_scr, dk_scr, dv_scr = scr if fused else [None] + scr
    p = w.plan
    kb_i, qb = _grid_index(1, w.nk), _grid_index(2, w.nq)
    last_q = w.nq - 1
    tq, tk = p.tile_q, p.tile_k
    # the still K carries a folded scale into dq; else dq takes it here
    dq_scale = None if p.fold_scale else w.scale

    @pl.when(qb == 0)
    def _init():
        for ref in scr:
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    r_base = qb * p.block_q

    def k_tile(g, kt):
        k0 = _at(kt, tk)
        c0 = kb_i * p.block_k + k0
        rows = (g, pl.ds(k0, tk))
        at = w.head(*rows)
        mg = w.mask_row(g)
        ks = _fold_scale(w.still(k_ref, *rows), w)
        vb = w.still(v_ref, *rows)
        k_own = w.own_lanes(ks, g) if fused else None   # for dq, cut once
        bias_col = _col(bias_ref, mg, kt) if w.have_bias else None
        segk_col = _col(segk_ref, mg, kt) if w.have_seg else None
        # query chunks of this block, ascending: those before n_lo see
        # none of the tile's keys, [n_lo, n_plain) cross the diagonal,
        # [n_plain, nqt) see all of them. Padded keys need no mask for
        # dk / dv: they only fill their own (sliced-off) rows. dq sums
        # over keys, so there a tile that holds padded keys masks them
        # in every chunk.
        n_lo = n_plain = 0
        if p.causal:
            n_lo = _clip((c0 - w.offset - r_base) // tq, 0, w.nqt)
            n_plain = _clip(
                (c0 + tk - 1 - w.offset - r_base + tq - 1) // tq, 0, w.nqt)
        if fused and p.sk != p.sk_p:
            n_plain = _where(c0 + tk > p.sk, w.nqt, n_plain)

        def chunk(masked):
            def body(i, carry):
                dk, dv = carry               # [tk, d] each (or a lane group)
                q0 = _at(i, tq)
                row = (g, 0, pl.ds(i, 1))
                qc = w.moving(q_ref, g, pl.ds(q0, tq))
                do = w.moving(g_ref, g, pl.ds(q0, tq))
                s = _scores(ks, qc, r_base + q0, c0, w, masked=masked,
                            bias_col=bias_col,
                            segq_row=(segq_ref[mg, 0, pl.ds(i, 1)]
                                      if w.have_seg else None),
                            segk_col=segk_col)
                prob = _probs(s, lse_ref[row])
                dv = dv + jax.lax.dot_general(
                    prob.astype(do.dtype), do, _NN,
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(vb, do, _NT,
                                         preferred_element_type=jnp.float32)
                # ds rounded to the input dtype once, for both products
                ds = (prob * (dp - delta_ref[row])).astype(qc.dtype)
                dk = dk + jax.lax.dot_general(
                    ds, qc, _NN, preferred_element_type=jnp.float32)
                if fused:
                    # k.T @ ds = (ds.T @ k).T, [d, tq], into the head's
                    # rows of its lane group's accumulator
                    dq_at = w.acc_at(g, i)
                    dq_scr[dq_at] = dq_scr[dq_at] + jax.lax.dot_general(
                        k_own, ds, _TN, preferred_element_type=jnp.float32)
                return dk, dv
            return body

        carry = _loop(n_lo, n_plain, chunk(True), (dk_scr[rows], dv_scr[rows]))
        dk, dv = _loop(n_plain, w.nqt, chunk(False), carry)
        dk_scr[rows], dv_scr[rows] = dk, dv

        @pl.when(qb == last_q)
        def _finalize():
            dk_ref[at] = w.own_lanes(dk * w.scale, g).astype(dk_ref.dtype)
            dv_ref[at] = w.own_lanes(dv, g).astype(dv_ref.dtype)

    def dq_tile(g, qt):
        dq_t = dq_scr[g, qt] if dq_scale is None else dq_scr[g, qt] * dq_scale
        dq_ref[w.head(g, pl.ds(_at(qt, tq), tq))] = dq_t.T.astype(dq_ref.dtype)

    @pl.when(_block_runs(qb, kb_i, w) | (qb == last_q))
    def _step():
        _over_tiles(w.nkt, k_tile, w)
        if fused and w.shared_lanes:
            w.write_groups(dq_scr, dq_ref, w.nqt, tq, dq_scale)
        elif fused:
            _over_tiles(w.nqt, dq_tile, w)


def _head_sums(x, h, exact: bool):
    """Float32 ``[b, s, h * d]`` summed over each head's lanes: ``[b, h, s]``.
    On the MXU, against a 0/1 ``[h * d, h]`` operand: a reduction over
    ``d`` lanes of a 128-lane register has no other cheap form (the
    compiler answers the reshape to ``[b, s, h, d]`` with a transpose of
    the whole float32 array). A default-precision product on the chip
    rounds its operands to bfloat16, so ``x`` goes in as two terms, its
    rounding to bfloat16 and what the rounding left: 16 bits of every
    addend under float32 accumulation, which is all of a product of two
    bfloat16 numbers (8 bits by 8). ``exact``, for wider operands, whose
    products fill a float32: the product at the highest precision, the
    float32 sum a ``[b, h, s, d]`` call makes."""
    width = x.shape[-1]
    heads = (jnp.arange(width)[:, None] // (width // h)
             == jnp.arange(h)[None, :]).astype(jnp.float32)
    if exact:
        return jnp.einsum("bsc,ch->bhs", x, heads,
                          precision=jax.lax.Precision.HIGHEST)
    # reduce_precision, not a cast there and back, which the compiler may
    # drop as excess precision it is allowed to keep
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return sum(jnp.einsum("bsc,ch->bhs", t, heads) for t in (hi, x - hi))


def _flash_bwd(q, k, v, bias, seg_q, seg_k, causal, out, lse, g,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, delta=None, scale: Optional[float] = None,
               num_heads: Optional[int] = None):
    """dq, dk, dv in the operands' layout (``[b, h, s, d]``, or
    ``[b, s, num_heads * d]`` each, also where q, k and v came fused in
    ``q``); ``lse`` and ``delta`` are ``[b, h, s_q]``."""
    if num_heads is None and v.shape[-1] != q.shape[-1]:
        # the backward kernels hold dO, K and V in blocks of one width
        raise NotImplementedError(
            f"flash_attention: no backward pass for values {v.shape[-1]} "
            f"wide under scores that contract over {q.shape[-1]}; the "
            f"forward takes unequal widths, the backward kernels do not yet")
    if delta is None:
        delta = out.astype(jnp.float32) * g.astype(jnp.float32)
        delta = (jnp.sum(delta, axis=-1) if num_heads is None
                 else _head_sums(delta, num_heads,
                                 exact=jnp.dtype(out.dtype).itemsize > 2))
    p, w, ops, b, h = _planned(q, k, v, bias, seg_q, seg_k, causal, block_q,
                               block_k, scale, num_heads)
    bh, hg, nq, nk, d = b * h, p.heads, w.nq, w.nk, p.d

    # padded q rows: g/delta 0 and lse huge, so p=exp(s-lse)=0 — they
    # contribute nothing to dk/dv, and their dq rows are sliced off
    g_r = _kernel_form(g, p.sq_p, p)
    kv_shape = g_r.shape[:-2] + (p.sk_p, g_r.shape[-1])
    lse_r = _rows(_pad_seq(lse, p.sq_p, 2, -NEG_INF), bh, nq, w.nqt, p.tile_q)
    delta_r = _rows(_pad_seq(delta, p.sq_p, 2), bh, nq, w.nqt, p.tile_q)

    acc_lanes = 128 if w.shared_lanes else d    # dk, dv of a whole lane group
    kv_scratch = [pltpu.VMEM((hg, p.block_k, acc_lanes), jnp.float32)] * 2
    dq_scratch = [pltpu.VMEM(w.acc_shape(w.nqt, p.tile_q), jnp.float32)]

    def call(kernel, name, grid, sp, out_specs, out_shapes, scratch):
        return pl.pallas_call(
            functools.partial(kernel, w=w), name=name, grid=grid,
            in_specs=[sp.q, sp.k, sp.v] + sp.masks + [sp.o, sp.qrow, sp.qrow],
            out_specs=out_specs,
            out_shape=[jax.ShapeDtypeStruct(s, q.dtype) for s in out_shapes],
            scratch_shapes=scratch, interpret=interpret,
        )(ops.q, ops.k, ops.v, *sp.mask_args, g_r, lse_r, delta_r)

    # dk/dv: grid (bh/heads, nk, nq), Q/dO on the inner dim; causal steps
    # before a key block's first useful q block re-request that first
    # block (DMA skipped, see _qi_clamp). dq: grid (bh/heads, nq, nk), K/V
    # on the inner dim; steps past the diagonal re-request the same block
    # (see _kj_clamp). Where both sequences are one step's, the dk/dv
    # pass leaves dq as well and there is no other
    sp = _specs(ops, p, w, h, dkv=True)
    if p.backward == "fused":
        dq, dk, dv = call(_dkv_kernel, "flash_bwd", (bh // hg, 1, 1), sp,
                          [sp.o, sp.dk, sp.dk], [g_r.shape] + [kv_shape] * 2,
                          dq_scratch + kv_scratch)
    else:
        sp_dq = _specs(ops, p, w, h)
        dq, = call(_dq_kernel, "flash_dq", (bh // hg, nq, nk), sp_dq,
                   [sp_dq.o], [g_r.shape], dq_scratch)
        dk, dv = call(_dkv_kernel, "flash_dkv", (bh // hg, nk, nq), sp,
                      [sp.dk, sp.dk], [kv_shape] * 2, kv_scratch)

    return (_user_form(dq, p.sq, q, p), _user_form(dk, p.sk, q, p),
            _user_form(dv, p.sk, q, p))


# ---------------------------------------------------------------------------
# custom VJP plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
                interpret, scale=None, num_heads=None):
    out, _ = _flash_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q,
                        block_k, interpret, scale, num_heads)
    return out


def _flash_core_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
                    interpret, scale=None, num_heads=None):
    out, lse = _flash_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q,
                          block_k, interpret, scale, num_heads)
    return out, (q, k, v, bias, seg_q, seg_k, out, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, scale, num_heads,
                    res, g):
    q, k, v, bias, seg_q, seg_k, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, seg_q, seg_k, causal, out, lse, g,
                            block_q, block_k, interpret, scale=scale,
                            num_heads=num_heads)
    return dq, dk, dv, None, None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core_fused(qkv, bias, seg_q, seg_k, causal, block_q, block_k,
                      interpret, scale, num_heads):
    """Self-attention over a fused projection's output ``[b, s, 3 * h*d]``,
    q, k and v side by side: the kernels pick each out by its blocks'
    lane offset, so nothing slices it into three arrays first."""
    return _flash_core_fused_fwd(qkv, bias, seg_q, seg_k, causal, block_q,
                                 block_k, interpret, scale, num_heads)[0]


def _flash_core_fused_fwd(qkv, bias, seg_q, seg_k, causal, block_q, block_k,
                          interpret, scale, num_heads):
    out, lse = _flash_fwd(qkv, None, None, bias, seg_q, seg_k, causal,
                          block_q, block_k, interpret, scale, num_heads)
    return out, (qkv, bias, seg_q, seg_k, out, lse)


def _flash_core_fused_bwd(causal, block_q, block_k, interpret, scale,
                          num_heads, res, g):
    qkv, bias, seg_q, seg_k, out, lse = res
    grads = _flash_bwd(qkv, None, None, bias, seg_q, seg_k, causal, out, lse,
                       g, block_q, block_k, interpret, scale=scale,
                       num_heads=num_heads)
    # each gradient behind a barrier of its own: the compiler then fuses
    # the concatenation into the projection's three backward products, as
    # it did when dq and dk / dv came from two calls. Three results of one
    # call it lays into a ``[b, s, 3 * h*d]`` buffer first, by three
    # update-slice fusions (0.32 ms a layer of the one-chip train step)
    grads = [jax.lax.optimization_barrier(x) for x in grads]
    return jnp.concatenate(grads, axis=-1), None, None, None


_flash_core_fused.defvjp(_flash_core_fused_fwd, _flash_core_fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_core_rot(q, k, v, q_rot, k_rot, causal, block_q, block_k,
                    interpret, scale, num_heads):
    """The forward of a ``[b, s, num_heads * d]`` call whose scores run
    over the rotary pair as well. Forward only, as a window is: the
    backward kernels take no second score operand."""
    return _flash_fwd(q, k, v, None, None, None, causal, block_q, block_k,
                      interpret, scale, num_heads, rot=(q_rot, k_rot))[0]


def _flash_core_rot_fwd(*args):
    return _flash_core_rot.fun(*args), None


def _flash_core_rot_bwd(*args):
    raise NotImplementedError(
        "flash_attention: no backward pass for a call with a rotary pair "
        "(q_rot, k_rot); the forward adds its product to the scores, the "
        "backward kernels do not yet")


_flash_core_rot.defvjp(_flash_core_rot_fwd, _flash_core_rot_bwd)


def _split_heads(x, h):
    b, s, width = x.shape
    return x.reshape(b, s, h, width // h).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def flash_attention(
    q, k=None, v=None,
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
    num_heads: Optional[int] = None,
    window: int = 0,
    q_rot: Optional[jax.Array] = None,
    k_rot: Optional[jax.Array] = None,
    kv_heads: Optional[int] = None,
    q_offset=None,
):
    """Flash attention over ``q``, ``k`` [b, h, s, d] and ``v``
    [b, h, s_k, dv]; the output is [b, h, s_q, dv]. ``dv`` may differ
    from ``d`` in the forward pass (scores over 192, values 128 wide: what
    latent attention was before its parts came apart, ``q_rot`` below);
    the backward pass raises for unequal widths.

    Rank-3 ``q``, ``k``, ``v`` [b, s, num_heads * d], heads side by side
    as a projection leaves them, give the output in that layout too, and
    the gradients. Where :func:`lane_heads` admits the shape the kernels
    read and write it in place; else this call transposes to
    [b, h, s, d] and back, as its caller would have had to. A fused
    self-attention projection ``[b, s, 3 * num_heads * d]`` (q, k and v
    side by side, one matmul's output) goes in whole as ``q`` with ``k``
    and ``v`` left out: the kernels read its three parts where they lie
    (three arrays sliced out of it are three copies).

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
    - ``window``: with ``causal``, a query sees its own key and the
      ``window - 1`` before it (a sliding window; key tiles wholly behind
      it are skipped, :func:`plan_blocks`). 0 is no window. The forward
      only: no backward kernel takes one (ROADMAP Reach).
    - ``kv_heads``: grouped-query attention. ``k`` and ``v`` hold fewer
      heads than ``q``, ``[b, s_k, kv_heads * d]`` beside ``[b, s, num_heads
      * d]`` (rank-4 operands say it themselves: ``[b, kv_heads, s_k, d]``),
      and query head ``h`` reads head ``h // (num_heads // kv_heads)``. The
      kernel reads each key head's tiles where the cache holds them; no
      head is repeated in HBM. Where a rank-3 head is not its own lane group
      (``d`` no multiple of 128) the call goes through ``[b, h, s, d]``.
      The forward only, under at most ``causal``, a ``window`` and a key
      bias.
    - ``q_offset`` (with ``causal``; an int or a traced int32 scalar): the
      index among the keys of the first query's own key, where that is not
      ``s_k - s_q``: a later piece of a prompt against a cache it has
      written its keys into, ``k`` and ``v`` the cache whole. Keys past the
      last query's own are never read. The forward only.
    - ``q_rot`` [b, s_q, num_heads * r] and ``k_rot`` [b, s_k, r], with
      rank-3 ``q``, ``k``, ``v``: a second score operand. Head ``h``'s
      scores are ``q_h . k_h + q_rot_h . k_rot``, one sum over ``d + r``
      accumulated in float32, before the scale, the mask and the softmax;
      ``scale`` None is ``(d + r) ** -0.5``. ``k_rot`` has no head: every
      head's rotary part meets the same keys (latent attention's rotary 64
      beside its 128: no head of 192 is built and ``k_rot`` is copied to
      no head). Where :func:`rot_lane_heads` admits the shape the kernel
      reads the pair in place; else this call builds the ``[b, h, s, d +
      r]`` operands its caller would have had to. Forward only, with
      ``causal`` as the one mask.
    """
    from ..core.errors import enforce

    if q_rot is not None or k_rot is not None:
        enforce(q_rot is not None and k_rot is not None and q.ndim == 3
                and k is not None and num_heads is not None and not window
                and attn_mask is None and key_bias is None
                and segment_ids is None and not return_lse
                and q_rot.shape[-1] == num_heads * k_rot.shape[-1],
                "flash_attention: a rotary pair is q_rot [b, s, h * r] and "
                "k_rot [b, s_k, r] beside [b, s, h * d] q, k, v with "
                "num_heads, under at most the causal mask")
        d, dv, r = (q.shape[-1] // num_heads, v.shape[-1] // num_heads,
                    k_rot.shape[-1])
        scale = (d + r) ** -0.5 if scale is None else scale
        if not rot_lane_heads(d, dv, num_heads, r):
            q, k, v, q_rot = (_split_heads(x, num_heads)
                              for x in (q, k, v, q_rot))
            k_rot = jnp.broadcast_to(k_rot[:, None], k.shape[:3] + (r,))
            return _merge_heads(flash_attention(
                jnp.concatenate([q, q_rot], -1),
                jnp.concatenate([k, k_rot], -1), v, causal=causal,
                block_q=block_q, block_k=block_k, interpret=interpret,
                scale=scale))
        block_q, block_k = resolve_block_shapes(block_q, block_k)
        return _flash_core_rot(
            q, k, v, q_rot, k_rot, causal, block_q, block_k,
            default_interpret() if interpret is None else interpret, scale,
            num_heads)

    heads = num_heads if q.ndim == 3 else q.shape[1]
    if q.ndim == 4 and k is not None:
        kv_heads = k.shape[1]
    grouped = kv_heads is not None and kv_heads != heads
    if window or grouped or q_offset is not None:
        enforce(k is not None and heads is not None and attn_mask is None
                and segment_ids is None and heads % (kv_heads or heads) == 0
                and (causal or not (window or q_offset is not None)),
                "flash_attention: a window, a query offset and grouped heads "
                "take q, k, v with their head counts (num_heads a multiple of "
                "kv_heads) and at most a key bias; the first two are causal")
        if q.ndim == 3:
            d = q.shape[-1] // heads
            dv = v.shape[-1] // (kv_heads or heads)
            if not lane_heads(d, dv, heads) or (
                    grouped and lane_heads(d, dv, heads) != 1):
                out = flash_attention(
                    _split_heads(q, heads),
                    *(_split_heads(x, kv_heads or heads) for x in (k, v)),
                    causal=causal, key_bias=key_bias, block_q=block_q,
                    block_k=block_k, interpret=interpret, return_lse=True,
                    scale=scale, window=window, q_offset=q_offset)
                return ((_merge_heads(out[0]), out[1]) if return_lse
                        else _merge_heads(out[0]))
        block_q, block_k = resolve_block_shapes(block_q, block_k)
        out = _flash_fwd(
            q, k, v, None if key_bias is None else key_bias.astype(jnp.float32),
            None, None, causal, block_q, block_k,
            default_interpret() if interpret is None else interpret, scale,
            num_heads if q.ndim == 3 else None, window,
            kv_heads=kv_heads if q.ndim == 3 else None, q_offset=q_offset)
        return out if return_lse else out[0]

    fused = k is None
    if fused:
        enforce(v is None and q.ndim == 3 and num_heads is not None
                and q.shape[-1] % (3 * num_heads) == 0,
                "flash_attention: without k and v, q is a fused "
                "[b, s, 3 * h*d] projection and num_heads is given")
        d = q.shape[-1] // (3 * num_heads)
        dense_mask = attn_mask is not None and not (
            attn_mask.ndim == 4 and attn_mask.shape[1:3] == (1, 1))
        if dense_mask or return_lse or not lane_heads(d, d, num_heads):
            q, k, v = jnp.split(q, 3, axis=-1)
            fused = False
    if q.ndim == 3 and not fused:
        enforce(num_heads is not None and q.shape[-1] % num_heads == 0,
                f"flash_attention: a [b, s, h*d] call names its head count "
                f"(num_heads={num_heads!r} for a width of {q.shape[-1]})")
        d = q.shape[-1] // num_heads
        if not lane_heads(d, v.shape[-1] // num_heads, num_heads):
            out = flash_attention(
                *(_split_heads(x, num_heads) for x in (q, k, v)),
                causal=causal, attn_mask=attn_mask, key_bias=key_bias,
                segment_ids=segment_ids, kv_segment_ids=kv_segment_ids,
                block_q=block_q, block_k=block_k, interpret=interpret,
                return_lse=return_lse, scale=scale)
            if return_lse:
                return _merge_heads(out[0]), out[1]
            return _merge_heads(out)
    elif q.ndim == 4:
        num_heads = None
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
            if num_heads is not None:
                return _merge_heads(_mask_fallback(
                    *(_split_heads(x, num_heads) for x in (q, k, v)),
                    mask, causal, scale))
            return _mask_fallback(q, k, v, mask, causal, scale)
    seg_q = segment_ids
    seg_k = kv_segment_ids if kv_segment_ids is not None else segment_ids
    bias = None if key_bias is None else key_bias.astype(jnp.float32)
    if fused:
        return _flash_core_fused(q, bias, seg_q, seg_k, causal, block_q,
                                 block_k, interpret, scale, num_heads)
    if return_lse:
        return _flash_fwd(q, k, v, bias, seg_q, seg_k, causal,
                          block_q, block_k, interpret, scale, num_heads)
    return _flash_core(q, k, v, bias, seg_q, seg_k, causal,
                       block_q, block_k, interpret, scale, num_heads)


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
