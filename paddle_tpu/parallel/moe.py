"""Mixture-of-experts with expert parallelism over the mesh ``ep`` axis.

Gap-fill component (SURVEY §2.2: TP/PP/SP/**MoE-EP** are absent in the
reference — its only model partitioning is the distributed lookup table,
distribute_transpiler.py:1100). This supplies the modern equivalent:
a top-k-routed expert FFN bank whose experts are sharded across the
``ep`` mesh axis, with token dispatch as ``lax.all_to_all`` pairs riding
ICI — the TPU-native analog of the reference's prefetch-RPC row-sharded
table (split_ids → PrefetchVariable → merge becomes dispatch-einsum →
all_to_all → combine-einsum).

Design (GShard/Switch-style, static shapes for XLA):
- router softmax in f32, top-k selection with a *static capacity* per
  expert: C = ceil(local_tokens · k / E · capacity_factor). Tokens over
  capacity are dropped (their combine weight is zero) — this is what
  keeps every shape static under jit.
- dispatch/combine are one-hot einsums → the MXU does the routing.
- expert compute is a batched einsum over the local expert bank
  ([E_local, C·n, d] @ [E_local, d, ff]) — large, batched, bf16-ready.
- EP path runs under ``shard_map``: experts sharded on ``ep``, tokens
  sharded on (data axes + ``ep``), two tiled all_to_alls swap the
  token↔expert sharding around the expert compute.

Returns ``(out, aux_loss)`` — aux_loss is the load-balance term
(mean-prob · dispatch-fraction · E) to be added to the model loss.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..framework import LayerHelper, cast_compute
from .. import initializer as init
from . import mesh as mesh_lib


# -- static-config capture (analysis.contracts / moe:capacity lint) ---------
# Every moe() call records its routing shape here when a capture is
# active: the capacity/top_k/token numbers are fully static (they size
# the dispatch tensors), so the expected token drop rate is computable
# without running anything. analysis.check wraps its program traces in
# capture_moe_configs() and feeds the records to rules.check_moe_capacity.

_capture_tls = threading.local()


@contextlib.contextmanager
def capture_moe_configs():
    """Collect the static routing config of every ``moe()`` layer traced
    inside the block. Yields the list the records append to. Nested
    captures each see only their own block's layers; with no capture
    active, recording is a no-op (zero trace-time cost)."""
    prev = getattr(_capture_tls, "log", None)
    _capture_tls.log = log = []
    try:
        yield log
    finally:
        _capture_tls.log = prev


def _record_config(**cfg) -> None:
    log = getattr(_capture_tls, "log", None)
    if log is not None:
        log.append(cfg)


def _topk_dispatch(probs, top_k: int, capacity: int, normalize_gates: bool):
    """Build dispatch/combine tensors [t, E, C] from router probs [t, E].

    Position-in-expert is assigned k-major (all 1st choices before any
    2nd choices), matching GShard's priority so 1st-choice tokens are
    dropped last.
    """
    t, e = probs.shape
    vals, idx = jax.lax.top_k(probs, top_k)            # [t, k]
    if normalize_gates:
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
    mask = jax.nn.one_hot(idx, e, dtype=jnp.float32)   # [t, k, E]
    flat = jnp.transpose(mask, (1, 0, 2)).reshape(top_k * t, e)
    pos = jnp.cumsum(flat, axis=0) - flat              # position within expert
    pos = jnp.transpose(pos.reshape(top_k, t, e), (1, 0, 2))
    pos_k = jnp.sum(pos * mask, axis=-1)               # [t, k]
    keep = (pos_k < capacity).astype(jnp.float32)
    slot = jax.nn.one_hot(pos_k.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = jnp.einsum("tke,tkc,tk->tec", mask, slot, keep)
    combine = jnp.einsum("tke,tkc,tk->tec", mask, slot, keep * vals)
    return dispatch, combine, mask


def _aux_loss(probs, mask):
    """Load-balance loss (Switch eq. 4): E · Σ_e fraction_e · meanprob_e."""
    e = probs.shape[-1]
    me = jnp.mean(probs, axis=0)                       # mean router prob per expert
    ce = jnp.mean(jnp.sum(mask, axis=1), axis=0)       # fraction routed per expert
    ce = ce / jnp.maximum(jnp.sum(ce), 1e-9)
    return e * jnp.sum(me * ce)


def _expert_ffn(xe, w1, b1, w2, b2, act):
    """Batched expert FFN: xe [E_local, C', d] through per-expert weights.

    Plain compute-dtype einsums (no f32 preferred_element_type): XLA's
    TPU matmul accumulates bf16 in f32 regardless, and an f32-output
    einsum over bf16 operands makes autodiff compute the backward dots
    as f32×f32 — the ~1/8-rate MXU path (same trap the attention
    scores custom-VJP fixes)."""
    xe, w1, w2 = cast_compute(xe, w1, w2)
    h = jnp.einsum("ecd,edf->ecf", xe, w1) + b1[:, None, :].astype(xe.dtype)
    h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :].astype(xe.dtype)
    return y


def _route_compute(xt, wg, w1, b1, w2, b2, *, top_k, capacity, act,
                   normalize_gates, exchange=None):
    """Shared router→dispatch→experts→combine over tokens [t, d].
    ``exchange(x, inverse)`` wraps the expert compute with the EP
    token↔expert reshard; None on the dense path."""
    # router stays f32 (gate correctness); everything sized by tokens —
    # dispatch/combine one-hot einsums and the expert bank — runs in the
    # compute dtype (the dispatch einsum's t·E·C·d flops rival the
    # expert FFN's at real capacity factors)
    logits = jnp.matmul(xt.astype(jnp.float32), wg)
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, mask = _topk_dispatch(probs, top_k, capacity, normalize_gates)
    aux = _aux_loss(probs, mask)
    xt_c = cast_compute(xt)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(xt_c.dtype), xt_c)  # [E, C, d]
    if exchange is not None:
        xe = exchange(xe, inverse=False)
    ye = _expert_ffn(xe, w1, b1, w2, b2, act)
    if exchange is not None:
        ye = exchange(ye, inverse=True)
    yt = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye)
    return yt, aux


def _moe_body(x, wg, w1, b1, w2, b2, *, axis_name, top_k, capacity, act,
              normalize_gates, data_axes):
    """Per-device EP computation: x [b_local, s, d] local tokens,
    w1/b1/w2/b2 local expert shard [E_local, ...], wg replicated."""
    varying = tuple(data_axes) + (axis_name,)
    wg = mesh_lib.pvary(wg, varying)
    if data_axes:
        w1, b1, w2, b2 = (mesh_lib.pvary(a, tuple(data_axes)) for a in (w1, b1, w2, b2))

    def exchange(x, inverse):
        # token-shard ↔ expert-shard: [E, C, d] → [E/n, n·C, d] and back
        split, concat = (1, 0) if inverse else (0, 1)
        return jax.lax.all_to_all(x, axis_name, split_axis=split,
                                  concat_axis=concat, tiled=True)

    b, s, d = x.shape
    yt, aux = _route_compute(x.reshape(b * s, d), wg, w1, b1, w2, b2,
                             top_k=top_k, capacity=capacity, act=act,
                             normalize_gates=normalize_gates, exchange=exchange)
    aux = jax.lax.pmean(aux, varying)
    return yt.reshape(b, s, d).astype(x.dtype), aux


def moe(
    x,
    num_experts: int,
    d_ff: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    mesh: Optional[Mesh] = None,
    axis_name: str = mesh_lib.EP,
    act: str = "gelu",
    normalize_gates: bool = True,
    param_attr=None,
    name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k-routed MoE FFN over ``x`` [batch, seq, d_model].

    Returns ``(out, aux_loss)``. With ``mesh`` given and its ``ep`` axis
    >1, experts are sharded over ``ep`` and tokens dispatched via
    all_to_all (batch must be sharded over data axes + ``ep``);
    otherwise runs the dense single-device path with identical numerics
    (capacity permitting).
    """
    from ..layers.ops import apply_activation

    helper = LayerHelper("moe", name=name)
    b, s, d = x.shape
    act_fn = lambda h: apply_activation(h, act)

    wg = helper.create_parameter("router_w", shape=(d, num_experts),
                                 dtype=jnp.float32, attr=param_attr)
    w1 = helper.create_parameter("expert_w1", shape=(num_experts, d, d_ff),
                                 dtype=jnp.float32, attr=param_attr)
    b1 = helper.create_parameter("expert_b1", shape=(num_experts, d_ff),
                                 dtype=jnp.float32, initializer=init.Constant(0.0))
    w2 = helper.create_parameter("expert_w2", shape=(num_experts, d_ff, d),
                                 dtype=jnp.float32, attr=param_attr)
    b2 = helper.create_parameter("expert_b2", shape=(num_experts, d),
                                 dtype=jnp.float32, initializer=init.Constant(0.0))

    ep = mesh.shape[axis_name] if mesh is not None and axis_name in mesh.axis_names else 1
    if ep > 1 and num_experts % ep != 0:
        raise ValueError(f"num_experts={num_experts} not divisible by ep={ep}")

    data_axes = tuple(a for a in (mesh_lib.DATA_AXES if mesh is None else
                                  mesh_lib.data_axis_names(mesh))
                      if mesh is not None and mesh.shape[a] > 1)
    shards = ep * int(np.prod([mesh.shape[a] for a in data_axes] or [1]))
    t_local = (b // max(1, shards)) * s if ep > 1 else b * s
    capacity = max(1, int(math.ceil(t_local * top_k / num_experts * capacity_factor)))
    # record under the FULL scoped path (what params are named under):
    # two MoE layers in different scopes are distinct findings — the
    # scope-local helper name ("moe_0") would collide their fingerprints
    # and a baseline for one would suppress the other
    from ..framework import current_context
    _ctx = current_context()
    _record_config(name=_ctx.full_name(helper.name) if _ctx else helper.name,
                   num_experts=num_experts, top_k=top_k,
                   capacity_factor=float(capacity_factor), capacity=capacity,
                   tokens=t_local, ep=ep)

    if ep == 1:
        # dense path (single device / ep absent): same algorithm, no collectives
        yt, aux = _route_compute(x.reshape(b * s, d), wg, w1, b1, w2, b2,
                                 top_k=top_k, capacity=capacity, act=act_fn,
                                 normalize_gates=normalize_gates)
        return yt.reshape(b, s, d).astype(x.dtype), aux

    batch_shard = tuple(data_axes) + (axis_name,)
    xspec = P(batch_shard if len(batch_shard) > 1 else batch_shard[0], None, None)
    espec = P(axis_name)
    body = functools.partial(_moe_body, axis_name=axis_name, top_k=top_k,
                             capacity=capacity, act=act_fn,
                             normalize_gates=normalize_gates, data_axes=data_axes)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(), espec, espec, espec, espec),
        out_specs=(xspec, P()))
    return fn(x, wg, w1, b1, w2, b2)


# -- a held share of a wide expert layer (sigmoid, bias-corrected, dropless) ----

# Rows of one grouped product. The (token, held expert) pairs of a call are
# data: a prompt of t tokens gives t * top_k * held / total of them on
# average and t * top_k at most. They are walked in blocks of this many, as
# many blocks as there are pairs, so nothing is dropped, no capacity is
# stated, and the buffers stay one block large whatever the routing does.
# 512 is what the scatter-add that returns a block's rows to their tokens
# allows on the TPU: XLA walks up to some count of indices one by one, a
# microsecond a row (0.51 ms for 512 rows of 7,168 into 15,872), and above
# it takes a form that costs 12.7 ms whatever the count (2,048 rows 12.7 ms,
# 4,096 13.1; a one-hot product on the MXU 1.43 / 2.50 / 4.98 ms; my chip
# run, PR 31, PERF.md section 6).
PAIR_BLOCK = 512


def sigmoid_topk_route(h, router_w, select_bias, top_k: int,
                       scaling: float = 1.0):
    """DeepseekV3's ``noaux_tc`` routing with one group: scores
    ``sigmoid(h W_r)`` in float32 at full precision (a score that decides a
    selection may not depend on the matmul's rounding mode), the ``top_k``
    of ``score + select_bias`` selected, and weights from the unbiased
    scores of the selected, normalised to sum 1 and times ``scaling``.
    ``h [t, d]`` -> ``(experts [t, top_k] int32, weights [t, top_k] f32)``."""
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.matmul(
            h.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(scores + select_bias.astype(jnp.float32),
                                   top_k)
        picked = jnp.take_along_axis(scores, experts, axis=-1)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling
    return experts.astype(jnp.int32), weights


def softmax_topk_route(h, router_w, top_k: int):
    """GraniteMoe's routing: the ``top_k`` largest of the raw logits ``h
    W_r`` (float32 at full precision, as :func:`sigmoid_topk_route` takes its
    scores and for its reason), weighted by the softmax over those ``top_k``
    logits alone: the weights sum to 1 and the unselected logits move
    nothing. ``h [t, d]`` -> ``(experts [t, top_k] int32, weights [t, top_k]
    f32)``."""
    with jax.named_scope("router"):
        logits = jnp.matmul(
            h.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        picked, experts = jax.lax.top_k(logits, top_k)
        weights = jax.nn.softmax(picked, axis=-1)
    return experts.astype(jnp.int32), weights


def _record_held_plan(tokens, total, held, first, top_k, banks, block, back,
                      routing):
    """One zero-length span for each expert layer traced; ``banks`` may
    hold more groups than the ``held`` this layer reads."""
    from ..core import profiler

    profiler.record_span(
        "moe.plan", time.time_ns(), 0, experts_total=total, experts_held=held,
        first_expert=first, top_k=top_k, tokens=tokens, form="ragged_dot",
        pair_block=min(block, tokens * top_k), back=back, routing=routing,
        expert_bytes_held=sum(
            held * math.prod(w.shape[1:]) * w.dtype.itemsize for w in banks))


def _sorted_pairs(experts, first_expert: int, experts_held: int):
    """The ``[t * top_k]`` (token, expert) pairs by held expert: ``(order,
    sizes, ends)``, the pairs' indices sorted by expert with those held
    elsewhere last, each held expert's count and where its run ends."""
    local = experts.reshape(-1) - first_expert          # [t * top_k]
    here = (local >= 0) & (local < experts_held)
    key = jnp.where(here, local, experts_held)          # absent ones last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=experts_held + 1)[:experts_held]
    return order, sizes, jnp.cumsum(sizes)


def _block_rows(h, banks, flat_w, order, sizes, ends, n_pairs, lo, block: int,
                top_k: int, bank_offset):
    """Rows ``lo .. lo + block`` of the sorted pairs through the three
    grouped products: ``(token [block], live [block], y [block, d]`` float32,
    weighted, zero where no pair stands)``."""
    w_gate, w_up, w_down = banks
    pair = jax.lax.dynamic_slice(order, (lo,), (block,))
    live = lo + jnp.arange(block) < n_pairs
    token = jnp.where(live, pair // top_k, 0)
    # this block's rows of each group: the group's span, clipped
    in_block = (jnp.clip(ends, lo, lo + block)
                - jnp.clip(ends - sizes, lo, lo + block))
    gs = jax.lax.dynamic_update_slice(
        jnp.zeros((w_gate.shape[0],), jnp.int32), in_block.astype(jnp.int32),
        (bank_offset,))
    x = h[token]
    gate = jax.lax.ragged_dot(x, w_gate, gs, preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(x, w_up, gs, preferred_element_type=jnp.float32)
    y = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(h.dtype), w_down, gs,
                           preferred_element_type=jnp.float32)
    return token, live, jnp.where(live[:, None], y * flat_w[pair][:, None], 0.0)


def moe_held(h, experts, weights, w_gate, w_up, w_down, *, first_expert: int,
             experts_held: int, experts_total: int, bank_offset=0,
             pair_block: int = PAIR_BLOCK, back: str = "scatter",
             routing: str = "sigmoid_topk"):
    """The part of a routed layer's result that the experts held here
    give: ``sum over e in a token's selection, first_expert <= e <
    first_expert + experts_held, of weights_e * E_e(h)``, each ``E_e`` a
    gated SiLU FFN. ``h [t, d]``; ``experts``, ``weights`` ``[t, top_k]``
    from :func:`sigmoid_topk_route` over all ``experts_total``. Returns
    ``[t, d]`` float32. What the experts held elsewhere would add is left
    out; no code stands in for them or for their exchange.

    The pairs are sorted by expert and each block of them goes through
    one grouped product a matrix (``jax.lax.ragged_dot``: on the TPU a
    grouped-matmul kernel that visits only the row tiles and the experts
    that a group fills) and back to its tokens by a scatter-add. The
    banks ``[G, d, f]`` / ``[G, f, d]`` may hold more groups than this
    layer's (a stack of layers, flattened):
    ``bank_offset`` (traced or static) says where this layer's
    ``experts_held`` start, and every other group gets size 0, so a layer
    of a scanned stack reads its experts in place, with no slice taken.

    ``back="gather"`` is the form for a layer of many narrow experts
    (Granite: ten pairs a token, experts 768 wide, 160 blocks of 512 a piece
    where Trinity has 16): blocks of ``pair_block`` pairs, their rows
    returned to the tokens by gathers (:func:`_held_by_gathers`), and pairs
    that fit one block (a step's rows) through every held expert with no
    pair sorted (:func:`_held_densely`); the defaults are the walk above,
    unchanged. ``routing`` names the routing function in the ``moe.plan``
    span."""
    t, top_k = experts.shape
    banks = (w_gate, w_up, w_down)
    if back == "gather" and t * top_k <= pair_block:
        back = "dense"
    _record_held_plan(t, experts_total, experts_held, first_expert, top_k,
                      banks, pair_block, back, routing)
    if back == "dense":
        return _held_densely(h, experts, weights, banks, first_expert,
                             experts_held)
    if back == "gather":
        return _held_by_gathers(h, experts, weights, banks, first_expert,
                                experts_held, bank_offset, pair_block)
    with jax.named_scope("moe"):
        order, sizes, ends = _sorted_pairs(experts, first_expert, experts_held)
        n_pairs = ends[-1]
        block = min(pair_block, t * top_k)
        order = jnp.pad(order, (0, block))
        flat_w = weights.reshape(-1)

        def one_block(i, out):
            token, live, y = _block_rows(h, banks, flat_w, order, sizes, ends,
                                         n_pairs, i * block, block, top_k,
                                         bank_offset)
            if t > block:
                return out.at[token].add(y)     # a dead row adds 0 to token 0
            # a step's few tokens: a 0/1 [t, block] operand times the block,
            # microseconds on the MXU where the scatter walks every row
            back = (jnp.arange(t)[:, None] == token[None, :]) & live[None, :]
            return out + jnp.matmul(back.astype(h.dtype), y.astype(h.dtype),
                                    preferred_element_type=jnp.float32)

        out = jnp.zeros((t, h.shape[-1]), jnp.float32)
        if block == t * top_k:      # every pair fits one block (a decode step)
            return one_block(0, out)
        return jax.lax.fori_loop(0, (n_pairs + block - 1) // block,
                                 one_block, out)


def _held_densely(h, experts, weights, banks, first_expert, experts_held):
    """:func:`moe_held`'s result for a step's few tokens where the experts
    are many and small: every held expert applied to every token, a token's
    weight zero for an expert it did not select. 32 rows of ten pairs touch
    35.7 of 36 held experts, so the grouped products read every bank anyway,
    a group of four or five rows at a time, at 57% of the HBM's rate (0.49 ms
    a product of 226 MB in ``granite-serve-agent``'s step; PERF.md section 6,
    PR 49); here the gate and up banks are one batched product each and the
    down bank, weighted activations against ``[held * f, d]``, one plain one.
    Seven times the multiply-adds, which a step has to spare. The banks hold
    this layer's experts and no others."""
    w_gate, w_up, w_down = banks
    held, f, d = w_down.shape
    assert w_gate.shape[0] == experts_held == held, (w_gate.shape, experts_held)
    with jax.named_scope("moe"):
        # [t, held]: what a token gives each held expert
        mine = (experts - first_expert)[..., None] == jnp.arange(held)
        share = jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=1)
        gate = jnp.einsum("td,edf->tef", h, w_gate,
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("td,edf->tef", h, w_up,
                        preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up * share[..., None]).astype(h.dtype)
        return jnp.matmul(act.reshape(-1, held * f), w_down.reshape(held * f, d),
                          preferred_element_type=jnp.float32)


# Tokens a walk of :func:`_held_by_gathers` takes at once: the rows its
# blocks leave wait in one buffer of ``tokens * top_k`` rows (every pair
# could be held here), 335 MB at 4,096 tokens of ten pairs 4,096 wide (a
# walk of 2,048 gives a group of Granite's pairs 284 rows and the grouped
# products a fifth more time: 25.0 ms a piece of 8,192 tokens against 21.2;
# my chip run, PR 49, call 207).
GATHER_TOKENS = 4096


def _held_by_gathers(h, experts, weights, banks, first_expert, experts_held,
                     bank_offset, block):
    """:func:`moe_held`'s result with the rows returned by gathers, for
    ``t * top_k`` pairs that are many blocks: the pairs sorted by expert go
    through the grouped products in blocks of ``block`` and each block's
    rows, weighted, are written where they stand in the sorted order (one
    contiguous update a block); then a token's ``top_k`` rows are read back
    from where its pairs stood (the inverse of the sort) and summed in
    float32. A pair held elsewhere is sorted behind every pair held here,
    where no block writes: it reads zeros. XLA's scatter-add returns a row a
    microsecond (``PAIR_BLOCK``'s comment), a gather of as many rows takes a
    fifth of that and the update nothing (PERF.md section 6, PR 31 and PR
    49). The tokens are walked ``GATHER_TOKENS`` at a time so that the
    buffer stays that size."""
    t, top_k = experts.shape
    d = h.shape[-1]
    pieces = next(n for n in range(-(-t // GATHER_TOKENS), t + 1) if t % n == 0)
    size = t // pieces
    rows = -(-size * top_k // block) * block

    def walk(args):
        h, experts, weights = args
        order, sizes, ends = _sorted_pairs(experts, first_expert, experts_held)
        place = jnp.argsort(order).astype(jnp.int32)        # the sort's inverse
        n_pairs = ends[-1]
        order = jnp.pad(order, (0, rows - size * top_k))
        flat_w = weights.reshape(-1)

        def one_block(i, stood):
            _, _, y = _block_rows(h, banks, flat_w, order, sizes, ends, n_pairs,
                                  i * block, block, top_k, bank_offset)
            return jax.lax.dynamic_update_slice(stood, y.astype(h.dtype),
                                                (i * block, 0))

        stood = jax.lax.fori_loop(0, (n_pairs + block - 1) // block, one_block,
                                  jnp.zeros((rows, d), h.dtype))
        place = place.reshape(size, top_k)
        out = jnp.zeros((size, d), jnp.float32)
        for k in range(top_k):
            out = out + stood[place[:, k]].astype(jnp.float32)
        return out

    with jax.named_scope("moe"):
        if pieces == 1:
            return walk((h, experts, weights))
        cut = lambda a: a.reshape((pieces, size) + a.shape[1:])
        return jax.lax.map(walk, (cut(h), cut(experts), cut(weights))
                           ).reshape(t, d)


def moe_ep_rules():
    """Sharding-rule entries placing expert banks on ``ep`` — append to a
    ShardingRules table (transformer_tp_rules(extra=moe_ep_rules()))."""
    return [
        (r".*moe.*/expert_(w1|b1|w2|b2)$", P("ep")),
        (r".*moe.*/router_w$", P()),
    ]
