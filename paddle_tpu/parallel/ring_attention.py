"""Ring attention — sequence/context parallelism over the mesh ICI.

Gap-fill component (SURVEY §2.2/§5): the reference has NO sequence
parallelism — nothing distributes a single sequence. Here, attention
over a sequence sharded on the mesh's ``sp`` axis: each device holds a
query/key/value shard, K/V shards rotate around the ring via
``ppermute`` (neighbor ICI hops), and per-shard results merge in
log-space from the flash kernel's (out, lse) pairs.

Each ring step runs the pallas flash kernel (ops/flash_attention) on
the local Q shard against the visiting K/V shard, so per-chip memory is
O(S/n · d) for the shard buffers plus O(block²) inside the kernel —
never an S/n × S/n score matrix. The backward is a second ring pass
reusing the flash backward kernels with the COMBINED logsumexp
(flash-attention-2 style): dq accumulates locally, dk/dv accumulate on
buffers that travel with their K/V shard and arrive home after the full
cycle. Differentiable end-to-end via a custom VJP. One scan/ppermute/
accumulate machinery serves every schedule; schedules differ only in the
three visibility branches (earlier/own/later visiting rank).

Causal schedules:

- ``"ring"``: the visiting shard is fully visible (earlier ranks),
  causally visible (own rank), or invisible (later ranks) — selected
  with lax.switch so invisible steps do no FLOPs. Load-imbalanced: rank
  r does r+1 real steps (the last rank ~2n-1× the first's work, and the
  step time is the max over ranks).
- ``"zigzag"`` (default for causal): the sequence is split into 2n
  blocks and rank r holds blocks (r, 2n-1-r) — the standard
  context-parallel zigzag layout. Each ring step then costs EVERY rank
  exactly half a shard-pair of attention: own shard = local causal over
  the zigzag-ordered shard; an earlier rank's visit = all local queries
  attend its first half-block; a later rank's visit = the local second
  half-block attends all of it. Per-rank work is 2n units/rank vs
  (4r+2) for "ring" (see :func:`causal_work_per_rank`), identical
  numerics (tested).

Zigzag layout cost: with the default ``layout="natural"`` each call
gathers q/k/v into zigzag order and the output back — cross-shard
reshuffles per attention call. A transformer stack should instead keep
activations in zigzag order end-to-end (permute token ids once before
the embedding, unpermute once after the stack — positions travel with
the tokens) and pass ``layout="zigzag"`` so the ring sees shard-local
data only.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.errors import enforce
from ..ops import flash_attention as fa
from .mesh import pvary

NEG_INF = -1e30


def _merge(acc, lse_c, out_i, lse_i):
    """Log-space merge of per-shard flash results."""
    lse_new = jnp.logaddexp(lse_c, lse_i)
    w_old = jnp.exp(lse_c - lse_new)[..., None]
    w_new = jnp.exp(lse_i - lse_new)[..., None]
    return acc * w_old + out_i.astype(jnp.float32) * w_new, lse_new


# --------------------------------------------------------------------------
# Schedules: each provides the three visibility branches (visiting rank
# earlier than / equal to / later than the local rank) for the forward
# and backward ring passes. `None` branch list means "every step is a
# full step" (non-causal).
# --------------------------------------------------------------------------


class _RingSchedule:
    """Contiguous shards; visiting shard fully/causally/in-visible."""

    def __init__(self, causal: bool, block_q: int, block_k: int):
        self.causal = causal
        self.block_q, self.block_k = block_q, block_k

    def fwd_branches(self, q):
        b, h, sl, d = q.shape

        def full(k_cur, v_cur):
            return fa.flash_attention(q, k_cur, v_cur, causal=False,
                                      block_q=self.block_q, block_k=self.block_k,
                                      return_lse=True)

        def diag(k_cur, v_cur):
            return fa.flash_attention(q, k_cur, v_cur, causal=True,
                                      block_q=self.block_q, block_k=self.block_k,
                                      return_lse=True)

        def masked(k_cur, v_cur):
            return (jnp.zeros_like(q), jnp.full((b, h, sl), NEG_INF, jnp.float32))

        return [full, diag, masked] if self.causal else None

    def bwd_branches(self, q, out, lse, g, delta, interpret):
        def grads(k_cur, v_cur, caus):
            return fa._flash_bwd(q, k_cur, v_cur, None, None, None, caus,
                                 out, lse, g, self.block_q, self.block_k,
                                 interpret=interpret, delta=delta)

        def full(k_cur, v_cur):
            return grads(k_cur, v_cur, False)

        def diag(k_cur, v_cur):
            return grads(k_cur, v_cur, True)

        def masked(k_cur, v_cur):
            return (jnp.zeros_like(q), jnp.zeros_like(k_cur), jnp.zeros_like(v_cur))

        return [full, diag, masked] if self.causal else None


class _ZigzagSchedule:
    """Rank r holds blocks (r, 2n-1-r) of the 2n-block split: every step
    costs exactly half a shard-pair on every rank (balanced causal)."""

    def __init__(self, block_q: int, block_k: int):
        self.block_q, self.block_k = block_q, block_k

    def fwd_branches(self, q):
        b, h, sl, d = q.shape
        h2 = sl // 2

        def earlier(k_cur, v_cur):
            # visiting rank s < r: its first half (block s) precedes both
            # local blocks — fully visible; its second half (block
            # 2n-1-s) follows both — invisible
            return fa.flash_attention(q, k_cur[:, :, :h2], v_cur[:, :, :h2],
                                      causal=False, block_q=self.block_q,
                                      block_k=self.block_k, return_lse=True)

        def diag(k_cur, v_cur):
            # own shard: local causal is exactly the zigzag visibility
            # (block r precedes block 2n-1-r in both q and k order)
            return fa.flash_attention(q, k_cur, v_cur, causal=True,
                                      block_q=self.block_q, block_k=self.block_k,
                                      return_lse=True)

        def later(k_cur, v_cur):
            # visiting rank s > r: both its blocks fall between the local
            # blocks — visible only to the local second half
            out2, lse2 = fa.flash_attention(q[:, :, h2:], k_cur, v_cur,
                                            causal=False, block_q=self.block_q,
                                            block_k=self.block_k, return_lse=True)
            out = jnp.concatenate(
                [jnp.zeros((b, h, h2, d), out2.dtype), out2], axis=2)
            lse = jnp.concatenate(
                [jnp.full((b, h, h2), NEG_INF, jnp.float32), lse2], axis=2)
            return out, lse

        return [earlier, diag, later]

    def bwd_branches(self, q, out, lse, g, delta, interpret):
        b, h, sl, d = q.shape
        h2 = sl // 2

        def earlier(k_cur, v_cur):
            dq_i, dk_h, dv_h = fa._flash_bwd(
                q, k_cur[:, :, :h2], v_cur[:, :, :h2], None, None, None, False,
                out, lse, g, self.block_q, self.block_k,
                interpret=interpret, delta=delta)
            pad = jnp.zeros((b, h, sl - h2, d), dk_h.dtype)
            return (dq_i, jnp.concatenate([dk_h, pad], axis=2),
                    jnp.concatenate([dv_h, pad], axis=2))

        def diag(k_cur, v_cur):
            return fa._flash_bwd(q, k_cur, v_cur, None, None, None, True,
                                 out, lse, g, self.block_q, self.block_k,
                                 interpret=interpret, delta=delta)

        def later(k_cur, v_cur):
            dq_h, dk_i, dv_i = fa._flash_bwd(
                q[:, :, h2:], k_cur, v_cur, None, None, None, False,
                out[:, :, h2:], lse[:, :, h2:], g[:, :, h2:],
                self.block_q, self.block_k, interpret=interpret,
                delta=delta[:, :, h2:])
            dq_i = jnp.concatenate(
                [jnp.zeros((b, h, h2, d), dq_h.dtype), dq_h], axis=2)
            return dq_i, dk_i, dv_i

        return [earlier, diag, later]


def _dispatch(branches, idx, src, k_cur, v_cur):
    """Visibility dispatch shared by fwd/bwd: [earlier, own, later]."""
    b_ = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
    return jax.lax.switch(b_, branches, k_cur, v_cur)


def _ring_fwd_body(q, k0, v0, *, axis_name, varying_axes, schedule):
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]
    branches = schedule.fwd_branches(q)

    def step(carry, i):
        k_cur, v_cur, acc, lse_c = carry
        if branches is None:  # non-causal: every step is a full step
            out_i, lse_i = fa.flash_attention(
                q, k_cur, v_cur, causal=False, block_q=schedule.block_q,
                block_k=schedule.block_k, return_lse=True)
        else:
            out_i, lse_i = _dispatch(branches, idx, (idx - i) % n, k_cur, v_cur)
        acc, lse_c = _merge(acc, lse_c, out_i, lse_i)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, lse_c), None

    vaxes = tuple(varying_axes) or (axis_name,)
    acc0 = pvary(jnp.zeros((b, h, sl, d), jnp.float32), vaxes)
    lse0 = pvary(jnp.full((b, h, sl), NEG_INF, jnp.float32), vaxes)
    (_, _, acc, lse), _ = jax.lax.scan(step, (k0, v0, acc0, lse0), jnp.arange(n))
    return acc.astype(q.dtype), lse


def _ring_bwd_body(q, k0, v0, out, lse, g, *, axis_name, varying_axes, schedule):
    """Second ring pass: flash backward kernels with the combined lse.
    dk/dv ride with their shard and come home after n rotations."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    interpret = fa.default_interpret()
    # delta is k/v-shard-invariant: compute once, not per ring step
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    branches = schedule.bwd_branches(q, out, lse, g, delta, interpret)

    def step(carry, i):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
        if branches is None:
            dq_i, dk_i, dv_i = fa._flash_bwd(
                q, k_cur, v_cur, None, None, None, False, out, lse, g,
                schedule.block_q, schedule.block_k, interpret=interpret,
                delta=delta)
        else:
            dq_i, dk_i, dv_i = _dispatch(branches, idx, (idx - i) % n,
                                         k_cur, v_cur)
        dq_acc = dq_acc + dq_i.astype(jnp.float32)
        dk_cur = dk_cur + dk_i.astype(jnp.float32)
        dv_cur = dv_cur + dv_i.astype(jnp.float32)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc), None

    vaxes = tuple(varying_axes) or (axis_name,)
    dk0 = pvary(jnp.zeros(k0.shape, jnp.float32), vaxes)
    dv0 = pvary(jnp.zeros(v0.shape, jnp.float32), vaxes)
    dq0 = pvary(jnp.zeros(q.shape, jnp.float32), vaxes)
    (_, _, dk, dv, dq), _ = jax.lax.scan(
        step, (k0, v0, dk0, dv0, dq0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k0.dtype), dv.astype(v0.dtype)


def _make_sp_attention(axis_name, varying_axes, schedule):
    """custom_vjp wrapper shared by every schedule."""

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _ring_fwd_body(q, k, v, axis_name=axis_name,
                                varying_axes=varying_axes, schedule=schedule)
        return out

    def attn_fwd(q, k, v):
        out, lse = _ring_fwd_body(q, k, v, axis_name=axis_name,
                                  varying_axes=varying_axes, schedule=schedule)
        return out, (q, k, v, out, lse)

    def attn_bwd(res, g):
        q, k, v, out, lse = res
        return _ring_bwd_body(q, k, v, out, lse, g, axis_name=axis_name,
                              varying_axes=varying_axes, schedule=schedule)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


# --------------------------------------------------------------------------
# Schedule accounting & zigzag layout helpers
# --------------------------------------------------------------------------


def causal_work_per_rank(n: int, schedule: str = "zigzag"):
    """Attention compute per rank over the full causal pass, in units of
    (sl/2)² score tiles (sl = local shard length). Plain ring: rank r
    does r full-shard steps (4 units) plus its causal diagonal (2);
    zigzag: every rank does 2 units on every one of the n steps. Both
    sum to 2n² (same total FLOPs); zigzag is flat."""
    if schedule == "ring":
        return [4 * r + 2 for r in range(n)]
    if schedule == "zigzag":
        return [2 * n] * n
    raise ValueError(f"unknown schedule {schedule!r}")


def zigzag_order(seq_len: int, n: int):
    """Global sequence index order that places blocks (r, 2n-1-r) of the
    2n-block split contiguously on rank r."""
    block = seq_len // (2 * n)
    idx = []
    for r in range(n):
        idx.extend(range(r * block, (r + 1) * block))
        idx.extend(range((2 * n - 1 - r) * block, (2 * n - r) * block))
    return jnp.asarray(idx, jnp.int32)


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------


def ring_attention(
    q, k, v,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    batch_axes: Optional[tuple] = ("dp", "fsdp"),
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    schedule: str = "auto",
    layout: str = "natural",
):
    """Attention over [b, h, s, d] with s sharded on ``axis_name``.

    Batch may additionally be sharded over ``batch_axes``; heads stay
    unsharded here (combine with TP by sharding h outside via shard_map
    composition). ``block_q``/``block_k`` default through the same
    flag resolution as :func:`flash_attention` (flash_block_q/_k), and
    unset they leave each shard's kernels to the flash plan.

    ``schedule``: "auto" picks the load-balanced "zigzag" for causal
    attention (falling back to "ring" when s is not divisible by 2n) and
    the plain "ring" otherwise.

    ``layout``: "natural" inputs are gathered into zigzag order and the
    output gathered back — cross-shard traffic per call. Pass "zigzag"
    when activations already live in zigzag order (permute once outside
    the layer stack; see module docstring) to keep the ring shard-local.
    """
    enforce(schedule in ("auto", "ring", "zigzag"),
            f"unknown schedule {schedule!r} (auto|ring|zigzag)")
    enforce(layout in ("natural", "zigzag"),
            f"unknown layout {layout!r} (natural|zigzag)")
    block_q, block_k = fa.resolve_block_shapes(block_q, block_k)
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        # degenerate ring: single-shard flash attention
        return fa.flash_attention(q, k, v, causal=causal,
                                  block_q=block_q, block_k=block_k)

    n = mesh.shape[axis_name]
    if schedule == "auto":
        schedule = "zigzag" if (causal and q.shape[2] % (2 * n) == 0) else "ring"
    if schedule == "zigzag" and not causal:
        schedule = "ring"  # zigzag only changes causal visibility
    # zigzag-ordered activations under the contiguous ring schedule would
    # mask the wrong token pairs — silently wrong attention
    enforce(not (layout == "zigzag" and schedule != "zigzag"),
            f"layout='zigzag' requires the zigzag schedule, but schedule "
            f"resolved to {schedule!r} (causal={causal}, seq={q.shape[2]}, "
            f"2n={2 * n}); un-permute the activations or fix seq divisibility")

    bspec = tuple(a for a in (batch_axes or ()) if a in mesh.axis_names)
    bshard = bspec if len(bspec) > 1 else (bspec[0] if bspec else None)
    spec = P(bshard, None, axis_name, None)
    vaxes = tuple(mesh.axis_names)

    if schedule == "zigzag":
        s = q.shape[2]
        enforce(s % (2 * n) == 0,
                f"zigzag needs seq {s} divisible by 2n={2 * n}")
        body = _make_sp_attention(axis_name, vaxes,
                                  _ZigzagSchedule(block_q, block_k))
        fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        if layout == "zigzag":
            return fn(q, k, v)
        order = zigzag_order(s, n)
        inv = jnp.argsort(order)
        out = fn(jnp.take(q, order, axis=2), jnp.take(k, order, axis=2),
                 jnp.take(v, order, axis=2))
        return jnp.take(out, inv, axis=2)

    body = _make_sp_attention(axis_name, vaxes,
                              _RingSchedule(causal, block_q, block_k))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
