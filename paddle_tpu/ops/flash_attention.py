"""Flash attention — pallas TPU kernels (forward AND backward).

New first-class component per SURVEY §5/§7: the reference has no
attention kernels at all (attention was composed from mul/softmax ops in
models, e.g. benchmark/fluid/models/machine_translation.py), and no
answer to long sequences beyond LoD ragged batching. This supplies
O(seq) -memory attention on TPU:

- K/V are streamed through VMEM on the innermost grid dimension
  (Pallas double-buffers the HBM→VMEM DMA automatically), so sequence
  length is bounded by HBM, not by the ~16MB VMEM.
- Online softmax state (m, l, acc) lives in VMEM scratch that persists
  across the innermost grid steps; output is finalized on the last step.
- Backward is two pallas kernels of the same shape: a dq pass
  (q-block-major, streaming K/V) and a dkv pass (k-block-major,
  streaming Q/dO), both recomputing probabilities blockwise from the
  saved logsumexp — the standard flash-attention-2 decomposition.
- Masking: causal, an additive per-key bias [b, s_k] (padding), and
  segment ids (the LoD ragged-batch equivalent, layers/sequence.py
  design) — all fused into the kernels.
- Per-row vectors (bias, segment ids, lse, delta) cross the kernel
  boundary as (bh, 1, s) arrays in (1, 1, block) blocks: the form the
  Mosaic tiling rule accepts (a (1, block) block of a (bh, s) array
  breaks its sublane rule) at 8x sublane padding in HBM, against 128x
  for a lane-replicated (bh, s, 128) layout.

Ring/context-parallel attention (parallel/ring_attention.py) reuses
these kernels per shard and merges (out, lse) pairs in log-space.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Chosen because it compiles, not tuned (ROADMAP S6): the v5e compiler
# (jax 0.9.0 / libtpu 0.0.34) refuses the causal forward at (1024, 1024)
# once seq >= 2048 — 17.29-17.79 MB of scoped VMEM against the 16 MB
# limit, the causal mask's f32 intermediates — while (1024, 512),
# (512, 1024) and (512, 512) compile forward and backward at every
# shape in tests/test_tpu_compile.py, also with bias + segment ids at
# d=256. The rates once quoted here (33/42 TFLOP/s at 1024x1024) were
# measured on an earlier kernel body and another JAX; no rate has been
# measured for this one.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 512


def resolve_block_shapes(block_q, block_k):
    """Resolve block sizes: explicit args win; None falls to the
    ``flash_block_q``/``flash_block_k`` config flags (env
    ``PDTPU_FLASH_BLOCK_Q``/``_K`` — a microbench sweep winner applies
    without a code edit), flag 0 to the module defaults.
    Validated here so a typo'd env value fails naming the flag instead
    of as a Mosaic tiling error deep in kernel lowering. NOTE: like all
    shape-affecting knobs this is read at TRACE time — set the flag
    (or env) before the first jit compilation of the calling step;
    already-cached executables keep their block shapes."""
    from ..core.config import get_flag
    from ..core.errors import enforce

    if block_q is None:
        block_q = get_flag("flash_block_q") or DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = get_flag("flash_block_k") or DEFAULT_BLOCK_K
    for name, val in (("flash_block_q", block_q), ("flash_block_k", block_k)):
        enforce(isinstance(val, int) and val > 0 and val % 8 == 0,
                f"{name}: block size must be a positive multiple of 8 "
                f"(TPU sublane tiling), got {val!r}")
    return block_q, block_k


def default_interpret() -> bool:
    """Interpret the kernels only where there is no TPU to compile them
    for (the CPU tests); on a TPU they always lower through Mosaic."""
    return jax.devices()[0].platform == "cpu"


NEG_INF = -1e30
LANES = 128  # lane width for 1-d-per-row scratch (m/l/lse/delta)


def _causal_mask(s, qi, kj, block_q, block_k, offset):
    """Bottom-right-aligned causal mask (decode convention: with sq < sk
    the last query sees every key), matching the XLA fallback's
    ``tril(k=sk-sq)``. ``offset`` = sk_orig - sq_orig, static."""
    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_idx + offset >= k_idx, s, NEG_INF)


def _segment_mask(s, seg_q, seg_k):
    # seg_q: [block_q], seg_k: [block_k]
    return jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)


def _block_scores(q_ref, k_ref, bias_ref, segq_ref, segk_ref, qi, kj, *,
                  scale, causal, block_q, block_k, causal_offset):
    """Shared score assembly for the fwd/dq/dkv kernels: q·kᵀ (scaled),
    additive key bias, segment mask, causal mask — one definition so the
    three kernels can never desynchronize.

    The dot operands stay in the INPUT dtype (bf16 in → one MXU-native
    bf16×bf16 pass with f32 accumulation; the previous f32 upcast ran
    every kernel matmul at the ~1/8-rate f32 MXU path and capped the
    whole kernel at ~17% MFU). Softmax state and masks are f32. The
    scale is applied to the f32 scores, not the bf16 operand. Returns
    (q, k) UNSCALED in their native dtype, the scaled f32 scores, and
    ``masked`` — a (possibly traced) bool: can this tile contain
    NEG_INF scores? The kernels gate :func:`_zero_masked`'s per-element
    compare/select on it, and the causal mask itself runs only on
    diagonal-crossing tiles (a tile is fully visible when its last key
    index is within the FIRST query row's allowance). The kernel is
    VPU-bound (exp + reductions), so shaving mask ops off interior
    tiles is real time, not noise."""
    q = q_ref[0]
    kb = k_ref[0]
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    masked = bias_ref is not None or segq_ref is not None
    if bias_ref is not None:
        s = s + bias_ref[0, 0, :][None, :]
    if segq_ref is not None:
        s = _segment_mask(s, segq_ref[0, 0, :], segk_ref[0, 0, :])
    if causal:
        fully_visible = (kj + 1) * block_k - 1 <= qi * block_q + causal_offset
        s = jax.lax.cond(
            fully_visible, lambda t: t,
            lambda t: _causal_mask(t, qi, kj, block_q, block_k,
                                   causal_offset), s)
        if not masked:  # keep python True static; only upgrade False
            masked = jnp.logical_not(fully_visible)
    return q, kb, s, masked


def _maybe_zero_masked(p, s, masked):
    """Apply :func:`_zero_masked` only when the tile can actually hold
    masked scores. Three cases, two static: ``masked`` is python False
    for unmasked dense attention (no select at all) and python True
    when a bias/segment mask is statically present without causal
    (unconditional select, no dead cond); a traced bool on the causal
    path (cond skips the per-element compare/select on interior
    tiles)."""
    if masked is False:
        return p
    if masked is True:
        return _zero_masked(p, s)
    return jax.lax.cond(masked, lambda t: _zero_masked(t, s),
                        lambda t: t, p)


def _zero_masked(p, s):
    """Zero probabilities where the score was masked: with every score in
    a block at NEG_INF, exp(s - m) (or exp(s - lse)) is exp(0) = 1 —
    masked positions must contribute 0, not 1."""
    return jnp.where(s <= NEG_INF / 2, 0.0, p)


def _pad_seq(x, target, axis, value=0.0):
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _kj_clamp(causal, block_q, block_k, nk, offset):
    """Index clamp for K/V-side blocks in causal kernels: iterations
    past a q-row's last useful key block keep requesting the SAME block
    index, and Pallas's pipelining skips the HBM→VMEM DMA when the
    index does not change — the compute for those iterations is already
    gated off by ``run``, so without this the skipped upper-triangle
    tiles still paid their (dominant) K/V fetch bandwidth. Last useful
    kj for q row qi: floor(((qi+1)·bq + offset − 1)/bk), clamped to
    [0, nk−1]."""
    if not causal:
        return lambda kk, j: kk

    def clamp(kk, j):
        last = ((j + 1) * block_q + offset - 1) // block_k
        return jnp.minimum(kk, jnp.clip(last, 0, nk - 1))
    return clamp


def _qi_clamp(causal, block_q, block_k, nq, offset):
    """Mirror of :func:`_kj_clamp` for the dkv kernel's Q-side blocks:
    iterations before a key block's first useful q row re-request the
    first useful block. First useful qi for key block kj:
    max(0, floor((kj·bk − offset)/bq))."""
    if not causal:
        return lambda kk, j: kk

    def clamp(kk, j):
        first = jnp.clip((j * block_k - offset) // block_q, 0, nq - 1)
        return jnp.maximum(kk, first)
    return clamp


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                num_k_blocks: int, causal_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # causal: skip key blocks strictly above the (offset) diagonal
    run = (not causal) or (kj * block_k < (qi + 1) * block_q + causal_offset)

    @pl.when(run)
    def _step():
        _, _, s, masked = _block_scores(
            q_ref, k_ref, bias_ref, segq_ref, segk_ref,
            qi, kj, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
            causal_offset=causal_offset)
        vb = v_ref[0]
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = _maybe_zero_masked(jnp.exp(s - m_new[:, None]), s, masked)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        # p rounded to the input dtype for the MXU pass; accumulator f32
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        # (1, block_q) row store: sublane→lane relayout, Mosaic-supported
        lse_ref[0] = (m_scr[:, 0] + jnp.log(l))[None, :]


def _pad_all(q, k, v, bias, seg_q, seg_k, block_q, block_k):
    """Pad seq dims to whole blocks. Padded keys get a NEG_INF bias;
    padded q/k segment ids get distinct negative ids so they never
    match. Returns padded operands + the original (sq, sk)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sq_p = pl.cdiv(sq, block_q) * block_q
    sk_p = pl.cdiv(sk, block_k) * block_k
    if sq_p != sq or sk_p != sk:
        q = _pad_seq(q, sq_p, 2)
        k = _pad_seq(k, sk_p, 2)
        v = _pad_seq(v, sk_p, 2)
        if sk_p != sk:
            if bias is None:
                bias = jnp.zeros((b, sk), jnp.float32)
            bias = _pad_seq(bias, sk_p, 1, NEG_INF)
        if seg_q is not None:
            seg_q = _pad_seq(seg_q, sq_p, 1, -1)
            seg_k = _pad_seq(seg_k, sk_p, 1, -2)
    return q, k, v, bias, seg_q, seg_k, sq, sk


def _flash_fwd(q, k, v, bias, seg_q, seg_k, causal: bool,
               block_q: int, block_k: int, interpret: bool):
    block_q = min(block_q, q.shape[2])
    block_k = min(block_k, k.shape[2])
    q, k, v, bias, seg_q, seg_k, sq_orig, sk_orig = _pad_all(
        q, k, v, bias, seg_q, seg_k, block_q, block_k)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    bh = b * h
    nq = sq // block_q
    nk = sk // block_k

    q_r = q.reshape(bh, sq, d)
    k_r = k.reshape(bh, sk, d)
    v_r = v.reshape(bh, sk, d)

    ck = _kj_clamp(causal, block_q, block_k, nk, sk_orig - sq_orig)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, ck(kk, j), 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, ck(kk, j), 0)),
    ]
    args = [q_r, k_r, v_r]
    have_bias = bias is not None
    have_seg = seg_q is not None
    if have_bias:
        bias_r = jnp.broadcast_to(bias[:, None, :], (b, h, sk)).reshape(bh, 1, sk)
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda i, j, kk: (i, 0, ck(kk, j))))
        args.append(bias_r.astype(jnp.float32))
    if have_seg:
        segq_r = jnp.broadcast_to(seg_q[:, None, :], (b, h, sq)).reshape(bh, 1, sq)
        segk_r = jnp.broadcast_to(seg_k[:, None, :], (b, h, sk)).reshape(bh, 1, sk)
        in_specs.append(pl.BlockSpec((1, 1, block_q),
                                     lambda i, j, kk: (i, 0, j)))
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda i, j, kk: (i, 0, ck(kk, j))))
        args += [segq_r.astype(jnp.int32), segk_r.astype(jnp.int32)]

    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        b_ref = next(it) if have_bias else None
        sq_ref = next(it) if have_seg else None
        sk_ref = next(it) if have_seg else None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = it
        _fwd_kernel(q_ref, k_ref, v_ref, b_ref, sq_ref, sk_ref,
                    o_ref, lse_ref, m_scr, l_scr, acc_scr,
                    scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, num_k_blocks=nk,
                    causal_offset=sk_orig - sq_orig)

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            # lse as (bh, 1, sq) — see the module docstring
            pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    out = out.reshape(b, h, sq, d)[:, :, :sq_orig]
    lse = lse[:, 0, :].reshape(b, h, sq)[:, :, :sq_orig]
    return out, lse


# ---------------------------------------------------------------------------
# backward (two pallas passes, flash-attention-2 style)


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
               g_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int,
               num_k_blocks: int, causal_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    run = (not causal) or (kj * block_k < (qi + 1) * block_q + causal_offset)

    @pl.when(run)
    def _step():
        _, kb, s, masked = _block_scores(
            q_ref, k_ref, bias_ref, segq_ref, segk_ref,
            qi, kj, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
            causal_offset=causal_offset)
        vb = v_ref[0]
        g = g_ref[0]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        p = _maybe_zero_masked(jnp.exp(s - lse[:, None]), s, masked)
        dp = jax.lax.dot_general(g, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, segq_ref, segk_ref,
                g_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                num_q_blocks: int, causal_offset: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    run = (not causal) or (kj * block_k < (qi + 1) * block_q + causal_offset)

    @pl.when(run)
    def _step():
        q, _, s, masked = _block_scores(
            q_ref, k_ref, bias_ref, segq_ref, segk_ref,
            qi, kj, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
            causal_offset=causal_offset)
        vb = v_ref[0]
        g = g_ref[0]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        p = _maybe_zero_masked(jnp.exp(s - lse[:, None]), s, masked)  # [bq, bk]
        # dv += p^T g
        dv_scr[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # ds carries the scale here (q is unscaled); rounded to the
        # input dtype for the dk MXU pass
        ds = p * (dp - delta[:, None]) * scale  # [bq, bk]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, bias, seg_q, seg_k, causal, out, lse, g,
               block_q: int, block_k: int, interpret: bool, delta=None):
    block_q = min(block_q, q.shape[2])
    block_k = min(block_k, k.shape[2])
    if delta is None:
        delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    q, k, v, bias, seg_q, seg_k, sq_orig, sk_orig = _pad_all(
        q, k, v, bias, seg_q, seg_k, block_q, block_k)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    bh = b * h
    nq = sq // block_q
    nk = sk // block_k
    causal_offset = sk_orig - sq_orig

    # padded q rows: g/delta 0 and lse huge, so p=exp(s-lse)=0 — they
    # contribute nothing to dk/dv, and their dq rows are sliced off
    g = _pad_seq(g, sq, 2)
    lse = _pad_seq(lse, sq, 2, -NEG_INF)
    delta = _pad_seq(delta, sq, 2)

    q_r = q.reshape(bh, sq, d)
    k_r = k.reshape(bh, sk, d)
    v_r = v.reshape(bh, sk, d)
    g_r = g.reshape(bh, sq, d)
    lse_r = lse.reshape(bh, 1, sq)
    delta_r = delta.reshape(bh, 1, sq)

    have_bias = bias is not None
    have_seg = seg_q is not None
    bias_r = segq_r = segk_r = None
    if have_bias:
        bias_r = jnp.broadcast_to(bias[:, None, :], (b, h, sk)) \
            .reshape(bh, 1, sk).astype(jnp.float32)
    if have_seg:
        segq_r = jnp.broadcast_to(seg_q[:, None, :], (b, h, sq)) \
            .reshape(bh, 1, sq).astype(jnp.int32)
        segk_r = jnp.broadcast_to(seg_k[:, None, :], (b, h, sk)) \
            .reshape(bh, 1, sk).astype(jnp.int32)

    # ---- dq pass: grid (bh, nq, nk), K/V streamed on the inner dim;
    # causal iterations past the diagonal re-request the same block so
    # their DMA is skipped (see _kj_clamp)
    ck = _kj_clamp(causal, block_q, block_k, nk, causal_offset)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, ck(kk, j), 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, ck(kk, j), 0)),
    ]
    dq_args = [q_r, k_r, v_r]
    if have_bias:
        dq_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda i, j, kk: (i, 0, ck(kk, j))))
        dq_args.append(bias_r)
    if have_seg:
        dq_specs.append(pl.BlockSpec((1, 1, block_q),
                                     lambda i, j, kk: (i, 0, j)))
        dq_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda i, j, kk: (i, 0, ck(kk, j))))
        dq_args += [segq_r, segk_r]
    dq_specs += [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, j)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, j)),
    ]
    dq_args += [g_r, lse_r, delta_r]

    def dq_kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        b_ref = next(it) if have_bias else None
        sqr = next(it) if have_seg else None
        skr = next(it) if have_seg else None
        g_ref, lse_ref, delta_ref, dq_ref, dq_scr = it
        _dq_kernel(q_ref, k_ref, v_ref, b_ref, sqr, skr, g_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, scale=scale, causal=causal,
                   block_q=block_q, block_k=block_k, num_k_blocks=nk,
                   causal_offset=causal_offset)

    dq = pl.pallas_call(
        dq_kernel,
        name="flash_dq",
        grid=(bh, nq, nk),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*dq_args)

    # ---- dk/dv pass: grid (bh, nk, nq), Q/dO streamed on the inner
    # dim; causal iterations before a key block's first useful q row
    # re-request that first block (DMA skipped, see _qi_clamp)
    cq = _qi_clamp(causal, block_q, block_k, nq, causal_offset)
    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, cq(kk, j), 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0)),
    ]
    dkv_args = [q_r, k_r, v_r]
    if have_bias:
        dkv_specs.append(pl.BlockSpec((1, 1, block_k), lambda i, j, kk: (i, 0, j)))
        dkv_args.append(bias_r)
    if have_seg:
        dkv_specs.append(pl.BlockSpec((1, 1, block_q),
                                      lambda i, j, kk: (i, 0, cq(kk, j))))
        dkv_specs.append(pl.BlockSpec((1, 1, block_k),
                                      lambda i, j, kk: (i, 0, j)))
        dkv_args += [segq_r, segk_r]
    dkv_specs += [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, cq(kk, j), 0)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, cq(kk, j))),
        pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, cq(kk, j))),
    ]
    dkv_args += [g_r, lse_r, delta_r]

    def dkv_kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        b_ref = next(it) if have_bias else None
        sqr = next(it) if have_seg else None
        skr = next(it) if have_seg else None
        g_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = it
        _dkv_kernel(q_ref, k_ref, v_ref, b_ref, sqr, skr, g_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, scale=scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    num_q_blocks=nq, causal_offset=causal_offset)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_dkv",
        grid=(bh, nk, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_args)

    return (dq.reshape(b, h, sq, d)[:, :, :sq_orig],
            dk.reshape(b, h, sk, d)[:, :, :sk_orig],
            dv.reshape(b, h, sk, d)[:, :, :sk_orig])


# ---------------------------------------------------------------------------
# custom VJP plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_core(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
                interpret):
    out, _ = _flash_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q,
                        block_k, interpret)
    return out


def _flash_core_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q, block_k,
                    interpret):
    out, lse = _flash_fwd(q, k, v, bias, seg_q, seg_k, causal, block_q,
                          block_k, interpret)
    return out, (q, k, v, bias, seg_q, seg_k, out, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, bias, seg_q, seg_k, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, seg_q, seg_k, causal, out, lse, g,
                            block_q, block_k, interpret)
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
):
    """Flash attention over [b, h, s, d].

    - ``key_bias``: additive [b, s_k] (padding mask).
    - ``segment_ids`` / ``kv_segment_ids``: int [b, s] ragged-batch ids
      (LoD analog); attention is masked across segment boundaries. When
      only ``segment_ids`` is given it is used for both sides (self
      attention).
    - ``attn_mask``: a [b,1,1,s_k] additive mask is converted to a key
      bias; any other dense mask falls back to the XLA composition.
    - ``block_q``/``block_k``: None resolves the ``flash_block_q``/``_k``
      config flags then the module defaults — see
      :func:`resolve_block_shapes` (read at trace time).
    - ``return_lse``: also return the per-query logsumexp [b, h, s_q]
      (forward only — used by ring attention to merge shards).
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
            return _mask_fallback(q, k, v, mask, causal)
    seg_q = segment_ids
    seg_k = kv_segment_ids if kv_segment_ids is not None else segment_ids
    bias = None if key_bias is None else key_bias.astype(jnp.float32)
    if return_lse:
        return _flash_fwd(q, k, v, bias, seg_q, seg_k, causal,
                          block_q, block_k, interpret)
    return _flash_core(q, k, v, bias, seg_q, seg_k, causal,
                       block_q, block_k, interpret)


def _mask_fallback(q, k, v, attn_mask, causal):
    from .attention_scores import scores_mxu
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = scores_mxu(q, k, scale)
    s = s + attn_mask
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        s = jnp.where(cm, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
