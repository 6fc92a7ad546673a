"""Flash attention kernel vs XLA reference (interpret mode on CPU)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import flash_attention as fa


def _ref(q, k, v, causal=False, key_bias=None):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        s = jnp.where(cm, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand(b=1, h=2, s=128, d=32, sk=None, seed=0):
    rng = np.random.RandomState(seed)
    sk = sk or s
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, sk, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, sk, d).astype(np.float32))
    return q, k, v


def test_forward_matches_reference():
    q, k, v = _rand(s=128)
    out = fa.flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_forward_causal():
    q, k, v = _rand(s=128)
    out = fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, causal=True)),
                               atol=2e-5, rtol=2e-5)


def test_forward_with_key_bias_padding():
    q, k, v = _rand(s=128)
    bias = jnp.where(jnp.arange(128)[None, :] < 100, 0.0, -1e9)  # [1, sk]
    out = fa.flash_attention(q, k, v, key_bias=bias, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(q, k, v, key_bias=bias)),
                               atol=2e-5, rtol=2e-5)


def test_forward_uneven_blocks():
    # seq not a multiple of block: exercised via block > seq fallback
    q, k, v = _rand(s=96)
    out = fa.flash_attention(q, k, v, block_q=96, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_cross_attention_different_kv_len():
    q, k, v = _rand(s=64, sk=128)
    out = fa.flash_attention(q, k, v, block_q=32, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_gradients_match_reference():
    q, k, v = _rand(s=64, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_gradients_with_bias():
    q, k, v = _rand(s=64, d=16)
    bias = jnp.where(jnp.arange(64)[None, :] < 48, 0.0, -1e9)

    gf = jax.grad(lambda a, b, c: jnp.sum(
        fa.flash_attention(a, b, c, key_bias=bias, block_q=32, block_k=32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(_ref(a, b, c, key_bias=bias) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_attention_layer_uses_flash():
    """layers.attention with use_flash must agree with the XLA path."""
    import paddle_tpu as pt
    from paddle_tpu.layers import attention as A
    q, k, v = _rand(b=2, h=4, s=64, d=16)
    out_x = A.scaled_dot_product_attention(q, k, v, causal=True, use_flash=False)
    out_f = A.scaled_dot_product_attention(q, k, v, causal=True, use_flash=True)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_f), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# v2: segment ids, pallas backward, ragged shapes, lse merging


def _ref_seg(q, k, v, seg_q, seg_k, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    mask = seg_q[:, None, :, None] == seg_k[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        s = jnp.where(cm, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with every key masked -> zero them like the kernel does
    allmask = jnp.all(s <= -1e29, axis=-1, keepdims=True)
    p = jnp.where(allmask, 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_segment_ids_match_reference():
    q, k, v = _rand(b=2, s=128, d=32, seed=3)
    seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 32, axis=1).reshape(1, 128)
                      .repeat(2, axis=0))
    out = fa.flash_attention(q, k, v, segment_ids=seg, block_q=64, block_k=64)
    ref = _ref_seg(q, k, v, seg, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_segment_ids_causal_grads():
    q, k, v = _rand(b=1, s=128, d=32, seed=4)
    seg = jnp.asarray(np.repeat([0, 1], 64).reshape(1, 128))

    def loss_f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, segment_ids=seg,
                                          block_q=64, block_k=64) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(_ref_seg(q, k, v, seg, seg, causal=True) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-3)


def test_non_divisible_seq_pads():
    q, k, v = _rand(b=1, h=1, s=100, d=32, sk=84, seed=5)
    out = fa.flash_attention(q, k, v, block_q=64, block_k=64)
    ref = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    g = jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, block_q=64, block_k=64) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(_ref(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-3)


def test_many_k_blocks_streams():
    """seq >> block: K/V streamed across many grid steps (the VMEM-ceiling
    fix) — numerics must still match the dense reference."""
    q, k, v = _rand(b=1, h=1, s=64, d=32, sk=1024, seed=6)
    out = fa.flash_attention(q, k, v, block_q=64, block_k=128)
    ref = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_return_lse_matches_logsumexp():
    q, k, v = _rand(b=1, h=1, s=64, d=32, seed=7)
    out, lse = fa.flash_attention(q, k, v, block_q=32, block_k=32, return_lse=True)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jax.scipy.special.logsumexp(s, axis=-1)),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_key_bias_grads_pallas_backward():
    q, k, v = _rand(b=2, s=96, d=32, seed=8)
    bias = jnp.asarray(np.where(np.arange(96) < 70, 0.0, -1e30)[None]
                       .repeat(2, axis=0).astype(np.float32))

    def loss_f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, key_bias=bias,
                                          block_q=32, block_k=32) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(_ref(q, k, v, key_bias=bias) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-3)


def test_causal_bottom_right_alignment_decode():
    """sq < sk causal (decode suffix): last query sees all keys —
    bottom-right alignment, matching the XLA fallback convention."""
    q, k, v = _rand(b=1, h=1, s=32, d=32, sk=128, seed=9)
    out = fa.flash_attention(q, k, v, causal=True, block_q=32, block_k=64)
    ref = _ref(q, k, v, causal=True)  # _ref uses tril(k=sk-sq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_kv_segment_ids_requires_query_ids():
    from paddle_tpu.core.errors import EnforceError

    q, k, v = _rand(s=64, d=32)
    seg = jnp.zeros((1, 64), jnp.int32)
    with pytest.raises(EnforceError):
        fa.flash_attention(q, k, v, kv_segment_ids=seg)


def test_dense_mask_fallback_keeps_bias_and_segments():
    q, k, v = _rand(b=1, h=2, s=64, d=32, seed=10)
    dense = jnp.zeros((1, 2, 64, 64), jnp.float32)  # not key-bias-reducible
    bias = jnp.asarray(np.where(np.arange(64) < 40, 0.0, -1e30)[None].astype(np.float32))
    out = fa.flash_attention(q, k, v, attn_mask=dense, key_bias=bias)
    ref = _ref(q, k, v, key_bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# -- mixed-precision backward of the dense (XLA) attention path --------------


def test_scores_mxu_bf16_grads_close_to_f32():
    """The bf16-cotangent backward (ops/attention_scores.scores_mxu)
    must stay within bf16 rounding of the exact f32 gradient."""
    from paddle_tpu.ops.attention_scores import scores_mxu as _scores_mxu

    q, k, v = _rand(b=2, h=2, s=32, d=16, seed=3)

    def loss_via(score_fn, q, k):
        s = score_fn(q, k)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v) ** 2)

    scale = 1.0 / math.sqrt(q.shape[-1])
    exact = lambda q, k: jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    mxu = lambda q, k: _scores_mxu(q, k, scale)

    qb, kb = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    gq_ref, gk_ref = jax.grad(lambda a, b: loss_via(exact, a, b), (0, 1))(q, k)
    gq, gk = jax.grad(lambda a, b: loss_via(mxu, a, b), (0, 1))(qb, kb)
    np.testing.assert_allclose(np.asarray(gq, np.float32), np.asarray(gq_ref),
                               rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(gk, np.float32), np.asarray(gk_ref),
                               rtol=0.05, atol=0.05)
    # f32 inputs take the same path with zero rounding change
    gq32, gk32 = jax.grad(lambda a, b: loss_via(mxu, a, b), (0, 1))(q, k)
    np.testing.assert_allclose(np.asarray(gq32), np.asarray(gq_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gk32), np.asarray(gk_ref), rtol=1e-5)


def test_dense_attention_backward_has_no_f32_dots():
    """Regression pin for the MXU-rate bug the custom VJP fixes: a bf16
    SDPA train step must lower with every dot's inputs in bf16."""
    from op_test import find_dots
    from paddle_tpu.layers.attention import scaled_dot_product_attention

    q, k, v = _rand(b=2, h=2, s=32, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(scaled_dot_product_attention(q, k, v, causal=True) ** 2)

    txt = jax.jit(jax.grad(loss, (0, 1, 2))).lower(qb, kb, vb).as_text()
    dots = [d[1:3] for d in find_dots(txt) if d[0] == "dot_general"]
    assert len(dots) >= 4, f"regex no longer matches dot_general ops: {len(dots)}"
    bad = [d for d in dots if d[0].endswith('f32') and d[1].endswith('f32')]
    assert not bad, f"f32xf32 dots in attention backward: {bad}"


def test_bf16_kernel_close_to_f32_reference():
    """bf16 operands now feed the kernel dots directly (MXU-native);
    fwd and grads must stay within bf16 rounding of the f32 reference."""
    q, k, v = _rand(b=1, h=2, s=96, d=32, seed=7)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    out = fa.flash_attention(qb, kb, vb, causal=True, block_q=32, block_k=32)
    ref = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)

    def loss_f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          block_q=32, block_k=32) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(_ref(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_f, (0, 1, 2))(qb, kb, vb)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=0.1, atol=0.1)


def test_block_shape_flags_resolve():
    """block_q/block_k=None resolve the flash_block_* config flags
    (PDTPU_FLASH_BLOCK_*); unset flags (0) resolve to None, which leaves
    the blocks to the plan; explicit args always win. Asserts the
    RESOLVED values and the plan they give (output is block-size-
    invariant, so numerics alone cannot catch the flags being ignored)."""
    from paddle_tpu.core.config import get_flag, set_flag
    from paddle_tpu.core.errors import EnforceError

    assert fa.resolve_block_shapes(None, None) == (None, None)
    assert fa.resolve_block_shapes(256, None) == (256, None)
    old_q, old_k = get_flag("flash_block_q"), get_flag("flash_block_k")
    try:
        set_flag("flash_block_q", 64)
        set_flag("flash_block_k", 64)
        assert fa.resolve_block_shapes(None, None) == (64, 64)
        assert fa.resolve_block_shapes(128, 128) == (128, 128)  # args win
        # the plan takes explicit blocks as the DMA block, and the
        # compute tile follows them
        plan = fa.plan_blocks(128, 128, 32, block_q=64, block_k=64)
        assert (plan.block_q, plan.block_k, plan.tile_q, plan.tile_k) == (
            64, 64, 64, 64)
        # a typo'd value fails loudly, naming the flag
        set_flag("flash_block_k", 100)
        with pytest.raises(EnforceError, match="flash_block_k"):
            fa.resolve_block_shapes(None, None)
        # and the end-to-end path consumes the flag (numerics unchanged)
        set_flag("flash_block_k", 64)
        q, k, v = _rand(s=128)
        np.testing.assert_allclose(np.asarray(fa.flash_attention(q, k, v)),
                                   np.asarray(_ref(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
    finally:
        set_flag("flash_block_q", old_q)
        set_flag("flash_block_k", old_k)


# -- the plan's regimes: blocks chosen from the shape ------------------------

# (sq, sk): two 512-tiles with the triangle skipped; 896 = one tile of
# queries, two of 448 keys, nothing padded; cross attention with the
# bottom-right causal offset; a length no tile divides (padded keys are
# masked by index)
PLAN_SHAPES = [(1024, 1024), (896, 896), (384, 640), (200, 200)]


def _plan_case(sq, sk, mask, seed, dtype=jnp.float32, d=32):
    q, k, v = (x.astype(dtype) for x in _rand(b=1, h=2, s=sq, sk=sk, d=d,
                                             seed=seed))
    kw, ref = {"causal": True}, lambda q, k, v: _ref(q, k, v, causal=True)
    if mask == "key_bias":
        bias = jnp.asarray(np.where(np.arange(sk) < sk - 37, 0.0, -1e30)
                           [None].astype(np.float32))
        kw = {"key_bias": bias}
        ref = lambda q, k, v: _ref(q, k, v, key_bias=bias)
    elif mask == "segment_ids":
        seg_q = jnp.asarray((np.arange(sq) * 3 // sq)[None])
        seg_k = jnp.asarray((np.arange(sk) * 3 // sk)[None])
        kw = {"segment_ids": seg_q, "kv_segment_ids": seg_k}
        ref = lambda q, k, v: _ref_seg(q, k, v, seg_q, seg_k)
    return (q, k, v), kw, ref


@pytest.mark.parametrize("mask", ["causal", "key_bias", "segment_ids"])
@pytest.mark.parametrize("sq,sk", PLAN_SHAPES)
def test_planned_blocks_match_dense_reference(sq, sk, mask):
    """No blocks given: the plan chooses them. Forward and gradients
    against the dense f32 reference at this file's tolerances."""
    (q, k, v), kw, ref = _plan_case(sq, sk, mask, seed=sq + sk)
    np.testing.assert_allclose(np.asarray(fa.flash_attention(q, k, v, **kw)),
                               np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    gf = jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=2e-3, err_msg=f"d{name} mismatch")


def test_planned_blocks_bf16_scale_folded_into_still_operand():
    """d = 64: the scale 1/8 is a power of two and is folded into the
    operand a walk holds still (exact in bf16); forward and gradients
    hold test_bf16_kernel_close_to_f32_reference's limits."""
    (q, k, v), kw, ref = _plan_case(1024, 1024, "causal", seed=11, d=64)
    assert fa.plan_blocks(1024, 1024, 64, causal=True).fold_scale
    assert not fa.plan_blocks(1024, 1024, 32, causal=True).fold_scale
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(qb, kb, vb, **kw), np.float32),
        np.asarray(ref(q, k, v)), rtol=0.05, atol=0.05)
    gf = jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, **kw) ** 2), (0, 1, 2))(qb, kb, vb)
    gr = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) ** 2),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=0.1, atol=0.1)


# name -> (bh, sq, d): what one chip's kernels see in the benchmark's cells
CELL_SHAPES = {"gpt2m_train": (512, 1024, 64),
               "gpt2l_train_shard": (160, 1024, 64),
               "gpt2m_prefill": (256, 896, 64)}


@pytest.mark.parametrize("name", CELL_SHAPES)
def test_plan_for_cell_shapes(name):
    """The plan for each cell shape: the causal triangle is skipped where
    the sequence holds more than one tile (at 512-row tiles 3 of 4; the
    chip puts 256-row tiles, 10 of 16, 1.4x slower: PERF.md), 896 is not
    padded on either axis, a grid step holds whole heads and the walk's
    share is what the flash.plan span reports."""
    from paddle_tpu.core import profiler

    bh, s, d = CELL_SHAPES[name]
    plan = fa.plan_blocks(s, s, d, jnp.bfloat16, causal=True, bh=bh)
    assert (plan.sq_p, plan.sk_p) == (s, s)             # no padding
    assert plan.block_q == s and plan.block_k == s      # K/V resident
    assert plan.block_q % plan.tile_q == 0 and plan.block_k % plan.tile_k == 0
    assert bh % plan.heads == 0
    share = plan.tiles_run / plan.tiles_all
    if s == 1024:
        assert (plan.tile_q, plan.tile_k) == (512, 512)
        assert share == 0.75
    else:
        assert (plan.tile_q, plan.tile_k) == (896, 448)  # 896: one q tile
        assert share == 1.0
    # every attention traced leaves its plan in the program's span ring
    since = profiler.time.time_ns()
    q = jnp.zeros((1, 2, s, d), jnp.bfloat16)
    jax.eval_shape(lambda q: fa.flash_attention(q, q, q, causal=True), q)
    spans = [sp for sp in profiler.spans(since) if sp[0] == "flash.plan"]
    assert spans, "no flash.plan span recorded at trace time"
    ids = spans[-1][4]
    assert (ids["sq"], ids["sk"], ids["d"]) == (s, s, d)
    assert (ids["tile_q"], ids["tile_k"]) == (plan.tile_q, plan.tile_k)
    assert ids["tiles_run"] / ids["tiles_all"] == share


def test_causal_multiblock_interior_tiles():
    """seq spanning many blocks under causal: interior (fully visible)
    tiles take the mask-free fast path, diagonal tiles mask, above-
    diagonal tiles are skipped — fwd and grads must still match the
    dense reference exactly."""
    q, k, v = _rand(s=256, d=32, seed=5)

    def loss_fa(q, k, v):
        return (fa.flash_attention(q, k, v, causal=True, block_q=32,
                                   block_k=32) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref(q, k, v, causal=True) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, causal=True, block_q=32,
                                      block_k=32)),
        np.asarray(_ref(q, k, v, causal=True)), atol=2e-5, rtol=2e-5)
    g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
    # decode offset: sq < sk shifts the diagonal; interior fast path
    # must respect the offset
    q2, k2, v2 = _rand(s=64, sk=256, d=32, seed=6)
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q2, k2, v2, causal=True, block_q=32,
                                      block_k=32)),
        np.asarray(_ref(q2, k2, v2, causal=True)), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the projections' own layout: ``[b, s, h*d]`` operands (and a fused
# ``[b, s, 3*h*d]`` one) read and written in place, against the
# ``[b, h, s, d]`` path and the dense reference (what the chip's compiler
# makes of it: test_tpu_compile.py)

# heads of a call at each of CELL_SHAPES
CELL_HEADS = {"gpt2m_train": 16, "gpt2l_train_shard": 10, "gpt2m_prefill": 16}


def _heads_apart(x, h):
    b, s, width = x.shape
    return x.reshape(b, s, h, width // h).transpose(0, 2, 1, 3)


def _heads_together(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _ref_masked(q, k, v, causal, key_bias=None, seg=None):
    """Dense reference over [b, h, s, d] with every mask the kernels take."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    keep = jnp.ones(s.shape[-2:], jnp.bool_)
    if causal:
        keep = jnp.tril(keep, k=s.shape[-1] - s.shape[-2])
    keep = keep[None, None]
    if seg is not None:
        keep = keep & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    # a query with every key masked attends to nothing, as in the kernels
    p = jnp.where(keep.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# name -> (heads, d, dv, layout the plan must take, heads a lane group)
PACKED_SHAPES = {
    "d64_even_heads": (4, 64, 64, "bsd", 2),
    "d128": (2, 128, 128, "bsd", 1),
    "d32_four_a_group": (4, 32, 32, "bsd", 4),
    "d64_odd_heads_falls_back": (3, 64, 64, "bhsd", 0),
    "d192_dv128_falls_back": (2, 192, 128, "bhsd", 0),
}
PACKED_MASKS = ("causal", "key_bias", "segment_ids")


@pytest.mark.parametrize("mask", PACKED_MASKS)
@pytest.mark.parametrize("shape", PACKED_SHAPES)
def test_projection_layout_matches_heads_apart_and_dense(shape, mask):
    """A rank-3 ``[b, s, h*d]`` call against the same call on
    ``[b, h, s, d]`` operands and against the dense reference: forward and
    the three gradients, under each mask; ``flash.plan`` says which layout
    the kernels got and how many heads share a 128-lane group. Shapes the
    packed form does not fit (an odd count of 64-wide heads, 192-wide
    scores over 128-wide values) fall back inside the call and agree too
    (forward only at unequal widths: the backward raises there)."""
    from paddle_tpu.core import profiler

    h, d, dv, layout, lane_heads = PACKED_SHAPES[shape]
    b, s = 2, 192
    rng = np.random.RandomState(len(shape) + len(mask))
    q, k = (jnp.asarray(rng.randn(b, s, h * d), jnp.float32) for _ in "qk")
    v = jnp.asarray(rng.randn(b, s, h * dv), jnp.float32)
    kw, ref_kw = {"causal": mask == "causal"}, {}
    if mask == "key_bias":
        ref_kw["key_bias"] = kw["key_bias"] = jnp.where(
            jnp.arange(s)[None, :] < jnp.array([[150], [s]]), 0.0, -1e9)
    if mask == "segment_ids":
        kw["causal"] = True
        ref_kw["seg"] = kw["segment_ids"] = jnp.asarray(
            np.sort(rng.randint(0, 3, (b, s)), axis=1), jnp.int32)
    if d != dv:
        kw["scale"] = 0.1147

    def packed(q, k, v):
        return fa.flash_attention(q, k, v, num_heads=h, **kw)

    def apart(q, k, v):
        return _heads_together(fa.flash_attention(
            _heads_apart(q, h), _heads_apart(k, h), _heads_apart(v, h), **kw))

    def dense(q, k, v):
        scale = kw.get("scale", 1.0 / math.sqrt(d)) * math.sqrt(d)
        return _heads_together(_ref_masked(
            _heads_apart(q, h) * scale, _heads_apart(k, h),
            _heads_apart(v, h), kw["causal"], **ref_kw))

    since = profiler.time.time_ns()
    out = packed(q, k, v)
    ids = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert (ids["layout"], ids["lane_heads"]) == (layout, lane_heads)
    assert ids["heads"] % max(lane_heads, 1) == 0
    assert out.shape == (b, s, h * dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(apart(q, k, v)),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                               atol=3e-5, rtol=3e-5)
    if d != dv:
        return
    w = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    grads = [jax.grad(lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
             for f in (packed, apart, dense)]
    for name, gp, ga, gd in zip("qkv", *grads):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(ga), atol=5e-6,
                                   rtol=1e-5, err_msg=f"d{name} vs [b,h,s,d]")
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), atol=2e-4,
                                   rtol=1e-3, err_msg=f"d{name} vs dense")


@pytest.mark.parametrize("shape,seq,blocks", [
    ("d64_even_heads", 256, None), ("d64_even_heads", 200, None),
    ("d64_even_heads", 24, None), ("d64_even_heads", 104, None),
    ("d32_four_a_group", 40, None), ("d64_even_heads", 100, (16, 64)),
    ("d128", 128, None), ("d64_odd_heads_falls_back", 128, None)])
def test_fused_projection_matches_its_three_parts(shape, seq, blocks):
    """q, k and v side by side in one ``[b, s, 3*h*d]`` array (a fused
    projection's output, passed as ``q`` alone) against the three passed
    apart: the kernels pick each part by a lane-block offset, a padded
    sequence (200 -> 256) pads the one array, and the gradient comes back
    as one array of the same form. Queries pad to 8 rows and keys to 16,
    so at 24, 40 or 104 rows (a short prompt's prefill), and under
    explicit blocks of two sizes, the key axis is the longer: the one
    array has rows for both, zeros beyond the sequence (a key block read
    past the array's end holds whatever lies there, and 0 x NaN is NaN)."""
    h, d, _, layout, _ = PACKED_SHAPES[shape]
    rng = np.random.RandomState(seq)
    qkv = jnp.asarray(rng.randn(2, seq, 3 * h * d), jnp.float32)

    kw = dict(causal=True, num_heads=h)
    if blocks:
        kw.update(block_q=blocks[0], block_k=blocks[1])

    def fused(x):
        return fa.flash_attention(x, **kw)

    def apart(x):
        return fa.flash_attention(*jnp.split(x, 3, axis=-1), **kw)

    out = fused(qkv)
    assert out.shape == (2, seq, h * d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(apart(qkv)),
                               atol=1e-6, rtol=1e-6)
    w = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    g_fused, g_apart = (jax.grad(lambda x: (f(x) * w).sum())(qkv)
                        for f in (fused, apart))
    assert g_fused.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_apart),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("name", CELL_SHAPES)
def test_packed_plan_for_cell_shapes(name):
    """What the plan gives the benchmark's cells in the projections'
    layout: pairs of 64-wide heads a step (one 128-lane block of one batch
    row), the tile walk of the ``[b, h, s, d]`` plan, and the fall back
    where a head would lie astride a lane group's edge."""
    bh, s, d = CELL_SHAPES[name]
    old = fa.plan_blocks(s, s, d, jnp.bfloat16, causal=True, bh=bh)
    new = fa.plan_blocks(s, s, d, jnp.bfloat16, causal=True, bh=bh,
                         num_heads=CELL_HEADS[name])
    assert (new.layout, new.lane_heads, new.heads) == ("bsd", 2, 2)
    assert (old.layout, old.lane_heads) == ("bhsd", 0)
    assert new[:7] == old[:7] and new.tiles_run == old.tiles_run
    assert fa.lane_heads(64, 64, 25) == 0      # GPT-2 XL: an odd head count
    assert fa.lane_heads(64, 64, 5) == 0       # 20 heads under tp4
    assert fa.lane_heads(192, 128, 64) == 0    # latent attention
    assert fa.lane_heads(256, 256, 3) == 1
    assert fa.lane_heads(64, 64, None) == 0    # a [b, h, s, d] call


def test_stacked_block_at_gpt2_medium_shape_records_only_bsd_plans():
    """Two scan-stacked layers at gpt2-medium's widths (d 1024, 16 heads
    of 64, sequence 1024, remat, ``jax.grad``), traced and not run: every
    attention the block traces hands the kernels the fused projection as
    it lies, and the prefill block too."""
    import paddle_tpu as pt
    from paddle_tpu.core import profiler
    from paddle_tpu.layers import stacked

    d, inner, heads, seq, layers = 1024, 4096, 16, 1024, 2

    def net(x):
        stack = stacked.encoder_stack_params(layers, d, inner)
        y = stacked.apply_stacked(x, stack, stacked.make_encoder_block,
                                  num_heads=heads, use_flash=True,
                                  causal=True, remat=True)
        one = {k: v[0] for k, v in stack.items()}
        z, (k, v) = stacked.prefill_block(x, one, heads, use_flash=True)
        assert k.shape == v.shape == x.shape
        return {"loss": jnp.mean(jnp.square(y)) + jnp.mean(z)}

    prog = pt.build(net)
    x = jax.ShapeDtypeStruct((2, seq, d), jnp.float32)
    params = jax.eval_shape(
        lambda key: prog.init(key, x=np.zeros((1, 8, d), np.float32))[0],
        jax.random.PRNGKey(0))
    since = profiler.time.time_ns()
    jax.eval_shape(jax.grad(
        lambda p, x: prog.apply(p, {}, training=True, x=x)[0]["loss"]),
        params, x)
    plans = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"]
    assert len(plans) >= 2
    assert {(p["layout"], p["lane_heads"], p["heads"], p["sq"], p["d"])
            for p in plans} == {("bsd", 2, 2, seq, 64)}


@pytest.mark.parametrize("axes", [{"dp": 4, "tp": 2}, {"dp": 8, "tp": 1},
                                  {"dp": 2, "tp": 4}, {"dp": 1, "tp": 8}],
                         ids=lambda a: "dp%dtp%d" % (a["dp"], a["tp"]))
def test_flash_sdpa_shards_the_projection_layout(axes):
    """Under a Trainer's mesh ``flash_sdpa`` runs the kernel per shard:
    batch rows over the data axes and whole heads of the minor dimension
    over ``tp``, a fused projection cut into its three parts first so
    that a shard's lanes are its heads of each. Four rows over dp 8 and
    four heads over tp 8 stay whole; one head a shard (tp 4) falls back
    to ``[b, h, s, d]`` inside the call. All agree with one device."""
    import paddle_tpu as pt
    from paddle_tpu.framework import mesh_mode
    from paddle_tpu.layers.attention import flash_sdpa

    b, s, h, d = 4, 128, 4, 64
    rng = np.random.RandomState(3)
    qkv = jnp.asarray(rng.randn(b, s, 3 * h * d), jnp.float32)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    bias = jnp.where(jnp.arange(s)[None, :] < 100, 0.0, -1e9) * jnp.ones((b, 1))

    def fused(x, bias=None):
        return flash_sdpa(x, None, None, True, key_bias=bias, num_heads=h)

    one = flash_sdpa(q, k, v, True, key_bias=bias, num_heads=h)
    grad_one = jax.grad(lambda x: fused(x).sum())(qkv)
    with mesh_mode(pt.make_mesh(axes)):
        apart = jax.jit(lambda q, k, v, bias: flash_sdpa(
            q, k, v, True, key_bias=bias, num_heads=h))(q, k, v, bias)
        whole = jax.jit(fused)(qkv, bias)
        grad = jax.jit(jax.grad(lambda x: fused(x).sum()))(qkv)
    np.testing.assert_allclose(np.asarray(apart), np.asarray(one), atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(one), atol=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_one),
                               atol=2e-6)


@pytest.mark.parametrize("case", ["self", "self_fused_padding_mask", "cross"])
def test_multi_head_attention_hands_the_kernel_its_projections(case):
    """``multi_head_attention(use_flash=True)`` gives the kernels q, k, v
    as its projections leave them (no head split; 4 heads of 32 share a
    lane group) and agrees with the dense path on the same parameters."""
    import paddle_tpu as pt
    from paddle_tpu.core import profiler
    from paddle_tpu.layers import attention as A

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 64, 128), jnp.float32)
    mem = jnp.asarray(rng.randn(2, 96, 128), jnp.float32)
    mask = A.padding_mask(jnp.asarray(rng.randint(0, 3, (2, 64))), pad_id=0)

    def net(use_flash):
        def fn(x, mem):
            if case == "cross":
                y = A.multi_head_attention(x, mem, num_heads=4,
                                           use_flash=use_flash, name="a")
            else:
                fused = case != "self"
                y = A.multi_head_attention(
                    x, num_heads=4, causal=not fused, fuse_qkv=fused,
                    attn_mask=mask if fused else None, use_flash=use_flash,
                    name="a")
            return {"y": y}
        return pt.build(fn)

    params, _ = net(False).init(jax.random.PRNGKey(0), x=x, mem=mem)
    dense = net(False).apply(params, {}, x=x, mem=mem)[0]["y"]
    since = profiler.time.time_ns()
    flash = net(True).apply(params, {}, x=x, mem=mem)[0]["y"]
    plans = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"]
    assert [(p["layout"], p["lane_heads"]) for p in plans] == [("bsd", 4)]
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("shape", ["d64_even_heads",
                                   "d64_odd_heads_falls_back"])
def test_projection_layout_lse_and_dense_mask(shape):
    """What else a ``[b, s, h*d]`` call may ask for: ``return_lse`` (ring
    attention's merge) gives ``[b, h, s]`` as the ``[b, h, s, d]`` call
    does, and a dense ``attn_mask`` still takes the XLA composition, with
    its warning, and comes back in the caller's layout."""
    h, d, _, _, _ = PACKED_SHAPES[shape]
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(2, 128, h * d), jnp.float32)
               for _ in "qkv")
    apart = [_heads_apart(x, h) for x in (q, k, v)]
    out, lse = fa.flash_attention(q, k, v, causal=True, num_heads=h,
                                  return_lse=True)
    out4, lse4 = fa.flash_attention(*apart, causal=True, return_lse=True)
    assert lse.shape == (2, h, 128)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse4), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_heads_together(out4)), atol=1e-6)
    dense = jnp.asarray(np.where(rng.rand(2, 1, 128, 128) < 0.8, 0.0, -1e9),
                        jnp.float32)
    with pytest.warns(UserWarning, match="dense attn_mask"):
        masked = fa.flash_attention(q, k, v, attn_mask=dense, num_heads=h)
    with pytest.warns(UserWarning, match="dense attn_mask"):
        masked4 = fa.flash_attention(*apart, attn_mask=dense)
    np.testing.assert_allclose(np.asarray(masked),
                               np.asarray(_heads_together(masked4)), atol=1e-6)


# the plans PR 40's tree gave these calls (the GPT cells' fused projection at
# both widths, the serve cells' prefill, latent attention's 192 / 128, a
# streamed call), field for field: ``window`` is a new last field, 0 for them
@pytest.mark.parametrize("kw,want", [
    (dict(sq=1024, sk=1024, d=64, causal=True, bh=32 * 16, num_heads=16),
     (1024, 1024, 64, 1024, 1024, 512, 512, 2, 1024, 1024, True, True, 3, 4,
      64, "bsd", 2, "fused")),
    (dict(sq=1024, sk=1024, d=64, causal=True, bh=16 * 10, num_heads=10),
     (1024, 1024, 64, 1024, 1024, 512, 512, 2, 1024, 1024, True, True, 3, 4,
      64, "bsd", 2, "fused")),
    (dict(sq=896, sk=896, d=64, causal=True, bh=16 * 16, num_heads=16),
     (896, 896, 64, 896, 896, 896, 448, 2, 896, 896, True, True, 2, 2, 64,
      "bsd", 2, "fused")),
    (dict(sq=1984, sk=1984, d=192, dv=128, causal=True, bh=8 * 64, scale=0.1),
     (1984, 1984, 192, 2048, 2048, 512, 512, 1, 2048, 2048, True, False, 10,
      16, 128, "bhsd", 0, "split")),
    (dict(sq=4096, sk=4096, d=128, causal=True, bh=8),
     (4096, 4096, 128, 1024, 1024, 512, 512, 2, 4096, 4096, True, False, 36,
      64, 128, "bhsd", 0, "split"))],
    ids=["gpt2m_train", "gpt2l_train", "gpt2m_prefill", "k25_prefill",
         "streamed"])
def test_a_call_without_a_window_plans_as_it_did(kw, want):
    """... ``rot`` (PR 43) the next, 0 for them too, ``group`` (PR 45) 1 for a
    call that gives no key/value head count, and ``tiles_written`` (PR 47)
    the newest: every tile run where a resident walk is within ``UNROLL``
    (the GPT cells'), none of latent attention's 16-tile walk, the tiles of
    the blocks below the diagonal where both sequences stream."""
    plan = fa.plan_blocks(dtype=jnp.bfloat16, **kw)
    assert tuple(plan)[:-1] == want + (0, 0, 1)
    assert fa.FlashPlan._fields[-4:] == ("window", "rot", "group",
                                         "tiles_written")
    resident = (plan.sq_p, plan.sk_p) == (plan.block_q, plan.block_k)
    assert plan.tiles_written == (
        24 if not resident else
        plan.tiles_run if plan.tiles_all <= fa.UNROLL else 0)


# -- a rotary pair: a second score operand in the projections' layout (PR 43) --------


def _rot_case(rows, heads, dtype, d=128, r=64, b=1, seed=0):
    """``q, k, v [b, rows, heads * d]``, ``q_rot [b, rows, heads * r]`` and
    ``k_rot [b, rows, r]`` in ``dtype``, and the dense float64 attention
    over them: head ``h``'s scores are ``q_h . k_h + q_rot_h . k_rot``."""
    rng = np.random.RandomState(seed)
    q, k, v, q_rot, k_rot = (
        jnp.asarray(rng.randn(b, rows, w).astype(np.float32)).astype(dtype)
        for w in (heads * d, heads * d, heads * d, heads * r, r))
    scale = 0.1147                              # k25's: not a power of two

    def apart(x, width):        # [b, h, rows, width]
        return np.asarray(x, np.float64).reshape(
            b, rows, -1, width).transpose(0, 2, 1, 3)

    qh, kh, vh, qrh = apart(q, d), apart(k, d), apart(v, d), apart(q_rot, r)
    kr = np.asarray(k_rot, np.float64).transpose(0, 2, 1)
    seen = np.tril(np.ones((rows, rows), bool))
    want = np.empty((b, rows, heads, d))
    for h in range(heads):      # a head at a time: [rows, rows] of float64
        s = (qh[:, h] @ kh[:, h].transpose(0, 2, 1) + qrh[:, h] @ kr) * scale
        s = np.where(seen, s, -np.inf)
        prob = np.exp(s - s.max(-1, keepdims=True))
        want[:, :, h] = (prob / prob.sum(-1, keepdims=True)) @ vh[:, h]
    return (q, k, v, q_rot, k_rot), scale, want.reshape(b, rows, heads * d)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("heads", [4, 64])
@pytest.mark.parametrize("rows", [200, 512, 1984])
def test_forward_with_a_rotary_pair_matches_dense(rows, heads, dtype):
    """Latent attention's widths, 128 + 64 over 128-wide values, causal,
    in the projections' own layout: a ragged sequence, whole tiles and the
    cell's 1,984 rows (loops traced, one head a step, the rotary part
    picked out of its lane group by the grid index); 4 heads a step where
    the plan groups them."""
    from paddle_tpu.core import profiler

    (q, k, v, q_rot, k_rot), scale, want = _rot_case(rows, heads, dtype)
    since = profiler.time.time_ns()
    got = fa.flash_attention(q, k, v, causal=True, scale=scale,
                             num_heads=heads, q_rot=q_rot, k_rot=k_rot)
    assert got.shape == q.shape and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)
    plan = [s[4] for s in profiler.spans(since) if s[0] == "flash.plan"][-1]
    assert (plan["layout"], plan["rot"], plan["d"], plan["dv"]) == (
        "bsd", 64, 128, 128)


def test_rotary_pair_plan_backward_and_fallback():
    """The cell's call (8 x 64 heads x 1,984, bfloat16) plans as the
    192-wide call did (the same blocks, tiles and causal skip), in ``bsd``
    with one head a step and ``rot`` 64; the backward raises; widths that
    fill no lane group go through the ``[b, h, s, d + r]`` form and agree
    with the dense reference."""
    plan = fa.plan_blocks(1984, 1984, 128, jnp.bfloat16, causal=True,
                          bh=8 * 64, scale=0.1147, num_heads=64, rot=64)
    old = fa.plan_blocks(1984, 1984, 192, jnp.bfloat16, causal=True,
                         bh=8 * 64, dv=128, scale=0.1147)
    assert (plan.layout, plan.lane_heads, plan.rot, plan.heads) == (
        "bsd", 1, 64, 1)
    assert (plan.tiles_run, plan.tiles_all) == (10, 16)
    assert plan[3:14] == old[3:14] and (old.layout, old.rot) == ("bhsd", 0)
    # the rows such a call is padded to inside, for a caller that can pad
    # what its products take instead (layers/latent.mla_prefill)
    assert [fa.padded_rows(s) for s in (37, 200, 1984, 2048, 2100)] == [
        40, 256, 2048, 2048, 3072]
    assert fa.rot_lane_heads(128, 128, 64, 64) == 2
    assert fa.rot_lane_heads(128, 128, 3, 64) == 0     # an odd count of 64s
    assert fa.rot_lane_heads(64, 64, 64, 32) == 0      # heads that share lanes

    (q, k, v, q_rot, k_rot), scale, _ = _rot_case(128, 2, jnp.float32)
    with pytest.raises(NotImplementedError, match="rotary pair"):
        jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, scale=scale, num_heads=2, q_rot=q_rot,
            k_rot=k_rot).sum())(q)

    from paddle_tpu.core import profiler
    (q, k, v, q_rot, k_rot), scale, want = _rot_case(37, 3, jnp.float32,
                                                     d=8, r=8)
    since = profiler.time.time_ns()
    with jax.default_matmul_precision("highest"):
        got = fa.flash_attention(q, k, v, causal=True, scale=scale,
                                 num_heads=3, q_rot=q_rot, k_rot=k_rot)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=2e-5)
    plan = [s[4] for s in profiler.spans(since) if s[0] == "flash.plan"][-1]
    assert (plan["layout"], plan["rot"], plan["d"]) == ("bhsd", 0, 16)


# -- grouped heads, with and without a window and a query offset (PR 45) ----------------


def _grouped_dense(q, k, v, heads, kv_heads, window, offset):
    """Dense masked softmax in float64, key head ``h // group`` indexed and
    never repeated: ``q [b, sq, heads * d]``, ``k, v [b, sk, kv_heads * d]``;
    query ``i`` sees keys ``j <= i + offset`` (and ``> i + offset - window``)."""
    b, sq, _ = q.shape
    sk, d = k.shape[1], q.shape[-1] // heads
    q4 = np.asarray(q, np.float64).reshape(b, sq, heads, d)
    k4 = np.asarray(k, np.float64).reshape(b, sk, kv_heads, d)
    v4 = np.asarray(v, np.float64).reshape(b, sk, kv_heads, d)
    r, c = np.arange(sq)[:, None] + offset, np.arange(sk)[None, :]
    keep = c <= r
    if window:
        keep &= c > r - window
    out = np.zeros((b, sq, heads, d))
    for h in range(heads):
        s = np.einsum("bqd,bkd->bqk", q4[:, :, h], k4[:, :, h * kv_heads // heads])
        s = np.where(keep, s / np.sqrt(d), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, h] = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                                 v4[:, :, h * kv_heads // heads])
    return out.reshape(b, sq, heads * d)


@pytest.mark.parametrize("heads,kv_heads,d", [(48, 8, 128), (12, 4, 64),
                                               (6, 1, 128), (4, 4, 64)],
                         ids=["48_on_8", "12_on_4_of_64", "6_on_1",
                              "4_on_4_of_64"])
@pytest.mark.parametrize("sq,sk,window,offset", [
    (128, 128, 0, None), (128, 384, 96, None), (128, 1280, 0, 300),
    (256, 1280, 96, 517)],
    ids=["causal", "window", "offset", "window_and_offset"])
def test_grouped_heads_are_the_dense_composition(heads, kv_heads, d, sq, sk,
                                                 window, offset):
    """48/8, 12/4 and 6/1 heads, rank-3 (in place where a head is its own
    lane group, through ``[b, h, s, d]`` where two share one) and rank-4,
    under the causal rule, a window, a traced query offset (a later piece
    of a prompt against the whole cache) and both: the kernel reads key head
    ``h // group`` where the cache holds it."""
    from paddle_tpu.core import profiler

    b = 1 if heads == 48 else 2
    rng = np.random.RandomState(heads + sq + window)
    q, k, v = (jnp.asarray(rng.randn(b, s, n * d), jnp.float32)
               for s, n in ((sq, heads), (sk, kv_heads), (sk, kv_heads)))
    want = _grouped_dense(q, k, v, heads, kv_heads, window,
                          sk - sq if offset is None else offset)
    since = profiler.time.time_ns()
    call = jax.jit(lambda q, k, v, off: fa.flash_attention(
        q, k, v, causal=True, num_heads=heads, kv_heads=kv_heads,
        window=window, q_offset=off))
    got = call(q, k, v, None if offset is None else jnp.int32(offset))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    plan = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert plan["kv_heads"] == kv_heads and plan["window"] == window
    assert plan["q_offset"] == (offset is not None)
    # two 64-wide heads share a lane group: in place only where none is shared
    assert plan["layout"] == ("bsd" if d == 128 or heads == kv_heads else "bhsd")
    apart = lambda x, n: x.reshape(b, -1, n, d).transpose(0, 2, 1, 3)
    got4 = fa.flash_attention(apart(q, heads), apart(k, kv_heads),
                              apart(v, kv_heads), causal=True, window=window,
                              q_offset=offset)
    np.testing.assert_allclose(
        np.asarray(got4.transpose(0, 2, 1, 3).reshape(b, sq, heads * d)), want,
        atol=2e-5)


def test_a_grouped_step_holds_the_key_heads_its_query_heads_read():
    """A step's query heads lie in one group or are whole groups. At the
    long-document cell's shapes (48 on 8 of 128, pieces of 2,048 over a
    4,096-key window and over a 33,792-key cache) a piece's queries are
    resident and a step holds one head, its group's key head streaming past
    it in 1,024-key blocks; the window's walk skips the tiles behind it."""
    for sk, window in ((6144, 4096), (33792, 0)):
        plan = fa.plan_blocks(2048, sk, 128, jnp.bfloat16, causal=True,
                              bh=8 * 48, num_heads=48, window=window, group=6)
        assert (plan.layout, plan.heads, plan.group) == ("bsd", 1, 6)
        assert (plan.block_q, plan.block_k, plan.tile_q, plan.tile_k) == (
            2048, 1024, 512, 512)
    # a 512-row query tile reaches 4,096 + 511 keys back: 9 tiles of 12
    windowed = fa.plan_blocks(2048, 6144, 128, jnp.bfloat16, causal=True,
                              bh=8 * 48, num_heads=48, window=4096, group=6)
    assert (windowed.tiles_run, windowed.tiles_all) == (4 * 9, 4 * 12)
    # shorter queries leave room for more heads a step: never astride a group
    short = fa.plan_blocks(512, 4608, 128, jnp.bfloat16, causal=True,
                           bh=8 * 48, num_heads=48, window=4096, group=6)
    assert short.heads in (2, 3, 6) and 6 % short.heads == 0
    assert not fa._in_groups(4, 6) and fa._in_groups(3, 6) and fa._in_groups(12, 6)


def test_grouped_heads_and_a_query_offset_are_forward_only():
    from paddle_tpu.core.errors import EnforceError

    q, k = jnp.zeros((1, 128, 4 * 128)), jnp.zeros((1, 128, 2 * 128))
    with pytest.raises(EnforceError, match="query offset"):
        fa.flash_attention(q, k, k, causal=False, num_heads=4, kv_heads=2,
                           q_offset=0)
    with pytest.raises(EnforceError, match="multiple"):
        fa.flash_attention(q, jnp.zeros((1, 128, 3 * 128)),
                           jnp.zeros((1, 128, 3 * 128)), causal=True,
                           num_heads=4, kv_heads=3)
    loss = lambda q: fa.flash_attention(q, k, k, causal=True, num_heads=4,
                                        kv_heads=2).sum()
    with pytest.raises(Exception):
        jax.grad(loss)(q)


# -- a streamed forward walk written out; one trace a walk (PR 47) ------------------


@pytest.fixture
def quarter_scale(monkeypatch):
    """The plan's constants at a quarter of their size, so that a streamed
    call with key blocks of several tiles is seconds on the CPU: tiles of
    128, 512 resident rows, 256-key blocks (an explicit small ``block_k``
    would make the tile follow the block, one tile a block)."""
    monkeypatch.setattr(fa, "TILE", 128)
    monkeypatch.setattr(fa, "RESIDENT", 512)
    monkeypatch.setattr(fa, "STREAM_BLOCK", 256)


def _two_on_one(sq, sk, seed):
    """``q [1, sq, 2 * 128]``, ``k, v [1, sk, 128]``: two query heads on one
    key head, float32."""
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(1, s, n * 128), jnp.float32)
                 for s, n in ((sq, 2), (sk, 1), (sk, 1)))


def _as_laid_out(layout, q, k, v, **kw):
    """The call over ``[b, s, h * d]`` operands or over ``[b, h, s, d]``
    ones; the output ``[b, s, h * d]`` either way."""
    if layout == "bsd":
        return fa.flash_attention(q, k, v, causal=True, num_heads=2,
                                  kv_heads=1, **kw)
    apart = lambda x, n: x.reshape(1, -1, n, 128).transpose(0, 2, 1, 3)
    out = fa.flash_attention(apart(q, 2), apart(k, 1), apart(v, 1),
                             causal=True, **kw)
    return out.transpose(0, 2, 1, 3).reshape(q.shape)


@pytest.mark.parametrize("layout,step_scores,form,written", [
    ("bsd", 1 << 17, "blocks", 36), ("bhsd", None, "blocks", 36),
    ("bsd", None, "interior", 16)],
    ids=["bsd_head_a_step", "bhsd_heads_looped", "bsd_two_heads_a_step"])
def test_a_windowed_piece_writes_out_every_key_block(
        quarter_scale, monkeypatch, layout, step_scores, form, written):
    """A piece of 512 queries on 2,048 keys under a 1,024-key window, a
    static offset and a key bias (the long-document cell's sliding call at a
    quarter, with two more key blocks behind it): eight key blocks of two
    tiles past four query tiles. Blocks 0 and 1 lie wholly behind the
    window and emit nothing, block 2 is cut by its edge, 3 has one tile cut
    and its others plain, 4 and 5 are interior and share a body, 6 and 7
    are the diagonal's. All 36 tiles run written out, in both layouts (a
    packed step's head a python iteration, a ``[b, h, s, d]`` step's two
    heads a traced loop round written-out tiles), against the dense
    reference; a packed step of two heads would hold 56 bodies, over
    ``WRITTEN``, and writes out the interior blocks' 16 tiles beside the
    loops."""
    if step_scores:     # a step of one head, as the cell's full-size one is
        monkeypatch.setattr(fa, "STEP_SCORES", step_scores)
    sq, sk, window = 512, 2048, 1024
    q, k, v = _two_on_one(sq, sk, 7)
    bias = jnp.where(jnp.arange(sk) < 700, -1e9, 0.0)[None].astype(jnp.float32)
    plan = fa.plan_blocks(sq, sk, 128, jnp.float32, causal=True, bh=2,
                          num_heads=2 if layout == "bsd" else None,
                          window=window, group=2)
    assert (plan.block_q, plan.block_k, plan.tile_q, plan.tile_k) == (
        512, 256, 128, 128)
    assert (plan.tiles_written, plan.tiles_run, plan.tiles_all) == (
        written, 36, 64)
    assert fa._written(plan, True)[0] == form
    assert plan.heads == (1 if step_scores else 2)
    inside = [kb for kb in range(8) if fa._interior(0, kb, plan, sk - sq)]
    runs = [kb for kb in range(8) if fa._block_runs(
        0, kb, fa._Walk(plan, 1.0, sk - sq, True, False))]
    assert (inside, runs) == ([4, 5], [2, 3, 4, 5, 6, 7])
    got = _as_laid_out(layout, q, k, v, window=window, key_bias=bias)
    # the reference takes the bias as keys a query may not see
    want = _grouped_dense(q, k[:, 700:], v[:, 700:], 2, 1, window, sk - sq - 700)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("layout", ["bsd", "bhsd"])
@pytest.mark.parametrize("piece", [0, 1, 2], ids=["first", "middle", "last"])
def test_a_piece_below_a_traced_diagonal_is_written_out(quarter_scale, layout,
                                                        piece):
    """A piece of 512 queries against a cache of 1,536 keys in six blocks,
    the diagonal where a traced scalar says: a key block wholly below the
    first query's diagonal runs the one written-out body of eight plain
    tiles, the two blocks the diagonal crosses run the loops, the blocks
    past it nothing. The plan counts the last piece: 32 written of 42 run
    of 48."""
    sq, sk = 512, 1536
    q, k, v = _two_on_one(sq, sk, 11 + piece)
    plan = fa.plan_blocks(sq, sk, 128, jnp.float32, causal=True, bh=2,
                          num_heads=2 if layout == "bsd" else None, group=2,
                          q_offset=True)
    assert (plan.tiles_written, plan.tiles_run, plan.tiles_all) == (32, 42, 48)
    assert fa._written(plan, False)[0] == "interior"
    got = jax.jit(lambda q, k, v, off: _as_laid_out(
        layout, q, k, v, q_offset=off))(q, k, v, jnp.int32(piece * sq))
    want = _grouped_dense(q, k, v, 2, 1, 0, piece * sq)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_plan_counts_the_tiles_it_writes_out():
    """``tiles_written`` beside ``tiles_run`` at the long-document cell's
    two calls (48 heads on 8 of 128; 2,048 queries): every tile of the
    window's piece on its 6,144 keys, and of the 33,792-key cache the 31
    blocks below the last piece's diagonal. A resident walk over ``UNROLL``
    tiles, a streamed block of one tile and a walk over ``WRITTEN`` bodies
    write nothing out; ``flash.plan`` carries the count."""
    from paddle_tpu.core import profiler

    cell = dict(d=128, dtype=jnp.bfloat16, causal=True, bh=8 * 48,
                num_heads=48, group=6)
    window = fa.plan_blocks(2048, 6144, window=4096, **cell)
    assert (window.tiles_written, window.tiles_run, window.tiles_all) == (
        36, 36, 48)
    full = fa.plan_blocks(2048, 33792, q_offset=True, **cell)
    assert (full.tiles_written, full.tiles_run, full.tiles_all) == (
        248, 258, 264)
    assert tuple(window)[:20] == tuple(fa.plan_blocks(
        2048, 6144, window=4096, q_offset=True, **cell))[:20]
    # latent attention's prefill: 16 tiles resident, over UNROLL
    assert fa.plan_blocks(1984, 1984, 128, jnp.bfloat16, causal=True,
                          bh=8 * 64, num_heads=64, rot=64).tiles_written == 0
    # a block of one tile has no neighbour to run under
    assert fa.plan_blocks(256, 256, 128, jnp.float32, causal=True, bh=2,
                          block_q=128, block_k=128).tiles_written == 0
    # a longer window adds interior blocks, which share the one body
    long = fa.plan_blocks(2048, 10240, window=8192, **cell)
    assert fa._written(long, True) == ("blocks", 68) and long.tiles_run == 68
    # two 64-wide heads a step are two bodies a tile, 56 in all, over WRITTEN:
    # the interior blocks' tiles are written out, the others walk in loops
    pair = fa.plan_blocks(2048, 6144, 64, jnp.bfloat16, causal=True,
                          bh=8 * 16, num_heads=16, window=4096)
    assert pair.heads == 2 and 2 * 28 > fa.WRITTEN
    assert fa._written(pair, True) == ("interior", 16) and pair.tiles_run == 36
    since = profiler.time.time_ns()
    q = jax.ShapeDtypeStruct((1, 2048, 6 * 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 6144, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k: fa.flash_attention(
        q, k, k, causal=True, num_heads=6, kv_heads=1, window=4096), q, k)
    ids = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert (ids["tiles_written"], ids["tiles_run"]) == (36, 36)


def test_the_forward_kernel_is_traced_once_a_walk(monkeypatch):
    """A generator calls the kernel once a layer and is traced in several
    passes: four equal calls and one different one inside one trace trace
    ``_fwd_kernel`` twice, a second trace of the same program not at all,
    and a call that differs only in its window or in its key heads anew. A
    plan is recorded at every call all the same. Counts, not seconds."""
    from paddle_tpu.core import profiler

    traced, kernel = [], fa._fwd_kernel
    monkeypatch.setattr(fa, "_fwd_kernel", lambda *refs, w: (
        traced.append(w), kernel(*refs, w=w))[1])
    fa._fwd_call.clear_cache()
    q = jax.ShapeDtypeStruct((1, 128, 4 * 128), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 384, 2 * 128), jnp.float32)

    def layers(q, k, off, window=96, kv_heads=2):
        k = k[..., :kv_heads * 128]
        outs = [fa.flash_attention(q, k, k, causal=True, num_heads=4,
                                   kv_heads=kv_heads, window=window)
                for _ in range(4)]
        return outs + [fa.flash_attention(q, k, k, causal=True, num_heads=4,
                                          kv_heads=kv_heads, q_offset=off)]

    since = profiler.time.time_ns()
    first = jax.make_jaxpr(layers)(q, k, jnp.int32(7))
    assert len(traced) == 2
    again = jax.make_jaxpr(lambda *a: layers(*a))(q, k, jnp.int32(7))
    assert len(traced) == 2
    plans = [sp for sp in profiler.spans(since) if sp[0] == "flash.plan"]
    assert len(plans) == 10
    # the program holds the kernel where each call stands, as it did
    for jaxpr in (first, again):
        assert str(jaxpr).count("name=flash_fwd") == 5
    jax.make_jaxpr(lambda *a: layers(*a, window=64))(q, k, jnp.int32(7))
    assert len(traced) == 3     # the windowed walk anew, the other remembered
    jax.make_jaxpr(lambda *a: layers(*a, kv_heads=1))(q, k, jnp.int32(7))
    assert len(traced) == 5
    assert [(w.plan.window, w.plan.group) for w in traced] == [
        (96, 2), (0, 2), (64, 2), (96, 4), (0, 4)]
    fa._fwd_call.clear_cache()
