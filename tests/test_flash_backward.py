"""The flash backward's two forms (interpret mode on the CPU): one resident
kernel, ``flash_bwd``, where the plan holds both sequences in one grid step,
and the streaming pair ``flash_dq`` + ``flash_dkv`` elsewhere. Apart from
tests/test_flash_attention.py so that neither file holds a worker long."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_flash_attention import (_heads_apart, _heads_together, _rand,
                                  _ref_masked, _ref_seg)

from paddle_tpu.ops import flash_attention as fa

# The backward of a call whose plan holds both sequences in one grid step
# is one kernel (``flash_bwd``: FlashPlan.backward == "fused"); explicit
# blocks that cut the query axis in two force the streaming pair
# (``flash_dq`` + ``flash_dkv``) onto the same operands.
# name -> (layout, sq, sk, heads, d, mask, dtype, blocks that force the pair)
FUSED_BACKWARD = {
    "bhsd_causal": ("bhsd", 256, 256, 2, 32, "causal", "float32", (128, 128)),
    "bhsd_scale_folded": ("bhsd", 256, 256, 2, 64, "causal", "float32",
                          (128, 128)),
    "bhsd_no_mask": ("bhsd", 256, 256, 2, 32, "none", "float32", (128, 256)),
    "bsd_two_heads_a_group": ("bsd", 256, 256, 4, 64, "causal", "float32",
                              (128, 128)),
    "bsd_head_a_group": ("bsd", 256, 256, 2, 128, "causal", "float32",
                         (128, 128)),
    "fused_qkv": ("fused", 256, 256, 4, 64, "causal", "float32", (128, 128)),
    "fewer_queries_than_keys": ("bhsd", 128, 256, 2, 32, "causal", "float32",
                                (64, 128)),
    "more_queries_than_keys": ("bsd", 256, 128, 2, 64, "causal", "float32",
                               (128, 64)),
    "padded_200": ("bsd", 200, 200, 2, 64, "causal", "float32", (104, 112)),
    "padded_200_no_mask": ("bhsd", 200, 200, 2, 32, "none", "float32",
                           (104, 112)),
    "padded_keys_in_loops": ("bhsd", 1160, 1160, 1, 32, "none", "bfloat16",
                             (768, 512)),
    "one_tile_896": ("fused", 896, 896, 2, 64, "causal", "float32",
                     (448, 448)),
    "key_bias": ("bsd", 256, 256, 2, 64, "key_bias", "float32", (128, 128)),
    "padded_row_biased_out": ("bsd", 200, 200, 2, 64, "key_bias_whole_row",
                              "float32", (104, 112)),
    "segment_ids": ("fused", 256, 256, 2, 64, "segment_ids", "float32",
                    (128, 128)),
    "every_key_masked": ("bhsd", 256, 256, 2, 32, "kv_segment_ids", "float32",
                         (128, 128)),
    "bfloat16": ("fused", 256, 256, 4, 64, "causal", "bfloat16", (128, 128)),
    "bfloat16_bhsd": ("bhsd", 256, 256, 2, 64, "causal", "bfloat16",
                      (128, 128)),
    "walk_in_loops": ("bhsd", 1536, 1536, 1, 32, "causal", "bfloat16",
                      (512, 512)),
}


@pytest.mark.parametrize("name", FUSED_BACKWARD)
def test_fused_backward_matches_split_pair_and_dense(name):
    """dq, dk and dv of the one resident backward kernel against the
    streaming pair on the same operands and against the dense reference:
    ``[b, h, s, d]``, ``[b, s, h*d]`` with two heads a lane group and with
    one, the fused ``[b, s, 3*h*d]`` entry, unequal lengths (the causal
    diagonal is bottom-right aligned; queries above it see no key), padded
    lengths (padded keys are masked out of dq in a written-out walk and in
    a looped one, which a batch row with every real key biased out needs),
    every mask, a row with every key masked (``_probs``'s
    guard), float32 and bfloat16, and a walk too long to be written out.
    ``flash.plan`` says which backward each call got."""
    from paddle_tpu.core import profiler

    layout, sq, sk, h, d, mask, dtype, blocks = FUSED_BACKWARD[name]
    q, k, v = _rand(b=2, h=h, s=sq, sk=sk, d=d, seed=len(name))
    w = jnp.asarray(np.random.RandomState(sq).randn(*q.shape), jnp.float32)
    kw, ref_kw = {"causal": mask in ("causal", "segment_ids")}, {}
    if mask == "key_bias":
        ref_kw["key_bias"] = kw["key_bias"] = jnp.where(
            jnp.arange(sk)[None, :] < jnp.array([[sk - 56], [sk]]), 0.0, -1e9)
    if mask == "key_bias_whole_row":
        # a batch row whose every key is biased out has lse near -1e9, and
        # exp(0 - lse) of a padded key's zero score is inf: dq is finite
        # only because the padded keys are masked before the exponential
        ref_kw["key_bias"] = kw["key_bias"] = jnp.asarray(
            [[-1e9] * sk, [0.0] * sk], jnp.float32)
    if mask == "segment_ids":
        ref_kw["seg"] = kw["segment_ids"] = jnp.asarray(
            np.sort(np.random.RandomState(3).randint(0, 3, (2, sq)), axis=1),
            jnp.int32)
    if mask == "kv_segment_ids":    # queries of segment 2 have no key at all
        kw["segment_ids"] = jnp.asarray((np.arange(sq) * 3 // sq)[None]
                                        .repeat(2, 0))
        kw["kv_segment_ids"] = jnp.asarray((np.arange(sk) * 2 // sk)[None]
                                           .repeat(2, 0))

    def flash(q, k, v, **blocks):
        q, k, v = (x.astype(dtype) for x in (q, k, v))
        if layout == "bhsd":
            out = fa.flash_attention(q, k, v, **kw, **blocks)
        else:
            parts = [_heads_together(x) for x in (q, k, v)]
            if layout == "fused":
                parts = [jnp.concatenate(parts, axis=-1)]
            out = _heads_apart(fa.flash_attention(
                *parts, num_heads=h, **kw, **blocks), h)
        return out.astype(jnp.float32)

    def dense(q, k, v):
        if mask == "kv_segment_ids":
            return _ref_seg(q, k, v, kw["segment_ids"], kw["kv_segment_ids"])
        return _ref_masked(q, k, v, kw["causal"], **ref_kw)

    def grads(f, **blocks):
        since = profiler.time.time_ns()
        out = jax.grad(lambda *a: (f(*a, **blocks) * w).sum(),
                       argnums=(0, 1, 2))(q, k, v)
        plans = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"]
        return out, {p["backward"] for p in plans}

    (fused, which), (split, which_split) = grads(flash), grads(
        flash, block_q=blocks[0], block_k=blocks[1])
    assert (which, which_split) == ({"fused"}, {"split"})
    exact = dtype == "float32"
    # float32 holds a score beside -1e9 to the nearest 64: both kernels
    # agree on such a row, a reference differentiated as if exact does not
    sound = slice(1, None) if mask == "key_bias_whole_row" else slice(None)
    for x, a, b_, c in zip("qkv", fused, split, grads(dense)[0]):
        assert np.isfinite(np.asarray(a)).all(), f"d{x}"
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), err_msg=f"d{x} vs the split pair",
            atol=2e-5 if exact else 0.05, rtol=1e-5 if exact else 0.05)
        np.testing.assert_allclose(
            np.asarray(a)[sound], np.asarray(c)[sound], err_msg=f"d{x} vs dense",
            atol=3e-4 if exact else 0.1, rtol=2e-3 if exact else 0.1)
    if mask == "kv_segment_ids":
        assert not np.asarray(fused[0])[:, :, -sq // 3 + 1:].any()


@pytest.mark.parametrize("seq,d,dtype,blocks,backward", [
    (1024, 64, "bfloat16", None, "fused"),
    (fa.RESIDENT, 64, "bfloat16", None, "fused"),
    (fa.RESIDENT, 128, "bfloat16", None, "fused"),
    (fa.RESIDENT + 128, 64, "bfloat16", None, "split"),
    (1024, 64, "bfloat16", (512, 1024), "split"),
    (1024, 64, "bfloat16", (1024, 512), "split"),
    (fa.RESIDENT, 256, "bfloat16", None, "split"),
    (fa.RESIDENT, 128, "float32", None, "split")])
def test_plan_names_its_backward(seq, d, dtype, blocks, backward):
    """One backward kernel exactly where a grid step holds both sequences
    (lengths up to ``RESIDENT`` under the plan's own blocks) and its blocks
    leave room in VMEM; a sequence that streams on either axis keeps the
    pair, and so do 256-wide heads and float32 operands at the longest
    resident length (21 and 18 MB a step where the compiler takes 10;
    tests/test_tpu_compile.py compiles both sides of the line).
    ``flash.plan`` carries it."""
    from paddle_tpu.core import profiler

    kw = dict(block_q=blocks[0], block_k=blocks[1]) if blocks else {}
    plan = fa.plan_blocks(seq, seq, d, dtype, causal=True, bh=4,
                          num_heads=2, **kw)
    assert plan.backward == backward
    since = profiler.time.time_ns()
    q = jax.ShapeDtypeStruct((2, seq, 2 * d), dtype)
    jax.eval_shape(lambda q: fa.flash_attention(q, q, q, causal=True,
                                                num_heads=2, **kw), q)
    ids = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert ids["backward"] == backward


def test_a_length_over_resident_runs_the_pair_and_matches_dense():
    """``RESIDENT`` + 128 rows stream in ``STREAM_BLOCK`` blocks under the
    plan's own choice: the gradient runs ``flash_dq`` + ``flash_dkv`` and
    agrees with the dense reference."""
    from paddle_tpu.core import profiler

    s = fa.RESIDENT + 128
    q, k, v = _rand(b=1, h=1, s=s, d=32, seed=s)
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    since = profiler.time.time_ns()
    got = jax.grad(lambda *a: (fa.flash_attention(*a, causal=True) * w).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    plans = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"]
    assert {(p["backward"], p["block_q"]) for p in plans} == {
        ("split", fa.STREAM_BLOCK)}
    want = jax.grad(lambda *a: (_ref_masked(*a, True) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for x, a, b_ in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=3e-4,
                                   rtol=2e-3, err_msg=f"d{x} vs dense")


@pytest.mark.parametrize("name", ["bsd_two_heads_a_group", "fused_qkv",
                                  "bhsd_causal"])
def test_a_remembered_forward_differentiates_as_a_traced_one(name, monkeypatch):
    """The forward kernel is reached through jit's cache (``_fwd_call``):
    the gradient of a jitted caller whose forward trace is remembered from
    an earlier pass equals, bit for bit, the one whose kernel is traced
    there, and the custom VJP's two forward rules share one trace."""
    layout, sq, sk, h, d, _, _, _ = FUSED_BACKWARD[name]
    q, k, v = _rand(b=2, h=h, s=sq, sk=sk, d=d, seed=3)
    traced, kernel = [], fa._fwd_kernel
    monkeypatch.setattr(fa, "_fwd_kernel", lambda *refs, w: (
        traced.append(w), kernel(*refs, w=w))[1])

    def loss(q, k, v):
        if layout == "bhsd":
            return fa.flash_attention(q, k, v, causal=True).sum()
        parts = [_heads_together(x) for x in (q, k, v)]
        if layout == "fused":
            parts = [jnp.concatenate(parts, axis=-1)]
        return fa.flash_attention(*parts, num_heads=h, causal=True).sum()

    grads = lambda: jax.jit(jax.grad(lambda *a: loss(*a), argnums=(0, 1, 2)))(
        q, k, v)
    fa._fwd_call.clear_cache()
    fresh = grads()
    assert len(traced) == 1
    jax.eval_shape(loss, q, k, v)       # another pass: the primal's rule
    remembered = grads()
    assert len(traced) == 1
    for a, b_ in zip(fresh, remembered):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    fa._fwd_call.clear_cache()
