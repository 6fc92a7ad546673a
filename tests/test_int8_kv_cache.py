"""int8 KV cache for incremental decoding (GPTConfig.kv_cache_dtype=
"int8"): symmetric per-vector quantization with scales factored out of
both attention matmuls — decode is HBM-bound, so cache bytes are
serving throughput. Serving-side analog of the int8 weight datapath
(quantize.int8_serving); no reference counterpart (no KV cache there
at all).
"""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.layers import stacked as S
from paddle_tpu.models import gpt


def test_quantize_kv_roundtrip_error_bound():
    """Cache entries are lane-dense, [..., heads*head_dim]; one scale a
    head's vector, [..., heads]."""
    rng = np.random.RandomState(0)
    heads, hd = 4, 64
    x = jnp.asarray(rng.randn(2, 8, heads * hd).astype(np.float32) * 3.0)
    q, s = S.quantize_kv(x, heads)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.shape == (2, 8, heads) and s.dtype == jnp.float32
    per_head = x.reshape(2, 8, heads, hd)
    deq = q.reshape(per_head.shape).astype(jnp.float32) * s[..., None]
    # symmetric int8: error <= scale/2 = max|x|/254 per vector
    err = np.abs(np.asarray(deq - per_head))
    bound = np.asarray(jnp.max(jnp.abs(per_head), axis=-1,
                               keepdims=True)) / 254 + 1e-6
    assert (err <= bound).all()
    # zero vectors dequantize to exactly zero
    qz, sz = S.quantize_kv(jnp.zeros((1, 1, 8)), 2)
    assert sz.shape == (1, 1, 2)
    assert np.asarray(qz.astype(jnp.float32)).sum() == 0.0


def test_decode_block_q8_close_to_fp():
    """One cached step: the int8-cache block must track the fp block
    within quantization error (loose block-output tolerance). Caches
    are [rows, T, heads*head_dim], scales [rows, T, heads]."""
    rng = np.random.RandomState(1)
    d, h, rows, T = 32, 4, 2, 16
    p = {k: jnp.asarray(v) for k, v in {
        "ln1/scale": np.ones((d,), np.float32),
        "ln1/bias": np.zeros((d,), np.float32),
        "qkv/w": rng.randn(d, 3, d).astype(np.float32) * 0.2,
        "qkv/b": np.zeros((3, d), np.float32),
        "out/w": rng.randn(d, d).astype(np.float32) * 0.2,
        "out/b": np.zeros((d,), np.float32),
        "ln2/scale": np.ones((d,), np.float32),
        "ln2/bias": np.zeros((d,), np.float32),
        "ffn_in/w": rng.randn(d, 2 * d).astype(np.float32) * 0.2,
        "ffn_in/b": np.zeros((2 * d,), np.float32),
        "ffn_out/w": rng.randn(2 * d, d).astype(np.float32) * 0.2,
        "ffn_out/b": np.zeros((d,), np.float32),
    }.items()}
    x = jnp.asarray(rng.randn(rows, 1, d).astype(np.float32))
    hist = jnp.asarray(rng.randn(rows, T, d).astype(np.float32))
    vals = jnp.asarray(rng.randn(rows, T, d).astype(np.float32))
    idx = jnp.asarray(5, jnp.int32)

    o_fp, _, _ = S.decode_block(x, p, hist, vals, idx, h)
    kq, ks = S.quantize_kv(hist, h)
    vq, vs = S.quantize_kv(vals, h)
    o_q8, kq2, ks2, vq2, vs2 = S.decode_block_q8(x, p, kq, ks, vq, vs, idx, h)
    np.testing.assert_allclose(np.asarray(o_q8), np.asarray(o_fp),
                               atol=0.05, rtol=0.05)
    assert kq2.shape == vq2.shape == (rows, T, d) and kq2.dtype == jnp.int8
    assert ks2.shape == vs2.shape == (rows, T, h)
    # only the row at the index was written
    keep = np.arange(T) != 5
    np.testing.assert_array_equal(np.asarray(kq2)[:, keep],
                                  np.asarray(kq)[:, keep])
    np.testing.assert_array_equal(np.asarray(vs2)[:, keep],
                                  np.asarray(vs)[:, keep])


def test_int8_kv_generator_matches_fp_on_overfit_model():
    """After overfitting a periodic stream, greedy decode with the int8
    cache must emit the same continuation as the compute-dtype cache
    (margins are large, quantization noise cannot flip the argmax) —
    the cache-swap end-to-end proof."""
    cfg = gpt.base_config(vocab_size=16, max_len=48, d_model=64,
                          d_inner=128, num_heads=4, num_layers=2,
                          use_flash=False, fused_ce=False)
    prog = pt.build(gpt.make_model(cfg))
    period = [3, 4, 5, 6]
    seq = np.array([period[i % 4] for i in range(32)], np.int32)
    ids = np.tile(seq, (4, 1))
    labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
    feed = {"ids": ids, "labels": labels.astype(np.int32)}
    tr = pt.Trainer(prog, opt.Adam(1e-2), loss_name="loss")
    tr.startup(sample_feed=feed)
    for _ in range(60):
        out = tr.step(tr._put_feed(feed))
    assert float(out["loss"]) < 0.2, float(out["loss"])

    prompt = jnp.asarray(ids[:2, :8])
    expect = [period[i % 4] for i in range(8)]
    outs = {}
    for kv in ("compute", "int8"):
        g = pt.build(gpt.make_generator(
            gpt.base_config(vocab_size=16, max_len=48, d_model=64,
                            d_inner=128, num_heads=4, num_layers=2,
                            use_flash=False, fused_ce=False,
                            kv_cache_dtype=kv), max_new_tokens=8))
        o, _ = g.apply(dict(tr.scope.params), {}, prompt)
        outs[kv] = np.asarray(o["ids"])
    assert outs["compute"][0].tolist() == expect
    np.testing.assert_array_equal(outs["int8"], outs["compute"])

    # beam path reorders the int8 cache leaves (q and scales) too
    gb = pt.build(gpt.make_generator(
        gpt.base_config(vocab_size=16, max_len=48, d_model=64,
                        d_inner=128, num_heads=4, num_layers=2,
                        use_flash=False, fused_ce=False,
                        kv_cache_dtype="int8"),
        max_new_tokens=8, beam_size=2))
    bo, _ = gb.apply(dict(tr.scope.params), {}, prompt)
    assert np.asarray(bo["ids"])[0, 0].tolist() == expect
