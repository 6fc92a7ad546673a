"""Phi-4-mini-flash on the CPU at tiny widths
(``benchmarks/tests/data/tiny-phi4-flash.json``: 8 layers, two periods of
the pattern; a window of 24, no multiple of a tile; prefill pieces of 80
tokens, one block of the scan's kernel and a tail): the generator (prefill
in pieces, the cross-decoder at one position, steps through the state, the
ring and the shared cache) against the plain reference's full forward; the
scan's kernel and its one-token form against the ``lax.scan`` definition;
the windowed flash call against dense masked softmax; the ring's
wrap-around; the family's counts against ISSUE 41's reckoning."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

import paddle_tpu as pt
from benchmarks.families import phi4_flash as family
from benchmarks.reference import phi4_flash as reference
from paddle_tpu.core import profiler
from paddle_tpu.layers import blocks, kv_ring, sambay
from paddle_tpu.models import phi4_flash
from paddle_tpu.ops import selective_scan as ss
from paddle_tpu.ops.flash_attention import flash_attention, plan_blocks

NEW = 10


def tiny_config():
    with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                           "tiny-phi4-flash.json")) as f:
        return json.load(f)


def real_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi4-mini-flash.json")) as f:
        return json.load(f)


_RUNS = {}


def generated(p_len: int):
    """One generation of 2 rows x (``p_len`` + ``NEW``) at the tiny size,
    made once a prompt length: ``(config, host params, prompts, the
    generator's outputs, the reference's logits at the generated positions,
    the spans the trace left)``."""
    if p_len not in _RUNS:
        cfg = tiny_config()
        params = family.Weights(cfg, 5, p_len, NEW).host_params()
        prompts = family.prompts(cfg["vocab_size"], 2, p_len, 5, 1)[0]
        prog = family._program(cfg, NEW)
        before = len(profiler.spans(0))
        out = jax.jit(lambda p, x: prog.apply(p, {}, prompt_ids=x)[0])(
            params, prompts)
        out = {k: np.asarray(v) for k, v in out.items()}
        spans = profiler.spans(0)[before:]
        sh = reference.shape_of(cfg)
        ids = np.concatenate([prompts, out["ids"][:, :-1]], axis=1)
        logits = jax.jit(jax.vmap(lambda i: reference.forward(
            family.reference_params(params, cfg), i, sh)))(ids)
        _RUNS[p_len] = (cfg, params, prompts, out,
                        np.asarray(logits)[:, p_len - 1:], spans)
    return _RUNS[p_len]


# -- the generator against the reference ------------------------------------------


# 200: two whole pieces and a tail, 8 windows long; 80: one piece exactly;
# 30: shorter than a piece, the steps pass the window's 24 -> 25
@pytest.mark.parametrize("p_len", [200, 80, 30])
@pytest.mark.parametrize("what", ["first_token", "steps", "carried_state"])
def test_the_generator_agrees_with_the_reference(p_len, what):
    """``first_token``: the prefill runs layers 5-7 (the full-attention
    layer's query and the cross-decoder) at the last prompt position only;
    the reference runs them over every position: the same distribution.
    ``steps``: every later token through the state, the ring and (K*, V*),
    prompt + new past two windows and past a piece. ``carried_state``: the
    audited state against its float64 definition."""
    cfg, params, prompts, out, logits, _ = generated(p_len)
    gap = (logits.max(-1) - np.take_along_axis(
        logits, out["ids"][..., None], -1)[..., 0]) / logits.std()
    if what == "first_token":
        assert gap[:, 0].max() < 1e-4
    elif what == "steps":
        assert p_len + NEW > cfg["sliding_window"] and gap.shape == (2, NEW)
        assert gap[:, 1:].max() < 1e-4
    else:
        audit = family.carried_check(
            out, family.audited_a_log(cfg, family.Weights(cfg, 5, p_len, NEW)))
        assert audit["ok"] and audit["carried_error"] < 1e-5
        assert audit["positions"] == p_len + NEW - 1


def test_the_first_token_is_the_prefills_and_the_logits_match_closely():
    """The generator's own first distribution against the reference's
    logits at the last prompt position, not only the argmax."""
    cfg = tiny_config()
    p_len = 200
    _, params, prompts, _, logits, _ = generated(p_len)

    def first(prompt_ids):
        state0, _, _ = phi4_flash._decoder(family.program_config(cfg),
                                           prompt_ids, NEW)
        return {"logp0": state0["logp0"]}

    prog = pt.build(first)
    got = jax.jit(lambda p, x: prog.apply(p, {}, training=False,
                                          prompt_ids=x)[0])(params, prompts)
    want = jax.nn.log_softmax(logits[:, 0], axis=-1)
    assert np.abs(np.asarray(got["logp0"]) - np.asarray(want)).max() < 2e-3


def test_the_generator_is_exported_loaded_and_served(tmp_path):
    """``io.save_inference_model`` -> ``load_inference_model`` ->
    ``PredictorServer`` through ``fleet/decode.py``, the door every
    generator takes: the served ids are the direct call's."""
    from paddle_tpu.fleet import decode

    p_len = 30
    cfg, params, prompts, out, _, _ = generated(p_len)
    decode.export_decoder(str(tmp_path), family.program_config(cfg), NEW,
                          np.zeros((2, p_len), np.int32), params=params,
                          batch_buckets=[2], model=phi4_flash)
    server = decode.decode_server(str(tmp_path), workers=1)
    try:
        served = server.submit({"prompt_ids": prompts}).result(timeout=300)
        assert np.array_equal(np.asarray(served["ids"]), out["ids"])
        assert set(served) == {"ids", "audit_delta", "audit_u", "audit_b",
                               "audit_state"}
        assert server.report()["compiles_since_warmup"] == 0
    finally:
        server.close(drain=False, timeout=30)


def test_the_parameter_table_is_the_programs_own():
    cfg = tiny_config()
    prog = family._program(cfg, 4)
    shapes = jax.eval_shape(
        lambda key: prog.init(key, prompt_ids=np.zeros((1, 40), np.int32))[0],
        jax.random.PRNGKey(0))
    table = family.parameter_table(cfg)
    assert list(table) == sorted(shapes)
    for name, s in shapes.items():
        assert (table[name].shape, table[name].dtype) == (s.shape, s.dtype), name
    kinds = family.kinds(cfg)
    assert kinds == list(phi4_flash.mixer_kinds(8)) == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross"]
    real = phi4_flash.mixer_kinds(32)
    assert [real.count(k) for k in ("mamba", "window", "full", "gmu", "cross")
            ] == [9, 8, 1, 7, 7] and real.index("full") == 17


def test_the_plans_say_what_is_carried_and_what_the_prefill_skips():
    cfg, _, _, _, _, spans = generated(200)
    by = lambda name: [s[4] for s in spans if s[0] == name]
    (plan,) = by("decode.plan")
    rows, window, kvw, di = 2, 24, 32, 128
    assert plan["state_bytes"] == rows * 3 * (16 * di * 4 + 3 * di * 4)
    assert plan["window_kv_bytes"] == rows * 2 * 2 * window * kvw * 4
    assert plan["shared_kv_bytes"] == rows * 2 * (200 + NEW) * kvw * 4
    assert plan["shared_kv_readers"] == 2 and plan["carry_free_layers"] == 2
    assert plan["cache_bytes"] == (plan["state_bytes"] + plan["window_kv_bytes"]
                                   + plan["shared_kv_bytes"])
    (pre,) = by("prefill.plan")
    assert (pre["chunk"], pre["pieces"], pre["self_layers"], pre["kv_layers"],
            pre["cross_positions"]) == (80, 3, 5, 1, 1)
    mamba = by("mamba.plan")
    assert len(mamba) == 6 and {(m["tokens"], m["blocks"], m["tail"])
                                for m in mamba} == {(80, 1, 16), (40, 0, 40)}
    assert all(m["state_dtype"] == "float32" and m["d_state"] == 16
               and m["state_bytes"] == rows * 16 * di * 4 for m in mamba)
    flash = by("flash.plan")
    assert len(flash) == 4 and all(f["window"] == 24 and f["causal"]
                                   for f in flash)
    assert {(f["sq"], f["sk"]) for f in flash} == {(80, 104), (40, 64)}


# -- the scan ------------------------------------------------------------------


def scan_inputs(seed, rows, s, di=256, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    delta = jax.nn.softplus(jax.random.normal(k[0], (rows, s, di)) - 3)
    u = jax.random.normal(k[1], (rows, s, di)) * delta
    b, c = (jax.random.normal(key, (rows, s, n)) for key in k[2:4])
    a = -jnp.exp(jax.random.normal(k[4], (n, di)))
    return delta, u, b, c, a, jax.random.normal(k[5], (rows, n, di))


# whole blocks of 64; blocks and a tail; a tail alone; a block's edge
@pytest.mark.parametrize("s", [128, 150, 40, 64, 65])
def test_mamba_fwd_is_the_scan(s):
    args = scan_inputs(s, 2, s)
    want_y, want_s = ss.mamba_scan(*args)
    got_y, got_s = ss.selective_scan(*args)
    assert got_y.dtype == got_s.dtype == jnp.float32
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("split", [64, 100])
def test_a_state_handed_from_call_to_call_and_to_steps_is_the_scans(split):
    """Two calls of the kernel and three one-token steps after them leave
    the state and the outputs one scan over everything leaves."""
    s = 150
    delta, u, b, c, a, state = scan_inputs(7, 1, s + 3)
    want_y, want_s = ss.mamba_scan(delta, u, b, c, a, state)
    cut = lambda lo, hi: tuple(x[:, lo:hi] for x in (delta, u, b, c))
    y1, state = ss.selective_scan(*cut(0, split), a, state)
    y2, state = ss.selective_scan(*cut(split, s), a, state)
    ys = [y1, y2]
    for t in range(s, s + 3):
        y, state = ss.mamba_step(delta[:, t], u[:, t], b[:, t], c[:, t], a, state)
        ys.append(y[:, None])
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), want_y,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=2e-5, atol=2e-5)


def test_the_scan_is_the_definition_in_float64():
    delta, u, b, c, a, _ = scan_inputs(3, 1, 130, di=128)
    _, state = ss.selective_scan(delta, u, b, c, a,
                                 jnp.zeros((1, 16, 128), jnp.float32))
    want = reference.carried_state(delta[0], u[0], b[0], a)
    assert np.abs(np.asarray(state[0]) - want).max() / np.abs(want).max() < 1e-5


# -- the windowed flash call --------------------------------------------------------


def dense_window(q, k, v, window, bias=None):
    sq, sk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    r = jnp.arange(sq)[:, None] + (sk - sq)
    c = jnp.arange(sk)[None, :]
    if bias is not None:
        s = s + bias[:, None, None, :]
    s = jnp.where((c <= r) & (c > r - window), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# (sq, sk, window, d, dv, block): a window that is no multiple of the tile,
# held keys before the piece; whole tiles skipped on both sides (4 of 6 run);
# a streamed grid of small blocks; a long key axis that streams
@pytest.mark.parametrize("sq,sk,window,d,dv,block,tiles", [
    (256, 352, 96, 64, 128, None, (1, 1)),
    (300, 300, 100, 64, 64, None, (1, 1)),
    (1024, 1536, 512, 64, 128, None, (4, 6)),
    (512, 1024, 200, 32, 32, 128, (12, 32)),
    (640, 3200, 700, 64, 64, None, (4, 8))])
@pytest.mark.parametrize("biased", [False, True])
def test_windowed_flash_is_dense_masked_softmax(sq, sk, window, d, dv, block,
                                                tiles, biased):
    k0 = jax.random.split(jax.random.PRNGKey(sq), 3)
    q = jax.random.normal(k0[0], (1, 2, sq, d))
    k = jax.random.normal(k0[1], (1, 2, sk, d))
    v = jax.random.normal(k0[2], (1, 2, sk, dv))
    # the first keys not there yet, as a prefill's first piece has them
    bias = (jnp.where(jnp.arange(sk)[None, :] < min(40, sk - sq), -1e9, 0.0)
            if biased else None)
    got = flash_attention(q, k, v, causal=True, window=window, key_bias=bias,
                          block_q=block, block_k=block)
    np.testing.assert_allclose(got, dense_window(q, k, v, window, bias),
                               rtol=2e-5, atol=2e-5)
    p = plan_blocks(sq, sk, d, jnp.float32, True, dv=dv, window=window,
                    block_q=block, block_k=block)
    assert (p.tiles_run, p.tiles_all) == tiles and p.window == window


def test_a_window_wider_than_the_keys_is_causal_attention():
    k0 = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (1, 2, 256, 64)) for key in k0)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, window=4096),
        flash_attention(q, k, v, causal=True), rtol=1e-6, atol=1e-6)


def test_a_window_is_causal_and_forward_only():
    from paddle_tpu.core.errors import EnforceError

    q = jnp.zeros((1, 2, 128, 64))
    with pytest.raises(EnforceError, match="window"):
        flash_attention(q, q, q, causal=False, window=64)


# -- the ring ---------------------------------------------------------------------


def attention_layer(seed, dims, dtype=jnp.float32):
    """One window layer's parameters, drawn here (no program)."""
    hd, wide, kvw, d = dims.head_dim, dims.heads * dims.head_dim, dims.kv_width, dims.d_model
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"norm/g": jnp.ones((d,)), "norm/b": jnp.zeros((d,)),
            "qkv/w": jax.random.normal(k[0], (d, wide + 2 * kvw)) * d ** -0.5,
            "qkv/b": jax.random.normal(k[1], (wide + 2 * kvw,)) * 0.1,
            "lambda": jax.random.normal(k[2], (4, hd)) * 0.1,
            "sub_norm/g": jnp.ones((2 * hd,)),
            "o/w": jax.random.normal(k[3], (wide, d)) * wide ** -0.5,
            "o/b": jax.random.normal(k[4], (d,)) * 0.1}


def reference_window_layer(x, p, dims, layer):
    sh = reference.Shape(dims.d_model, 8, dims.heads, dims.kv_heads, dims.window,
                         dims.d_inner, dims.d_state, dims.d_conv, dims.dt_rank,
                         dims.eps)
    lp = {"norm_g": p["norm/g"], "norm_b": p["norm/b"], "qkv": p["qkv/w"],
          "qkv_b": p["qkv/b"], "lambdas": p["lambda"],
          "sub_norm": p["sub_norm/g"], "o": p["o/w"], "o_b": p["o/b"]}
    return jax.vmap(lambda row: reference.mixer_part(row, lp, sh, layer)[0])(x)


DIMS = sambay.SambaDims(64, 4, 2, 16, 24, 128, 16, 4, 4, 1e-5)


# the steps alone from an empty ring (positions 0 .. 59 pass the wrap 24 ->
# 25 twice); a prefill in pieces, then steps, the ring made by ``ring_of``
# at a prompt length that is no multiple of the window
@pytest.mark.parametrize("prefilled", [0, 37, 48])
def test_the_ring_wraps_and_the_steps_see_the_window(prefilled):
    total, layer = 60, 1
    p = attention_layer(2, DIMS)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, total, 64))
    with jax.default_matmul_precision("highest"):
        want = reference_window_layer(x, p, DIMS, layer)
        held = (jnp.zeros((2, 24, 32)),) * 2
        outs, at = [], 0
        for piece in ([20, prefilled - 20] if prefilled else []):
            y, held = sambay.window_prefill(x[:, at:at + piece], p, DIMS, held,
                                            jnp.asarray(at), layer)
            outs.append(y)
            at += piece
        ring = kv_ring.ring_of(held, prefilled, DIMS.window)
        step = jax.jit(lambda x1, ring, t: sambay.window_decode(
            x1, p, DIMS, ring, t, layer))
        for t in range(prefilled, total):
            y, ring = step(x[:, t:t + 1], ring, jnp.asarray(t))
            outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                               rtol=2e-4, atol=2e-4)
    # the ring holds the last 24 positions, position t at slot t % 24
    with jax.default_matmul_precision("highest"):
        u = blocks.layer_norm(x, p["norm/g"], p["norm/b"], DIMS.eps)
        _, k_all, _ = sambay._qkv(u, p, DIMS)
    for t in range(total - 24, total):
        np.testing.assert_allclose(ring[0][:, t % 24], k_all[:, t],
                                   rtol=1e-4, atol=1e-4)


# -- the counts -------------------------------------------------------------------


def test_the_counts_are_issue_41s():
    """ISSUE 41's reckoning from the configuration: 3.85B parameters, 7.70
    GB; 1.963B matrix parameters below the cross-decoder; a step's bytes by
    part; the prefill's operations."""
    cfg = real_config()
    m, ks = family._matrices(cfg), family.kinds(cfg)
    matrices = sum(m[k] + m["ffn"] for k in ks)
    assert abs(matrices + m["head"] - 3.852e9) < 2e6
    table = family.parameter_table(cfg)
    total = sum(int(np.prod(s.shape)) for s in table.values())
    assert total == 3_852_562_944
    # (the compile's arguments: these, the prompt's ids and some padding)
    assert 0 <= cfg["memory"]["generator_weights_bytes"] - sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in table.values()) < 1e6
    below = sum(m[k] + m["ffn"] for k in ks[:18])
    assert abs(below - 1.963e9) < 2e6
    # a step at position 2,200 of 32 rows: the parts ISSUE 41 names
    step = family.decode_step_bytes(cfg, 32, 2199)
    weights = 2.0 * (matrices + m["head"])
    shared = 8 * 32 * 2200 * 5120
    rings = 8 * 32 * 512 * 5120
    states = 9 * 2 * family.state_bytes(cfg, 32)
    assert step == weights + shared + rings + states
    assert 7.69e9 < weights < 7.71e9 and 2.8e9 < shared < 2.9e9
    assert 0.66e9 < rings < 0.68e9 and 0.18e9 < states < 0.21e9
    # the prefill: layers 0-16 and K*, V* at every position, the rest at one
    flops = family.prefill_flops(cfg, 32, 2048)
    per_token = 2.0 * (sum(m[k] + m["ffn"] for k in ks[:17]) + m["kv"])
    assert 3.6e9 < per_token < 3.8e9
    assert 0.97 < 65536 * per_token / flops < 1.0
    assert cfg["memory"]["state_bytes"] == 9 * family.state_bytes(cfg, 32)
    for kernel, calls in (("mamba_fwd", 36), ("flash_fwd", 32)):
        ops, moved, n = family.kernel_counts(cfg, 32, 2048, kernel)
        assert n == calls and ops > 0 and moved > 0
    # a window's pairs: the triangle's first 512 rows, then 512 a row
    assert family.window_pairs(cfg, 2048) == 512 * 513 / 2 + 1536 * 512
