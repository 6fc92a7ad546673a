"""The generator's KV cache: lane-dense ``[rows, T, heads*head_dim]``
slabs (layers/stacked.py) and the one-row attention that reads them as
stored. The TPU pads a 64-wide minor dimension to 128 lanes, so the old
``[rows, h, T, hd]`` cache was held and read at twice its size; these
tests hold the new layout to the dense causal attention it replaces, at
every index, for the head sizes the zoo has. What the chip's compiler
makes of it is ``tests/test_tpu_compile.py``'s case.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import profiler
from paddle_tpu.layers import attention as A
from paddle_tpu.layers import stacked as S
from paddle_tpu.models import gpt


def block_params(rng, d, d_inner):
    def w(*shape):  # activations stay O(1) at every width
        return rng.randn(*shape).astype(np.float32) / np.sqrt(shape[0])

    return {k: jnp.asarray(v) for k, v in {
        "ln1/scale": 1 + 0.1 * rng.randn(d).astype(np.float32),
        "ln1/bias": w(d), "qkv/w": w(d, 3, d), "qkv/b": w(3, d),
        "out/w": w(d, d), "out/b": w(d),
        "ln2/scale": np.ones((d,), np.float32), "ln2/bias": w(d),
        "ffn_in/w": w(d, d_inner), "ffn_in/b": w(d_inner),
        "ffn_out/w": w(d_inner, d), "ffn_out/b": w(d),
    }.items()}


@pytest.mark.parametrize("head_dim", [64, 80, 128])
def test_decode_block_equals_dense_causal_at_every_index(head_dim):
    """Feeding a sequence one position at a time through ``decode_block``
    over an empty lane-dense cache gives, at every index, what the dense
    causal block gives for that position of the whole sequence; the cache
    it leaves is the k and v ``prefill_block`` returns. T = 37: not a
    multiple of 128, nor of 8."""
    heads, rows, T = 3, 2, 37
    d = heads * head_dim
    rng = np.random.RandomState(head_dim)
    p = block_params(rng, d, 2 * d)
    x = jnp.asarray(rng.randn(rows, T, d).astype(np.float32))

    dense = S.make_encoder_block(heads, use_flash=False, causal=True)(x, p)
    seeded, (k_all, v_all) = S.prefill_block(x, p, heads, use_flash=False)
    assert k_all.shape == v_all.shape == (rows, T, d)
    np.testing.assert_allclose(np.asarray(seeded), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)

    step = jax.jit(S.decode_block, static_argnums=5)
    k = v = jnp.zeros((rows, T, d), jnp.float32)
    for t in range(T):
        out, k, v = step(x[:, t:t + 1], p, k, v, jnp.asarray(t, jnp.int32),
                         heads)
        assert k.shape == v.shape == (rows, T, d)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(dense[:, t]),
                                   atol=2e-4, rtol=2e-4, err_msg=f"index {t}")
    np.testing.assert_allclose(np.asarray(k), np.asarray(k_all), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_all), atol=1e-5)


def test_decode_block_ignores_positions_past_the_index():
    """Whatever lies in the cache beyond ``index`` (a beam's stale rows,
    the zero tail of ``grow``) has no weight."""
    heads, head_dim, rows, T = 2, 64, 2, 20
    d = heads * head_dim
    rng = np.random.RandomState(3)
    p = block_params(rng, d, d)
    x = jnp.asarray(rng.randn(rows, 1, d).astype(np.float32))
    k = jnp.asarray(rng.randn(rows, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(rows, T, d).astype(np.float32))
    idx = jnp.asarray(7, jnp.int32)
    a, _, _ = S.decode_block(x, p, k, v, idx, heads)
    b, _, _ = S.decode_block(x, p, k.at[:, 8:].set(99.0),
                             v.at[:, 8:].set(-99.0), idx, heads)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the generator against the full forward -----------------------------------

CFG = dict(vocab_size=61, max_len=40, d_model=64, d_inner=96, num_heads=4,
           num_layers=2, use_flash=False, fused_ce=False)
PROMPT, NEW = 11, 9


def full_forward_logp(params, ids, cfg):
    """Log-probabilities [b, s, vocab] of the whole sequence through the
    dense causal blocks: no cache, no prefill, no scan."""
    def find(suffix):
        (name,) = [k for k in params if k.endswith(suffix)]
        return params[name]

    pe = A.positional_encoding(cfg.max_len, cfg.d_model, jnp.float32)
    x = find("embedding_0/w")[ids] + pe[:ids.shape[1]][None]
    block = S.make_encoder_block(cfg.num_heads, use_flash=False, causal=True)
    stack = {k.split("encoder_stack/", 1)[1]: v for k, v in params.items()
             if "encoder_stack/" in k}
    for i in range(cfg.num_layers):
        x = block(x, {k: v[i] for k, v in stack.items()})
    x = S._ln(x, find("layer_norm_0/scale"), find("layer_norm_0/bias"))
    return jax.nn.log_softmax(jnp.matmul(x, find("lm_head_0/w")), axis=-1)


@pytest.fixture(scope="module")
def generator_setup():
    cfg = gpt.base_config(**CFG)
    prompt = np.random.RandomState(0).randint(3, cfg.vocab_size,
                                              (3, PROMPT)).astype(np.int32)
    greedy = pt.build(gpt.make_generator(cfg, max_new_tokens=NEW))
    params, _ = greedy.init(jax.random.PRNGKey(5), prompt_ids=prompt)
    return cfg, params, prompt, greedy


def test_greedy_ids_equal_full_forward_argmax(generator_setup):
    cfg, params, prompt, greedy = generator_setup
    out, _ = greedy.apply(params, {}, prompt_ids=prompt)
    ids = np.asarray(out["ids"])
    assert ids.shape == (prompt.shape[0], NEW)
    seq = np.concatenate([prompt, ids], axis=1)
    logp = np.asarray(full_forward_logp(params, jnp.asarray(seq[:, :-1]), cfg))
    want = logp[:, PROMPT - 1:].argmax(-1)
    ended = np.cumsum(ids == 2, axis=1) - (ids == 2) > 0
    np.testing.assert_array_equal(np.where(ended, 2, want), ids)


def test_beam_scores_equal_full_forward_log_probs(generator_setup):
    """``beam_size`` 2 regathers every cache slab by its leading rows
    dimension each step: the score of every returned beam must be the
    full forward's log-probability of that sequence, the best beam first
    and no worse than the greedy sequence."""
    cfg, params, prompt, greedy = generator_setup
    beam = pt.build(gpt.make_generator(cfg, max_new_tokens=NEW, beam_size=2))
    out, _ = beam.apply(params, {}, prompt_ids=prompt)
    ids, scores = np.asarray(out["ids"]), np.asarray(out["scores"])
    assert ids.shape == (prompt.shape[0], 2, NEW)

    def score(new_ids):
        seq = np.concatenate([prompt, new_ids], axis=1)
        logp = np.asarray(full_forward_logp(params, jnp.asarray(seq[:, :-1]),
                                            cfg))[:, PROMPT - 1:]
        tok = np.take_along_axis(logp, new_ids[..., None], -1)[..., 0]
        ended = np.cumsum(new_ids == 2, axis=1) - (new_ids == 2) > 0
        return np.where(ended, 0.0, tok).sum(-1)

    for lane in range(2):
        np.testing.assert_allclose(scores[:, lane], score(ids[:, lane]),
                                   atol=2e-4, rtol=1e-5)
    g, _ = greedy.apply(params, {}, prompt_ids=prompt)
    assert (scores[:, 0] >= score(np.asarray(g["ids"])) - 1e-4).all()
    assert (scores[:, 0] >= scores[:, 1]).all()


@pytest.mark.parametrize("kv,beam", [("compute", 1), ("compute", 2),
                                     ("int8", 1)])
def test_decode_plan_recorded_once_a_trace(kv, beam):
    """One zero-length ``decode.plan`` span a generator traced, naming
    the cache as held: ``lane_width`` is a slab's minor dimension,
    heads * head_dim, so ``lane_width % 128 == 0`` reads "no padding"."""
    cfg = gpt.base_config(**dict(CFG, kv_cache_dtype=kv))
    prog = pt.build(gpt.make_generator(cfg, max_new_tokens=NEW,
                                       beam_size=beam))
    prompt = np.zeros((2, PROMPT), np.int32)
    shapes = jax.eval_shape(
        lambda key: prog.init(key, prompt_ids=prompt)[0], jax.random.PRNGKey(0))
    since = time.time_ns()
    jax.eval_shape(lambda p: prog.apply(p, {}, prompt_ids=prompt)[0], shapes)
    plans = [s for s in profiler.spans(since) if s[0] == "decode.plan"]
    assert len(plans) == 1
    name, _, dur, _, ids = plans[0]
    rows, total, width = 2 * beam, PROMPT + NEW, cfg.d_model
    assert dur == 0
    assert ids["lane_width"] == cfg.num_heads * (cfg.d_model // cfg.num_heads)
    assert (ids["rows"], ids["max_len"], ids["heads"], ids["head_dim"],
            ids["layers"]) == (rows, total, 4, 16, 2)
    slab = rows * total * width
    if kv == "int8":
        scales = rows * total * cfg.num_heads * 4
        assert ids["cache_dtype"] == "int8"
        assert ids["cache_bytes"] == 2 * cfg.num_layers * (slab + scales)
    else:
        assert ids["cache_dtype"] == "float32"
        assert ids["cache_bytes"] == 2 * cfg.num_layers * slab * 4
