"""Granite-4.0-H on the CPU at tiny widths
(``benchmarks/tests/data/tiny-granite-hybrid.json``: 4 layers, one period of
the toy pattern, Mamba-2 at 0, 1, 3 and attention at 2; four heads of 32 in
one lane group; 4 of 8 experts held, 3 a token; prefill pieces of 80 tokens,
five chunks of 16): the generator (prefill in pieces, steps through the
states, the convolution's ring and the cache) against the plain reference's
full forward, logits and not tokens, and the four multipliers.
(``test_granite_layers.py`` holds the layers and the share test,
``test_granite_served.py`` the plans, the served path and the small check:
three files so that ``--dist loadfile`` gives them three workers.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from granite_toy import (TINY, VOCAB, family, granite_hybrid, prompts,
                         reference_logp, scored, seeded)


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) prefill, then decoding through the carry, is one full forward ---------------


@pytest.mark.parametrize("p_len", [200, 80, 30, 7], ids=[
    "two_pieces_and_a_ragged_tail", "one_piece", "shorter_than_a_piece",
    "shorter_than_a_chunk"])
def test_prefill_then_steps_are_the_full_forward_at_every_position(highest,
                                                                   p_len):
    """Logits, not tokens: the scorer's distribution after the prompt and
    after each of nine given continuations (the prefill in pieces of 80, the
    steps over the states, the ring and the cache) against one reference
    forward over prompt + continuations."""
    new = 9
    _, params = seeded(TINY, p_len, new + 1)
    prompt = prompts(2, p_len, seed=1)
    nxt = prompts(2, new, seed=2)
    got = scored(TINY, params, prompt, nxt)
    want = reference_logp(TINY, params, np.concatenate([prompt, nxt], axis=1),
                          p_len - 1)
    assert got.shape == want.shape == (2, new + 1, VOCAB)
    # the recurrence's operands are bfloat16 (ops/ssd.OPERAND) in a float32
    # toy whose log-probabilities deviate by 0.0075 over the vocabulary: most
    # positions agree to a twentieth of that; where a token's tenth-largest
    # router logit is a near-tie the rounding picks another expert for it and
    # that position reads up to two deviations off (the next test takes the
    # experts' say away and holds every position)
    off = np.abs(got - want)
    assert np.median(off) < 3e-4
    assert (off.max(axis=(0, 2)) < 2e-3).mean() >= 0.6, off.max(axis=(0, 2))


def test_with_the_experts_nearly_silent_every_position_agrees(highest, monkeypatch):
    """The same comparison with the routed experts' output scaled down to a
    thousandth, so that a selection flipped by rounding moves nothing: the
    mixers, the shared expert, the carry and the head agree at every one of
    ten positions after a prompt of two pieces and a ragged tail."""
    monkeypatch.setattr(family, "EXPERT_DOWN_GAIN", 1e-3)
    _, params = seeded(TINY, 200, 10)
    prompt, nxt = prompts(2, 200, seed=1), prompts(2, 9, seed=2)
    got = scored(TINY, params, prompt, nxt)
    want = reference_logp(TINY, params, np.concatenate([prompt, nxt], axis=1),
                          199)
    assert np.abs(got - want).max() < 1e-3


@pytest.mark.parametrize("chunk", [16, 48, 80], ids=["a_chunk", "three", "five"])
def test_a_prefill_in_pieces_is_a_prefill_in_one_piece(highest, chunk):
    _, params = seeded(TINY, 96, 2)
    prompt = prompts(2, 96, seed=3)
    nxt = prompts(2, 1, seed=4)
    pieces = scored(dict(TINY, run=dict(TINY["run"], chunk=chunk)), params,
                    prompt, nxt)
    whole = scored(dict(TINY, run=dict(TINY["run"], chunk=96)), params, prompt,
                   nxt)
    assert np.abs(pieces - whole).max() < 5e-4


def test_a_piece_must_be_whole_chunks():
    cfg = dict(TINY, run=dict(TINY["run"], chunk=40))
    with pytest.raises(Exception, match="whole number"):
        pt.build(granite_hybrid.make_generator(
            family.program_config(cfg), max_new_tokens=2)).init(
                jax.random.PRNGKey(0), prompt_ids=prompts(1, 96))


def test_the_generator_s_ids_are_the_scorer_s_argmax_and_the_audit_holds(highest):
    new = 8
    weights, params = seeded(TINY, 200, new)
    prompt = prompts(2, 200, seed=5)
    gen = pt.build(granite_hybrid.make_generator(family.program_config(TINY),
                                                 max_new_tokens=new))
    out = jax.tree.map(np.asarray, gen.apply(params, {}, training=False,
                                             prompt_ids=prompt)[0])
    assert set(out) == {"ids", "audit_dt", "audit_x", "audit_b", "audit_state"}
    logp = scored(TINY, params, prompt, out["ids"][:, :-1])
    assert (np.argmax(logp, -1) == out["ids"]).all()
    assert out["audit_dt"].shape == (2, 200 + new - 1, 4)
    assert out["audit_x"].shape == (2, 200 + new - 1, 128)
    assert out["audit_state"].shape == (2, 16, 128)
    assert out["audit_state"].dtype == np.float32
    carried = family.carried_check(out, family.audited_a_log(TINY, weights),
                                   TINY["mamba_d_head"])
    assert carried["ok"] and carried["carried_error"] < 5e-6, carried
    # a state rounded to bfloat16 once is an error the limit refuses
    rounded = dict(out, audit_state=np.asarray(jax.lax.reduce_precision(
        jnp.asarray(out["audit_state"]), 8, 7)))
    assert not family.carried_check(
        rounded, family.audited_a_log(TINY, weights), TINY["mamba_d_head"])["ok"]


# -- (c) the four multipliers ----------------------------------------------------------


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 5.0), ("residual_multiplier", 0.5),
    ("attention_multiplier", 0.25), ("logits_scaling", 3.0)])
def test_each_multiplier_is_the_reference_s(highest, key, value):
    """With one multiplier changed the program is still the reference with
    that multiplier changed, and is not the reference as published."""
    cfg = dict(TINY, **{key: value})
    _, params = seeded(TINY, 30, 3)
    prompt, nxt = prompts(2, 30, seed=6), prompts(2, 2, seed=7)
    ids = np.concatenate([prompt, nxt], axis=1)
    got = scored(cfg, params, prompt, nxt)
    changed = reference_logp(cfg, params, ids, 29)
    spread = changed.std()      # of the log-probabilities over the vocabulary
    assert np.abs(got - changed).max() < 0.27 * spread
    assert np.abs(got - reference_logp(TINY, params, ids, 29)).max() > 0.67 * spread


