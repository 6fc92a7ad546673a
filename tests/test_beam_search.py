"""Beam-search / greedy decode tests (beam_search_op +
machine_translation book-test analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.layers.beam_search import beam_search, greedy_search
from paddle_tpu.models import transformer


def test_beam_search_finds_best_path_toy():
    """Deterministic toy LM: transition scores favor path 1->2->3(eos)."""
    vocab = 5
    logits_table = np.full((vocab, vocab), -10.0, np.float32)
    logits_table[1, 3] = 0.0   # from bos(1): token 3 best
    logits_table[1, 4] = -0.5  # token 4 second
    logits_table[3, 2] = 0.0   # from 3: eos best
    logits_table[4, 2] = 0.0
    table = jnp.asarray(jax.nn.log_softmax(jnp.asarray(logits_table), axis=-1))

    def step_fn(tokens, state):
        return jnp.take(table, tokens, axis=0), state

    seqs, scores = beam_search(step_fn, {"dummy": jnp.zeros((2 * 1,))},
                               batch_size=1, beam_size=2, max_len=4,
                               bos_id=1, eos_id=2)
    best = np.asarray(seqs)[0, 0]
    assert best[0] == 3 and best[1] == 2, f"unexpected best path {best}"
    # second beam should start with 4
    second = np.asarray(seqs)[0, 1]
    assert second[0] == 4
    assert float(scores[0, 0]) > float(scores[0, 1])


def test_greedy_matches_beam1():
    vocab = 6
    rng = np.random.RandomState(0)
    table = jnp.asarray(jax.nn.log_softmax(
        jnp.asarray(rng.randn(vocab, vocab).astype(np.float32)), axis=-1))

    def step_fn(tokens, state):
        return jnp.take(table, tokens, axis=0), state

    g = greedy_search(step_fn, {"s": jnp.zeros((3,))}, batch_size=3, max_len=5)
    b, _ = beam_search(step_fn, {"s": jnp.zeros((3,))}, batch_size=3, beam_size=1,
                       max_len=5)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(b)[:, 0])


def test_greedy_returns_the_last_state_on_request():
    """``with_state``: the same ids, and the state the last step left."""
    vocab = 6
    rng = np.random.RandomState(1)
    table = jnp.asarray(jax.nn.log_softmax(
        jnp.asarray(rng.randn(vocab, vocab).astype(np.float32)), axis=-1))

    def step_fn(tokens, state):
        return jnp.take(table, tokens, axis=0), {"n": state["n"] + 1,
                                                 "last": tokens}

    state0 = {"n": jnp.zeros((), jnp.int32), "last": jnp.zeros((3,), jnp.int32)}
    want = greedy_search(step_fn, state0, batch_size=3, max_len=5)
    got, state = greedy_search(step_fn, state0, batch_size=3, max_len=5,
                               with_state=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(state["n"]) == 5
    np.testing.assert_array_equal(np.asarray(state["last"]),
                                  np.asarray(got)[:, -2])


def _train_tiny_copy_model(max_steps=400, target_loss=0.35):
    cfg = transformer.base_config(src_vocab=12, trg_vocab=12, d_model=32,
                                  d_inner=64, num_heads=4, num_encoder_layers=1,
                                  num_decoder_layers=1, dropout=0.0,
                                  label_smooth_eps=0.0)
    model = pt.build(transformer.make_model(cfg))
    rng = np.random.RandomState(0)

    def batch(bs=32, s=5):
        src = rng.randint(3, 12, (bs, s)).astype(np.int64)
        trg = np.zeros_like(src)
        trg[:, 0] = 1
        trg[:, 1:] = src[:, :-1]
        labels = np.concatenate([trg[:, 1:], np.full((bs, 1), 2)], axis=1).astype(np.int64)
        return {"src_ids": src, "trg_ids": trg, "labels": labels}

    trainer = pt.Trainer(model, opt.Adam(5e-3), loss_name="loss")
    trainer.startup(sample_feed=batch())
    loss = None
    for _ in range(max_steps):
        loss = float(trainer.step(batch())["loss"])
        if loss < target_loss:
            break
    assert loss is not None and loss < 1.5, f"copy model failed to train: loss={loss}"
    return cfg, trainer, batch


def test_transformer_greedy_decode_copies():
    cfg, trainer, batch = _train_tiny_copy_model()
    dec = pt.build(transformer.make_decoder(cfg, max_len=6))
    feed = batch(bs=4)
    # decode program shares names with train program -> reuse params
    out, _ = dec.apply(trainer.scope.params, trainer.scope.state,
                       jnp.asarray(feed["src_ids"]))
    ids = np.asarray(out["ids"])
    # greedy decode should reproduce the source-shifted sequence mostly
    want = feed["src_ids"][:, :-1]
    got = ids[:, :want.shape[1]]
    acc = (got == want).mean()
    assert acc > 0.6, f"decode accuracy too low: {acc} (got {got[0]}, want {want[0]})"


def test_transformer_beam_decode_runs_and_beats_or_ties_greedy():
    cfg, trainer, batch = _train_tiny_copy_model(max_steps=100, target_loss=1.0)
    feed = batch(bs=2)
    dec_g = pt.build(transformer.make_decoder(cfg, max_len=6))
    dec_b = pt.build(transformer.make_decoder(cfg, max_len=6, beam_size=3))
    out_g, _ = dec_g.apply(trainer.scope.params, trainer.scope.state,
                           jnp.asarray(feed["src_ids"]))
    out_b, _ = dec_b.apply(trainer.scope.params, trainer.scope.state,
                           jnp.asarray(feed["src_ids"]))
    assert out_b["ids"].shape == (2, 3, 6)
    assert np.all(np.asarray(out_b["scores"])[:, 0] >= np.asarray(out_b["scores"])[:, 1] - 1e-5)


def test_exhaustive_beam_equals_brute_force_enumeration():
    """With beam_size >= vocab^max_len every prefix survives each top-k
    selection, so beam search IS exhaustive enumeration: the returned
    best sequence and score must equal the brute-force argmax over all
    vocab^max_len sequences — an exact oracle for score accumulation.
    Randomized Markov tables, eos unreachable."""
    import itertools

    vocab, max_len = 3, 3
    rng = np.random.RandomState(7)
    for trial in range(5):
        table = rng.randn(vocab + 3, vocab + 3).astype(np.float32)
        table[:, 2] = -100.0  # eos never competitive
        logp_np = np.asarray(jax.nn.log_softmax(jnp.asarray(table), axis=-1))

        def step_fn(tokens, state, _t=jnp.asarray(logp_np)):
            return jnp.take(_t, tokens, axis=0), state

        K = (vocab + 3) ** max_len  # 216 beams: exhaustive
        seqs, scores = beam_search(step_fn, {"d": jnp.zeros((K,))},
                                   batch_size=1, beam_size=K,
                                   max_len=max_len, bos_id=1, eos_id=2)
        # brute force over all candidate sequences from bos
        best_score, best_seq = -np.inf, None
        for cand in itertools.product(range(vocab + 3), repeat=max_len):
            s, prev = 0.0, 1
            for tok in cand:
                s += logp_np[prev, tok]
                prev = tok
            if s > best_score:
                best_score, best_seq = s, cand
        np.testing.assert_allclose(float(scores[0, 0]), best_score,
                                   rtol=1e-5)
        assert tuple(np.asarray(seqs)[0, 0]) == best_seq, \
            (trial, tuple(np.asarray(seqs)[0, 0]), best_seq)
