"""Multi-level (nested) LoD tests — lod_tensor.h:58-110 parity.

The reference's nested-LoD surface: create_lod_tensor with recursive
lengths (python/paddle/fluid/lod_tensor.py), level-selecting
sequence_expand (sequence_expand_op.cc ref_level attr), last-level
sequence_pool (sequence_pool_op.cc), and — the load-bearing consumer —
beam_search_decode emitting a (sentence-level, token-level) 2-level
LoD (beam_search_decode_op.cc), exercised end-to-end by the book
machine-translation test (test_machine_translation.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.layers as L
from paddle_tpu.layers.beam_search import (
    beam_search, beam_search_decode, beam_search_decode_lod)
from paddle_tpu.layers.sequence import LoDTensor


# ---------------------------------------------------------------------------
# structure: create / views / offsets
# ---------------------------------------------------------------------------


def test_nested_create_preserves_both_levels():
    # lod_tensor.h:58 example shape: 2 outer seqs; first holds 2 inner
    # (lens 3,2), second holds 1 inner (len 4). 9 rows total.
    data = np.arange(18, dtype=np.float32).reshape(9, 2)
    t = L.create_lod_tensor(data, [[2, 1], [3, 2, 4]])
    assert t.lod_level == 2
    assert t.recursive_sequence_lengths() == [[2, 1], [3, 2, 4]]
    assert t.lod() == [[0, 2, 3], [0, 3, 5, 9]]
    # outer level measured in rows: 3+2=5 and 4
    assert t.row_lengths(0) == [5, 4]
    np.testing.assert_array_equal(
        np.asarray(t.segment_ids(0)), [0] * 5 + [1] * 4)
    np.testing.assert_array_equal(
        np.asarray(t.segment_ids(1)), [0, 0, 0, 1, 1, 2, 2, 2, 2])


def test_single_level_triple_unpack_unchanged():
    vals, lens, seg = L.create_lod_tensor(
        np.arange(10, dtype=np.float32).reshape(5, 2), [[2, 3]])
    np.testing.assert_array_equal(np.asarray(lens), [2, 3])
    np.testing.assert_array_equal(np.asarray(seg), [0, 0, 1, 1, 1])


def test_nested_validation_rejects_inconsistent_levels():
    data = np.zeros((9, 1), np.float32)
    with pytest.raises(Exception, match="level 0"):
        LoDTensor(data, [[2, 2], [3, 2, 4]])  # 2+2 != 3 inner seqs
    with pytest.raises(Exception, match="innermost"):
        LoDTensor(data, [[2, 1], [3, 2, 3]])  # 3+2+3 != 9 rows


def test_three_level_row_lengths_compose():
    t = LoDTensor(np.zeros((10, 1), np.float32),
                  [[2], [1, 1], [4, 6]])
    assert t.row_lengths(0) == [10]
    assert t.row_lengths(1) == [4, 6]
    assert t.lod() == [[0, 2], [0, 1, 2], [0, 4, 10]]


def test_sequences_ragged_view():
    t = L.create_lod_tensor(np.arange(9, dtype=np.float32).reshape(9, 1),
                            [[2, 1], [3, 2, 4]])
    nested = t.sequences(0)
    assert len(nested) == 2 and len(nested[0]) == 2 and len(nested[1]) == 1
    np.testing.assert_array_equal(nested[0][1].ravel(), [3, 4])
    np.testing.assert_array_equal(nested[1][0].ravel(), [5, 6, 7, 8])


# ---------------------------------------------------------------------------
# level-aware ops
# ---------------------------------------------------------------------------


def test_pool_innermost_then_outer_matches_level0_sum():
    data = np.arange(9, dtype=np.float32).reshape(9, 1)
    t = L.create_lod_tensor(data, [[2, 1], [3, 2, 4]])
    # pool last level -> 3 rows, outer LoD remains (reference drops the
    # consumed level and keeps the rest)
    inner = t.pool("sum", level=-1)
    assert isinstance(inner, LoDTensor) and inner.lod_level == 1
    np.testing.assert_allclose(np.asarray(inner.values).ravel(), [3, 7, 26])
    # pooling the remaining level == pooling at level 0 directly
    outer = inner.pool("sum", level=0)
    direct = t.pool("sum", level=0)
    np.testing.assert_allclose(np.asarray(outer), np.asarray(direct))
    np.testing.assert_allclose(np.asarray(direct).ravel(), [10, 26])


def test_sequence_expand_ref_level_selects_counts():
    ref = L.create_lod_tensor(np.zeros((9, 1), np.float32),
                              [[2, 1], [3, 2, 4]])
    x = jnp.asarray([[10.0], [20.0]])
    # ref_level=0: counts are sub-sequence counts [2, 1]
    out0 = L.sequence_expand(x, ref, ref_level=0)
    np.testing.assert_array_equal(np.asarray(out0).ravel(), [10, 10, 20])
    # ref_level=1 (innermost): counts are token counts [3, 2, 4] over a
    # 3-row x
    x3 = jnp.asarray([[1.0], [2.0], [3.0]])
    out1 = L.sequence_expand(x3, ref, ref_level=1)
    np.testing.assert_array_equal(
        np.asarray(out1).ravel(), [1, 1, 1, 2, 2, 3, 3, 3, 3])


# ---------------------------------------------------------------------------
# beam-search decode -> 2-level LoD (the machine-translation round trip)
# ---------------------------------------------------------------------------


def _toy_translation_decode(batch=3, beam=2, max_len=6, vocab=7, eos=2):
    """Deterministic toy 'translation': per-source-row bias table makes
    the decode depend on the source, like the book demo's encoder
    states feeding the decoder."""
    rng = np.random.RandomState(7)
    src_bias = jnp.asarray(rng.randn(batch, vocab).astype(np.float32))
    table = jnp.asarray(jax.nn.log_softmax(
        jnp.asarray(rng.randn(vocab, vocab).astype(np.float32)), axis=-1))

    def step_fn(tokens, state):
        logp = jnp.take(table, tokens, axis=0)
        bias = jnp.repeat(src_bias, beam, axis=0)
        return jax.nn.log_softmax(logp + 0.5 * bias, axis=-1), state

    return beam_search(step_fn, {"s": jnp.zeros((batch * beam,))},
                       batch_size=batch, beam_size=beam, max_len=max_len,
                       eos_id=eos)


def test_beam_decode_emits_two_level_lod():
    eos = 2
    seqs, scores = _toy_translation_decode(eos=eos)
    valid = np.cumsum(np.asarray(seqs) == eos, axis=-1) \
        - (np.asarray(seqs) == eos)
    ids, sc = beam_search_decode_lod(seqs, valid == 0, scores=scores)

    # level 0: one group of K hypotheses per source sentence
    assert ids.lod_level == 2
    assert ids.recursive_sequence_lengths()[0] == [2, 2, 2]
    # level 1: per-hypothesis token counts; tokens match the trimmed rows
    hyp_lens = ids.recursive_sequence_lengths()[1]
    assert len(hyp_lens) == 6 and sum(hyp_lens) == ids.values.shape[0]
    nested = ids.sequences(0)
    for b in range(3):
        for k in range(2):
            ref_toks = np.asarray(seqs)[b, k][np.asarray(valid == 0)[b, k]]
            np.testing.assert_array_equal(nested[b][k].ravel(), ref_toks)
            # every finished hypothesis ends at its first EOS
            if eos in np.asarray(seqs)[b, k]:
                assert nested[b][k].ravel()[-1] == eos
    # scores LoD mirrors the hypothesis grouping, one score per hypothesis
    assert sc.recursive_sequence_lengths() == [[2, 2, 2], [1] * 6]
    np.testing.assert_allclose(np.asarray(sc.values),
                               np.asarray(scores).reshape(-1), rtol=1e-6)


def test_backtrack_decode_to_lod_round_trip():
    """beam_search_decode (backtracking form) output feeds the LoD
    packager too — the reference pipeline beam_search_op ->
    beam_search_decode_op."""
    t_steps, b, k, eos = 4, 2, 2, 2
    rng = np.random.RandomState(1)
    step_ids = rng.randint(3, 6, (t_steps, b, k)).astype(np.int32)
    step_ids[-1] = eos
    step_parents = rng.randint(0, k, (t_steps, b, k)).astype(np.int32)
    seqs, valid = beam_search_decode(step_ids, step_parents, end_id=eos)
    ids = beam_search_decode_lod(seqs, valid)
    assert ids.recursive_sequence_lengths()[0] == [k] * b
    # consume at level 0: first token of the first hypothesis per sentence
    firsts = [grp[0].ravel()[0] for grp in ids.sequences(0)]
    np.testing.assert_array_equal(
        firsts, np.asarray(seqs)[:, 0, 0])


# ---------------------------------------------------------------------------
# the full book machine-translation round trip: train -> beam decode ->
# 2-level LoD -> consume (test_machine_translation.py analog)
# ---------------------------------------------------------------------------


def test_seq2seq_train_decode_lod_round_trip():
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models import seq2seq

    V, E, H, S = 15, 16, 32, 5
    model = pt.build(seq2seq.make_model(src_vocab=V, trg_vocab=V, emb_dim=E,
                                        hidden=H))
    rng = np.random.RandomState(0)

    def batch(bs=16):
        src = rng.randint(3, V, (bs, S)).astype(np.int64)
        trg = np.zeros_like(src)
        trg[:, 0] = 1
        trg[:, 1:] = src[:, :-1]
        labels = np.concatenate([trg[:, 1:], np.full((bs, 1), 2)],
                                axis=1).astype(np.int64)
        return {"src_ids": src, "trg_ids": trg, "labels": labels,
                "src_lengths": np.full((bs,), S, np.int64)}

    trainer = pt.Trainer(model, opt.Adam(5e-3), loss_name="loss")
    trainer.startup(sample_feed=batch())
    for _ in range(120):
        out = trainer.step(batch())
    assert float(out["loss"]) < 1.0, float(out["loss"])

    # decode with the TRAINED params through the shared-name program
    K, T = 2, S + 2
    dec = pt.build(seq2seq.make_decoder(src_vocab=V, trg_vocab=V, emb_dim=E,
                                        hidden=H, max_len=T, beam_size=K))
    feed = batch(bs=4)
    out, _ = dec.apply(trainer.scope.params, trainer.scope.state,
                       jnp.asarray(feed["src_ids"]),
                       jnp.asarray(feed["src_lengths"]))
    seqs, scores = np.asarray(out["ids"]), np.asarray(out["scores"])
    assert seqs.shape == (4, K, T)

    # package as the reference's 2-level LoD decode output
    valid = (np.cumsum(seqs == 2, axis=-1) - (seqs == 2)) == 0
    ids, sc = beam_search_decode_lod(seqs, valid, scores=scores)
    assert ids.recursive_sequence_lengths()[0] == [K] * 4
    assert sc.recursive_sequence_lengths() == [[K] * 4, [1] * (4 * K)]

    # consume the nested output like the book demo: best hypothesis per
    # source sentence should mostly reproduce the copy task
    hits = total = 0
    for b, grp in enumerate(ids.sequences(0)):
        best = grp[0].ravel()
        want = feed["src_ids"][b][: len(best)]
        n = min(len(best), S)
        hits += (best[:n] == want[:n]).sum()
        total += n
    assert total > 0 and hits / total > 0.5, f"decode acc {hits}/{total}"


# ---------------------------------------------------------------------------
# property tests: structure invariants over random nested shapes
# ---------------------------------------------------------------------------

hypothesis = pytest.importorskip("hypothesis")  # optional dependency
from hypothesis import given, settings, strategies as st  # noqa: E402


@st.composite
def nested_lod(draw, min_len=0):
    """Random 2- or 3-level recursive_seq_lens (consistent by
    construction) + matching packed values."""
    levels = draw(st.integers(2, 3))
    top = draw(st.lists(st.integers(1, 3), min_size=1, max_size=3))
    lens = [top]
    for _ in range(levels - 1):
        n_units = sum(lens[-1])
        lens.append([draw(st.integers(min_len, 3)) for _ in range(n_units)])
    rows = sum(lens[-1])
    return lens, np.arange(rows * 2, dtype=np.float32).reshape(rows, 2)


@settings(max_examples=40, deadline=None)
@given(nested_lod())
def test_lod_structure_invariants(case):
    lens, values = case
    t = LoDTensor(values, lens)
    assert t.recursive_sequence_lengths() == [list(l) for l in lens]
    lod = t.lod()
    # offsets: monotone, start 0, each level's last offset counts the
    # units of the next level (rows for the innermost)
    for li, offs in enumerate(lod):
        assert offs[0] == 0 and all(a <= b for a, b in zip(offs, offs[1:]))
        nxt = len(lens[li + 1]) if li + 1 < len(lens) else values.shape[0]
        assert offs[-1] == nxt
    # row_lengths at EVERY level sums to the total rows, and has one
    # entry per sequence of that level
    for level in range(t.lod_level):
        rl = t.row_lengths(level)
        assert sum(rl) == values.shape[0]
        assert len(rl) == len(lens[level])


@settings(max_examples=40, deadline=None)
@given(nested_lod(min_len=1))
def test_lod_pool_composition_property(case):
    """sum-pool at the innermost level then sum-pooling the pooled rows
    at the outer level == sum-pooling level 0 directly — for ANY
    consistent nested structure (generalizes the one-case test above)."""
    lens, values = case
    t = LoDTensor(values, lens)
    inner = L.sequence_pool(t.values, t.segment_ids(-1),
                            t.num_seqs(-1), "sum")
    # group the innermost pooled rows by the composed outer structure
    outer_lens = lens[0] if t.lod_level == 2 else [
        sum(lens[1][pos:pos + n])
        for pos, n in zip(np.cumsum([0] + lens[0][:-1]), lens[0])]
    seg = np.repeat(np.arange(len(outer_lens)), outer_lens)
    direct = L.sequence_pool(t.values, t.segment_ids(0), t.num_seqs(0), "sum")
    via_inner = L.sequence_pool(inner, jnp.asarray(seg, jnp.int32),
                                len(outer_lens), "sum")
    np.testing.assert_allclose(np.asarray(via_inner), np.asarray(direct),
                               rtol=1e-5, atol=1e-5)
