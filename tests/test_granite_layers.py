"""The layers Granite-4.0-H forced, each against the plain reference at tiny
widths: the softmax-over-the-selected routing, **the share test** (two ranks'
parts and the shared expert once are the uncut layer), the pair walk by
gathers against the walk by scatter-add, the Mamba-2 mixer's piece and
one-token forms with the convolution's ring, plain NoPE grouped-query
attention. (``test_granite_hybrid.py`` says what the toy is.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from granite_toy import ADIMS, MDIMS, SHAPE, family, rand, reference
from paddle_tpu.layers import blocks, conv_tail, gqa, mamba2
from paddle_tpu.ops import ssd
from paddle_tpu.parallel import moe


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (b) the routing -------------------------------------------------------------------


def test_the_router_s_weights_are_a_softmax_over_the_selected_alone(highest):
    h, w = rand(10, 50, 64), rand(11, 64, 72, scale=0.3)
    experts, weights = moe.softmax_topk_route(h, w, 10)
    assert experts.shape == weights.shape == (50, 10)
    assert experts.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    logits = np.asarray(h @ w, np.float64)
    top = np.argsort(-logits, axis=-1)[:, :10]
    assert (np.sort(np.asarray(experts), -1) == np.sort(top, -1)).all()
    picked = np.take_along_axis(logits, np.asarray(experts), -1)
    want = np.exp(picked - picked.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(weights), want / want.sum(-1, keepdims=True),
                               atol=1e-5)
    # the other 62 logits move nothing: lower every unselected column
    chosen = np.zeros((50, 72), bool)
    np.put_along_axis(chosen, np.asarray(experts), True, axis=-1)
    lowered = jnp.asarray(np.where(chosen, logits, logits - 3.0), jnp.float32)
    _, again = moe.softmax_topk_route(lowered, jnp.eye(72), 10)
    np.testing.assert_allclose(np.asarray(again), np.asarray(weights), atol=1e-5)
    # and it is the reference's routing
    idx, ref_w = reference.route(h, {"router": w}, SHAPE._replace(top_k=10))
    assert (np.asarray(idx) == np.asarray(experts)).all()
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(weights), atol=1e-6)


# -- (d) the shares add up -------------------------------------------------------------


def test_the_shares_add_up(highest):
    """8 experts over 2 ranks: rank 0's and rank 1's ``moe_held`` parts, plus
    the shared expert counted once, summed into the stream under the
    residual multiplier, are the uncut reference layer (every expert held by
    one rank); by either walk of the pairs."""
    d, f, fs, t = 64, 32, 48, 70
    x = rand(60, 1, t, d)
    lp = {"ffn_norm": 1 + rand(61, d, scale=0.1),
          "router": rand(63, d, 8, scale=d ** -0.5),
          "shared_gate": rand(65, d, fs, scale=d ** -0.5),
          "shared_up": rand(66, d, fs, scale=d ** -0.5),
          "shared_down": rand(67, fs, d, scale=fs ** -0.5),
          "experts_gate": rand(68, 8, d, f, scale=d ** -0.5),
          "experts_up": rand(69, 8, d, f, scale=d ** -0.5),
          "experts_down": rand(70, 8, f, d, scale=f ** -0.5)}
    m = blocks.rms_norm(x, lp["ffn_norm"])[0]
    experts, weights = moe.softmax_topk_route(m, lp["router"], 3)
    shared = blocks.gated_ffn(m, lp["shared_gate"], lp["shared_up"],
                              lp["shared_down"])
    want = reference.ffn_part(x, lp, SHAPE._replace(held=8, rank=0))[0]
    for how in ({}, {"pair_block": 64, "back": "gather"}):
        parts = sum(moe.moe_held(
            m, experts, weights, *(lp[k][4 * rank:4 * rank + 4] for k in (
                "experts_gate", "experts_up", "experts_down")),
            first_expert=4 * rank, experts_held=4, experts_total=8, **how)
            for rank in range(2))
        got = x[0] + 0.22 * (shared + parts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    # and one rank's share alone is the program's layer for that rank
    one = reference.ffn_part(
        x, {**lp, **{k: lp[k][4:8] for k in ("experts_gate", "experts_up",
                                             "experts_down")}},
        SHAPE._replace(rank=1))[0]
    assert np.abs(np.asarray(one - want)).max() > 1e-2


# -- (e) the pair walk by gathers ------------------------------------------------------


@pytest.mark.parametrize("tokens,block,at_once", [
    (300, 128, 2048), (300, 4096, 2048), (96, 64, 32), (100, 64, 32),
    (64, 512, 2048)], ids=["blocks", "one_block", "token_pieces",
                           "ragged_token_pieces", "a_step"])
def test_the_walk_by_gathers_is_the_walk_by_scatter(highest, monkeypatch, tokens,
                                                   block, at_once):
    monkeypatch.setattr(moe, "GATHER_TOKENS", at_once)
    h = rand(20, tokens, 64)
    banks = (rand(21, 6, 64, 32, scale=0.125), rand(22, 6, 64, 32, scale=0.125),
             rand(23, 6, 32, 64, scale=0.18))
    experts, weights = moe.softmax_topk_route(h, rand(24, 64, 12), 4)
    kw = dict(first_expert=6, experts_held=6, experts_total=12)
    want = moe.moe_held(h, experts, weights, *banks, **kw)
    got = moe.moe_held(h, experts, weights, *banks, pair_block=block,
                       back="gather", **kw)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("first", [0, 6])
def test_a_step_through_every_held_expert_is_the_walk(highest, first):
    """``back="gather"`` with pairs that fit one block: a step's rows through
    all the held experts, a token's weight zero where it did not select one,
    is the sorted walk's result; one pair more than a block takes the walk."""
    h = rand(25, 32, 64)
    banks = (rand(26, 6, 64, 32, scale=0.125), rand(27, 6, 64, 32, scale=0.125),
             rand(28, 6, 32, 64, scale=0.18))
    experts, weights = moe.softmax_topk_route(h, rand(29, 64, 12), 4)
    kw = dict(first_expert=first, experts_held=6, experts_total=12)
    want = moe.moe_held(h, experts, weights, *banks, **kw)
    got = moe.moe_held(h, experts, weights, *banks, pair_block=128,
                       back="gather", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    walked = jax.make_jaxpr(lambda h: moe.moe_held(
        h, experts, weights, *banks, pair_block=127, back="gather", **kw))(h)
    assert "ragged_dot" in str(walked)
    assert "ragged_dot" not in str(jax.make_jaxpr(lambda h: moe.moe_held(
        h, experts, weights, *banks, pair_block=128, back="gather", **kw))(h))


def test_the_defaults_of_moe_held_are_the_walk_it_had():
    """``pair_block`` and ``back`` left out: the jaxpr of the layer is the
    one with ``PAIR_BLOCK`` and the scatter-add spelled out."""
    h = jnp.zeros((700, 64))
    banks = (jnp.zeros((6, 64, 32)),) * 2 + (jnp.zeros((6, 32, 64)),)
    kw = dict(first_expert=0, experts_held=6, experts_total=12)

    def layer(**how):
        def fn(h):
            experts, weights = moe.sigmoid_topk_route(
                h, jnp.ones((64, 12)), jnp.zeros((12,)), 2)
            return moe.moe_held(h, experts, weights, *banks, **kw, **how)
        return str(jax.make_jaxpr(fn)(h))

    assert layer() == layer(pair_block=moe.PAIR_BLOCK, back="scatter")
    back = r"f32\[700,64\] = scatter-add"     # a block's rows, to their tokens
    import re
    assert re.search(back, layer()) and not re.search(back, layer(back="gather"))


# -- (f) the mixers: piece and one-token forms ------------------------------------------


def mamba_layer(seed):
    d, di, cw, h = MDIMS.d_model, MDIMS.d_inner, MDIMS.conv_width, MDIMS.heads
    return {"norm/g": 1 + rand(seed, d, scale=0.1),
            "in/w": rand(seed + 1, d, 2 * di + 2 * MDIMS.d_state + h,
                         scale=d ** -0.5),
            "conv/w": rand(seed + 2, 4, cw, scale=0.3),
            "conv/b": rand(seed + 3, cw, scale=0.3),
            "dt/b": rand(seed + 4, h) - 3.0, "a_log": rand(seed + 5, h, scale=0.5),
            "d": 1 + rand(seed + 6, h, scale=0.1),
            "gate_norm/g": 1 + rand(seed + 7, di, scale=0.1),
            "out/w": rand(seed + 8, di, d, scale=di ** -0.5)}


def reference_mamba(x, p):
    return reference.mixer_part(x, family.reference_mixer(
        lambda n: p[n[len("mixer/"):]], "mamba"), SHAPE, "mamba")


@pytest.mark.parametrize("s", [5, 16, 40])
def test_a_mamba2_layer_s_prefill_against_reference(highest, s):
    p, x = mamba_layer(30), rand(40, 2, s, 64)
    got, (tail, state), given = mamba2.mamba2_prefill(
        x, p, MDIMS, mamba2.empty_carry(2, MDIMS, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference_mamba(x, p)),
                               atol=3e-3)
    assert tail.shape == (2, 3, MDIMS.conv_width) and state.dtype == jnp.float32
    assert [g.shape for g in given] == [(2, s, 4), (2, s, 128), (2, s, 16)]
    assert given[1].dtype == given[2].dtype == ssd.OPERAND


def test_a_mamba2_layer_in_pieces_then_steps_is_the_layer_whole(highest):
    """Two pieces, the ring made of what they left, then steps at positions
    that wrap the ring: the layer over the whole sequence. A step with its
    write switch off leaves ring and state as they were."""
    p, x = mamba_layer(50), rand(51, 2, 39, 64)
    want = np.asarray(reference_mamba(x, p))
    carried = mamba2.empty_carry(2, MDIMS, jnp.float32)
    outs = []
    for a, b in ((0, 16), (16, 32)):
        y, carried, _ = mamba2.mamba2_prefill(x[:, a:b], p, MDIMS, carried)
        outs.append(y)
    carried = mamba2.ring_of(carried, 32)
    assert carried[0].shape == (3, 2, MDIMS.conv_width)
    off = mamba2.mamba2_decode(x[:, 32:33], p, MDIMS, carried,
                               jnp.asarray(32), jnp.asarray(False))
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(off[1], carried))
    for t in range(32, 39):
        y, carried, given = mamba2.mamba2_decode(
            x[:, t:t + 1], p, MDIMS, carried, jnp.asarray(t), jnp.asarray(True))
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), want,
                               atol=3e-3)
    assert np.allclose(np.asarray(off[0]), np.asarray(outs[2]))


@pytest.mark.parametrize("p_len", [3, 4, 5, 10])
def test_the_ring_holds_position_t_at_slot_t_mod_3(p_len):
    tail = jnp.arange(p_len - 3, p_len, dtype=jnp.float32)[None, :, None]
    ring = conv_tail.ring_of(jnp.broadcast_to(tail, (2, 3, 4)), p_len)
    assert ring.shape == (3, 2, 4)
    for t in range(p_len - 3, p_len):
        assert (np.asarray(ring[t % 3]) == t).all()
    w = jnp.asarray([[1.0], [10.0], [100.0], [1000.0]]) * jnp.ones((4, 4))
    c, ring = conv_tail.ring_step(jnp.full((2, 4), float(p_len)), ring, w,
                                  jnp.zeros((4,)), jnp.asarray(p_len),
                                  jnp.asarray(True))
    taps = (p_len - 3) + 10 * (p_len - 2) + 100 * (p_len - 1) + 1000 * p_len
    np.testing.assert_allclose(np.asarray(c), np.asarray(jax.nn.silu(float(taps))),
                               rtol=1e-6)
    assert (np.asarray(ring[p_len % 3]) == p_len).all()


def attention_layer(seed):
    d, qw, kvw = ADIMS.d_model, ADIMS.q_width, ADIMS.kv_width
    return {"attn_norm/g": 1 + rand(seed, d, scale=0.1),
            "q/w": rand(seed + 1, d, qw, scale=d ** -0.5 * 3),
            "k/w": rand(seed + 2, d, kvw, scale=d ** -0.5 * 3),
            "v/w": rand(seed + 3, d, kvw, scale=d ** -0.5),
            "o/w": rand(seed + 4, qw, d, scale=qw ** -0.5)}


def test_the_plain_attention_layer_in_pieces_then_steps_is_the_layer_whole(highest):
    """No rotation, no gate, the scale handed in, query head ``i`` on key
    head ``i // 2``: a piece, a second piece at its offset, then steps,
    against the reference's dense masked softmax; the default scale of
    ``cache_attention`` is still ``1 / sqrt(hd)``."""
    from paddle_tpu.ops.flash_attention import padded_keys

    p, x = attention_layer(70), rand(71, 2, 21, 64)
    want = np.asarray(reference.mixer_part(x, family.reference_mixer(
        lambda n: p[n[len("mixer/"):]], "attention"), SHAPE, "attention"))
    cache = (jnp.zeros((2, padded_keys(21), ADIMS.kv_width)),) * 2
    outs = []
    for a, b in ((0, 8), (8, 16)):
        y, cache = gqa.plain_prefill(x[:, a:b], p, ADIMS, cache, jnp.asarray(a),
                                     0.0625, 0.22)
        outs.append(y)
    for t in range(16, 21):
        y, cache = gqa.plain_decode(x[:, t:t + 1], p, ADIMS, cache,
                                    jnp.asarray(t), 0.0625, 0.22)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), want,
                               atol=2e-4)
    q = rand(72, 2, ADIMS.q_width)
    live = jnp.arange(cache[0].shape[1]) <= 20
    np.testing.assert_allclose(
        np.asarray(gqa.cache_attention(q, *cache, live, ADIMS)),
        np.asarray(gqa.cache_attention(q, *cache, live, ADIMS, 16 ** -0.5)))


