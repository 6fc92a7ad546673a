"""Trinity (``model_type: afmoe``) at tiny sizes on the CPU, against the one
plain reference, ``benchmarks/reference/trinity.py``: a sliding and a full
layer's prefill, prefill then steps through the ring and the full cache, a
prefill in pieces, the held shares of an expert layer adding up, the served
path, and the small check failing in a lower precision. Seeded weights;
float32 unless a case says otherwise.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:        # benchmarks/ of this checkout, as its own
    sys.path.insert(0, ROOT)    # tests' conftest.py does

import paddle_tpu as pt
from benchmarks.families import trinity as family
from benchmarks.reference import trinity as reference
from paddle_tpu.core import profiler
from paddle_tpu.layers import blocks, decoding, gqa, kv_ring
from paddle_tpu.models import trinity
from paddle_tpu.parallel import moe

# The family's toy configuration file: layers 1-5 of 8 (1 dense; 3 full, the
# others sliding), a window of 8, 4 of 16 experts held (rank 2), 2 a token,
# 4 query heads on 2 key heads of 128 (a head its own lane group, so the
# kernel takes the grouped heads in place).
with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                       "tiny-trinity.json")) as _f:
    TINY = json.load(_f)
# ... of which the whole-model cases here hold layers 1-3 (a dense sliding
# layer, a sliding and a full layer with experts: every kind, in a program
# that compiles in half the time)
TINY.update(num_hidden_layers=3, layer_indices=[1, 2, 3])
SHAPE = reference.shape_of(TINY)
KINDS = [kind for _, kind, _ in reference.layers_of(TINY)]
DIMS = gqa.GQADims(64, 4, 2, 128, 8, 10000.0, 1e-5)
VOCAB = TINY["vocab_size"]


def tiny(**run):
    return dict(TINY, run=dict(TINY["run"], **run))


def rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def seeded(config, prompt_len, new, seed=3):
    """``(weights, the program's parameters on the device)``."""
    weights = family.decoder_params(config, seed, prompt_len, new)
    return weights, jax.tree.map(jnp.asarray, weights.host_params())


def prompts(rows, length, seed=0):
    return family.prompts(VOCAB, rows, length, seed, 1)[0]


def scored(config, params, prompt, next_ids):
    """The decoder's log-probabilities under ``next_ids``, ``[rows, n + 1,
    vocab]``: its own prefill, carry and steps (``decoding.make_scorer``)."""
    prog = pt.build(trinity.make_scorer(family.program_config(config)))
    return np.asarray(prog.apply(params, {}, training=False, prompt_ids=prompt,
                                 next_ids=next_ids)[0]["logp"])


def reference_logp(params, ids, first):
    lg = reference.logits(family.reference_params(params, TINY),
                          jnp.asarray(ids), SHAPE, KINDS, first=first)
    return np.asarray(jax.nn.log_softmax(lg, axis=-1))


# -- (a) one layer's prefill, window and full --------------------------------------


def attention_layer(seed):
    d, qw, kvw, hd = DIMS.d_model, DIMS.q_width, DIMS.kv_width, DIMS.head_dim
    return {"attn_norm/g": 1 + rand(seed, d, scale=0.1),
            "q/w": rand(seed + 1, d, qw, scale=d ** -0.5),
            "k/w": rand(seed + 2, d, kvw, scale=d ** -0.5),
            "v/w": rand(seed + 3, d, kvw, scale=d ** -0.5),
            "gate/w": rand(seed + 4, d, qw, scale=d ** -0.5),
            "q_norm/g": 1 + rand(seed + 5, hd, scale=0.1),
            "k_norm/g": 1 + rand(seed + 6, hd, scale=0.1),
            "o/w": rand(seed + 7, qw, d, scale=qw ** -0.5),
            "post_norm/g": 1 + rand(seed + 8, d, scale=0.1)}


def reference_layer(x, p, kind):
    return reference.attention_part(
        x, family.reference_attention(lambda n: p[n[len("mixer/"):]]), SHAPE,
        kind)


@pytest.mark.parametrize("s", [5, 8, 21])
def test_a_window_layer_s_prefill_against_reference(highest, s):
    """One piece from an empty window: the flash kernel under ``window=``
    with 4 query heads on 2 key heads, the slots that hold nothing masked."""
    p, x = attention_layer(10), rand(1, 2, s, 64)
    held = (jnp.zeros((2, DIMS.window, DIMS.kv_width)),) * 2
    since = profiler.time.time_ns()
    got, held = gqa.window_prefill(x, p, DIMS, held, jnp.int32(0))
    want = reference_layer(x, p, reference.SLIDING)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert held[0].shape == (2, DIMS.window, DIMS.kv_width)
    plan = [sp[4] for sp in profiler.spans(since) if sp[0] == "attn.plan"][-1]
    assert (plan["kind"], plan["heads"], plan["kv_heads"], plan["head_dim"],
            plan["window"], plan["rotary"], plan["form"], plan["keys"]) == (
                "window", 4, 2, 128, 8, True, "prefill", 8 + s)
    flash = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert (flash["kv_heads"], flash["window"], flash["layout"]) == (2, 8, "bsd")


@pytest.mark.parametrize("s", [5, 21])
def test_a_full_layer_s_prefill_against_reference(highest, s):
    """One piece at position 0 into an empty cache longer than the piece:
    nothing rotated, every key up to the query's own, the kernel told where
    the piece lies (``q_offset``)."""
    p, x = attention_layer(20), rand(2, 2, s, 64)
    cache = (jnp.zeros((2, 48, DIMS.kv_width)),) * 2
    since = profiler.time.time_ns()
    got, cache = gqa.full_prefill(x, p, DIMS, cache, jnp.int32(0))
    want = reference_layer(x, p, reference.FULL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(cache[0][:, s:]).max()) == 0.0
    plan = [sp[4] for sp in profiler.spans(since) if sp[0] == "attn.plan"][-1]
    assert (plan["kind"], plan["rotary"], plan["form"], plan["keys"]) == (
        "full", False, "prefill", 48)
    flash = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert flash["kv_heads"] == 2 and flash["q_offset"] and not flash["window"]


def test_a_layer_in_pieces_then_steps_is_the_layer_whole(highest):
    """A window layer and a full layer, each over 30 positions as two
    pieces and then one-token steps (the ring wraps at 8, 16 and 24): every
    position's output is the reference's over the whole sequence."""
    x = rand(3, 2, 30, 64)
    for kind, p in ((reference.SLIDING, attention_layer(30)),
                    (reference.FULL, attention_layer(40))):
        want = np.asarray(reference_layer(x, p, kind))
        sliding = kind == reference.SLIDING
        carried = (jnp.zeros((2, DIMS.window if sliding else 32,
                              DIMS.kv_width)),) * 2
        piece = gqa.window_prefill if sliding else gqa.full_prefill
        step = jax.jit(gqa.window_decode if sliding else gqa.full_decode,
                       static_argnums=2)
        outs = []
        for p0, n in ((0, 6), (6, 7)):      # pieces shorter than the window
            y, carried = piece(x[:, p0:p0 + n], p, DIMS, carried, jnp.int32(p0))
            outs.append(y)
        if sliding:
            carried = kv_ring.ring_of(carried, 13, DIMS.window)
        for t in range(13, 30):
            y, carried = step(x[:, t:t + 1], p, DIMS, carried, jnp.int32(t))
            outs.append(y)
        np.testing.assert_allclose(np.concatenate(outs, axis=1), want,
                                   atol=3e-5, err_msg=kind)


# -- (b) prefill, then steps through the ring and the full cache ----------------------


@pytest.mark.parametrize("p_len", [5, 21], ids=["shorter_than_the_window",
                                                "longer_than_the_window"])
def test_prefill_then_steps_are_the_full_forward_at_every_position(highest,
                                                                   p_len):
    """20 steps after a prompt of 5 or 21 with a window of 8: the ring
    wraps twice and more; at every generated position the decoder's
    distribution is the reference's over the whole sequence so far."""
    new = 20
    _, params = seeded(TINY, p_len, new)
    prompt = prompts(2, p_len)
    next_ids = prompts(2, new - 1, seed=1)
    got = scored(TINY, params, prompt, next_ids)
    want = reference_logp(params, np.concatenate([prompt, next_ids], 1),
                          first=p_len - 1)
    assert got.shape == want.shape == (2, new, VOCAB)
    np.testing.assert_allclose(got, want, atol=2e-4)


# -- (c) a prefill in pieces ---------------------------------------------------------------


@pytest.mark.parametrize("chunk", [4, 8, 16], ids=[
    "piece_shorter_than_the_window", "piece_equal_to_the_window",
    "piece_longer_than_the_window"])
def test_a_prefill_in_pieces_is_a_prefill_in_one_piece(highest, chunk):
    """37 tokens in pieces of 4, 8 or 16 (a scan, then a tail) against one
    piece of 64: the same distributions for the first token and for two
    steps after it."""
    np.testing.assert_allclose(_in_pieces(chunk), _in_pieces(64), atol=2e-4)


@functools.lru_cache(maxsize=None)
def _in_pieces(chunk):
    _, params = seeded(TINY, 37, 3)
    prompt, next_ids = prompts(2, 37, seed=2), prompts(2, 2, seed=3)
    with jax.default_matmul_precision("highest"):
        return scored(tiny(chunk=chunk), params, prompt, next_ids)


def test_the_plans_say_two_cache_shapes_and_how_the_prompt_is_walked():
    prompt = prompts(2, 37)
    prog = pt.build(trinity.make_generator(family.program_config(TINY),
                                           max_new_tokens=5))
    since = profiler.time.time_ns()
    jax.eval_shape(lambda: prog.init(jax.random.PRNGKey(0), prompt_ids=prompt))
    by = lambda name: [sp[4] for sp in profiler.spans(since) if sp[0] == name]
    plan = by("decode.plan")[-1]
    assert plan["cache_kind"] == "kv" and plan["kv_heads"] == 2
    assert (plan["window_layers"], plan["full_layers"], plan["window"]) == (2, 1, 8)
    # two rings of 8 keys, one cache of 42 positions padded to 48: k and v
    assert plan["window_kv_bytes"] == 2 * 2 * 2 * 8 * 256 * 4
    assert plan["full_kv_bytes"] == 1 * 2 * 2 * 48 * 256 * 4
    assert plan["cache_bytes"] == plan["window_kv_bytes"] + plan["full_kv_bytes"]
    assert plan["lane_width"] == 256 and plan["full_len"] == 48
    assert by("prefill.plan")[-1]["chunk"] == 16
    assert by("prefill.plan")[-1]["pieces"] == 3
    held = by("moe.plan")[-1]
    assert (held["experts_total"], held["experts_held"], held["first_expert"],
            held["top_k"]) == (16, 4, 8, 2)
    forms = {(a["kind"], a["form"]) for a in by("attn.plan")}
    assert forms == {("window", "prefill"), ("full", "prefill"),
                     ("window", "step"), ("full", "step")}


# -- (d) the shares add up -------------------------------------------------------------------


def test_the_shares_add_up(highest):
    """16 experts over 4 ranks: the four ranks' ``moe_held`` parts, plus
    the shared expert counted once, under the norm after and with the
    residual, are the uncut reference layer (every expert held by one
    rank)."""
    d, f, t = 64, 32, 40
    x = rand(60, 1, t, d)
    lp = {"ffn_norm": 1 + rand(61, d, scale=0.1),
          "ffn_post_norm": 1 + rand(62, d, scale=0.1),
          "router": rand(63, d, 16, scale=d ** -0.5),
          "select_bias": rand(64, 16, scale=0.3),
          "shared_gate": rand(65, d, f, scale=d ** -0.5),
          "shared_up": rand(66, d, f, scale=d ** -0.5),
          "shared_down": rand(67, f, d, scale=f ** -0.5),
          "experts_gate": rand(68, 16, d, f, scale=d ** -0.5),
          "experts_up": rand(69, 16, d, f, scale=d ** -0.5),
          "experts_down": rand(70, 16, f, d, scale=f ** -0.5)}
    m = blocks.rms_norm(x, lp["ffn_norm"])[0]
    experts, weights = moe.sigmoid_topk_route(
        m, lp["router"], lp["select_bias"], 2, TINY["route_scale"])
    parts = sum(moe.moe_held(
        m, experts, weights, *(lp[k][4 * rank:4 * rank + 4] for k in (
            "experts_gate", "experts_up", "experts_down")),
        first_expert=4 * rank, experts_held=4, experts_total=16)
        for rank in range(4))
    shared = blocks.gated_ffn(m, lp["shared_gate"], lp["shared_up"],
                              lp["shared_down"])
    got = x[0] + blocks.rms_norm(shared + parts, lp["ffn_post_norm"])
    want = reference.ffn_part(x, lp, SHAPE._replace(held=16, rank=0))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    # and one rank's share alone is the program's layer for that rank
    one = reference.ffn_part(
        x, {**lp, **{k: lp[k][8:12] for k in ("experts_gate", "experts_up",
                                              "experts_down")}}, SHAPE)[0]
    assert np.abs(np.asarray(one - want)).max() > 1e-2


# -- (e) the served path ------------------------------------------------------------------------


def test_the_served_generator_returns_what_the_scorer_scores_highest(tmp_path,
                                                                     highest):
    """``export_decoder(model=models.trinity)`` -> ``decode_server``: a
    bucket-sized request and a single prompt that coalesces and pads both
    return the ids that ``make_scorer`` scores highest at every step."""
    from paddle_tpu.fleet import decode

    new = 6
    _, params = seeded(TINY, 19, new)
    prompt = prompts(2, 19, seed=4)
    cfg = family.program_config(TINY)
    decode.export_decoder(str(tmp_path / "m"), cfg, new, prompt, params=params,
                          model=trinity)
    server = decode.decode_server(str(tmp_path / "m"), max_wait_ms=1)
    try:
        whole = server.submit({"prompt_ids": prompt}).result(timeout=120)
        one = server.submit({"prompt_ids": prompt[1:]}).result(timeout=120)
    finally:
        server.close(drain=False, timeout=30)
    ids = np.asarray(whole["ids"])
    assert ids.shape == (2, new)
    assert np.array_equal(np.asarray(one["ids"]), ids[1:])
    logp = scored(TINY, params, prompt, ids[:, :-1])
    ended = np.cumsum(ids == 2, axis=1) - (ids == 2) > 0
    assert (np.where(ended, 2, np.argmax(logp, -1)) == ids).all()


# -- (f) the small check ---------------------------------------------------------------------------


def test_the_small_check_passes_as_stated_and_fails_in_a_lower_precision(
        highest, monkeypatch):
    """The benchmark's own check at the tiny size, under limits for the
    float32 the toy states (the cell's are bfloat16's, read on the chip):
    float32 ids pass with no gap; the same weights rounded to an 8-bit
    float, the precision below the bfloat16 that the cell's configuration
    states, fail it, in the program and in the reference alike; a reference
    without its window reads a gap."""
    monkeypatch.setattr(family, "AGREE_FLOOR", 0.95)
    monkeypatch.setattr(family, "MEAN_GAP_LIMIT", 0.008)
    new = 16        # 32 served tokens: a fault's misses outnumber a tenth
    weights, params = seeded(TINY, 21, new)
    prompt = prompts(2, 21, seed=5)
    gen = pt.build(trinity.make_generator(family.program_config(TINY),
                                          max_new_tokens=new))
    run = lambda p: np.asarray(gen.apply(p, {}, training=False,
                                         prompt_ids=prompt)[0]["ids"])
    served = run(params)
    good = family.served_check(TINY, weights, prompt, served)
    assert good["ok"] and good["worst_logit_gap"] < 1e-3, good
    float8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
    low = run({name: float8(a) if a.ndim >= 2 else a
               for name, a in params.items()})
    assert not family.served_check(TINY, weights, prompt, low)["ok"]
    in_float8 = lambda sh, part, layer, kind, lp: (sh, kind, {
        k: float8(v) if v.ndim >= 2 else v for k, v in lp.items()})
    assert not family.served_check(TINY, weights, prompt, served,
                                   edit=in_float8)["ok"]
    no_window = lambda sh, part, layer, kind, lp: (
        sh._replace(window=10 ** 9), kind, lp)
    assert family.served_check(TINY, weights, prompt, served,
                               edit=no_window)["worst_logit_gap"] > 1e-3


def test_the_flash_backward_refuses_a_window_and_grouped_heads():
    from paddle_tpu.ops.flash_attention import flash_attention

    q, k = jnp.zeros((1, 64, 4 * 128)), jnp.zeros((1, 64, 2 * 128))
    loss = lambda q: flash_attention(q, k, k, causal=True, num_heads=4,
                                     kv_heads=2).sum()
    with pytest.raises(Exception):
        jax.grad(loss)(q)
