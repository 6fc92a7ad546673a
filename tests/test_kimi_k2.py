"""Kimi-K2 (the DeepseekV3 decoder) at tiny sizes on the CPU, against the one
plain reference, ``benchmarks/reference/kimi_k2.py``: the pieces
(RMSNorm, gated FFN, YaRN, latent attention in both forms, the
bias-corrected sigmoid router, the held share of an expert layer), the
whole model through prefill and cache, the served path, and the flash
forward at unequal widths. Seeded weights; float32 unless a case says
otherwise.
"""

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
from benchmarks.families import kimi_k2 as family
from benchmarks.reference import kimi_k2 as reference
from paddle_tpu.core import profiler
from paddle_tpu.layers import blocks, decoding, latent
from paddle_tpu.models import kimi_k2
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.parallel import moe

# A configuration file of the family, tiny: every mechanism of the published
# one (a leading dense layer, a YaRN blend that has fast, blended and slow
# dimensions, mscale_all_dim set, a held block that is not the first).
TINY = {
    "family": "kimi_k2", "vocab_size": 61, "hidden_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 48, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "n_routed_experts": 4, "num_experts_per_tok": 3,
    "n_shared_experts": 1, "moe_intermediate_size": 16,
    "routed_scaling_factor": 2.827, "rms_norm_eps": 1e-5, "rope_theta": 50.0,
    "max_position_embeddings": 64,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 1, "factor": 8, "mscale": 1,
                     "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "published": {"n_routed_experts": 16},
    "deployment": {"expert_rank": 1},
    "run": {"dtype": "float32"},
}
SHAPE = reference.shape_of(TINY)
DIMS = latent.MLADims(32, 4, 24, 16, 8, 8, 12)
YARN = latent.Yarn(50.0, 8, 16, 4, 1, 1, 1)


def tiny(**run):
    return dict(TINY, run=dict(TINY["run"], **run))


def rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def init_params(config, prompt, new=4, scorer=False):
    cfg = family.program_config(config)
    prog = pt.build(decoding.make_scorer(kimi_k2._decoder, cfg) if scorer
                    else kimi_k2.make_generator(cfg, max_new_tokens=new))
    feed = {"prompt_ids": prompt}
    if scorer:
        feed["next_ids"] = np.zeros((prompt.shape[0], new), np.int32)
    params, _ = prog.init(jax.random.PRNGKey(5), **feed)
    # a selection bias large enough to decide selections at this size
    for k in params:
        if k.endswith("select_bias"):
            params[k] = rand(11, *params[k].shape, scale=0.05)
    return prog, params


# -- the pieces ---------------------------------------------------------------------


def _rms():
    x, g = rand(0, 3, 5, 32), rand(1, 32)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) * g
    return blocks.rms_norm(x, g), want


def _ffn():
    x, wg, wu, wd = rand(0, 5, 32), rand(1, 32, 48), rand(2, 32, 48), rand(3, 48, 32)
    g = np.asarray(x @ wg, np.float64)
    want = (g / (1 + np.exp(-g)) * np.asarray(x @ wu)) @ np.asarray(wd)
    return blocks.gated_ffn(x, wg, wu, wd), want


def _yarn():
    # the closed form at the published numbers: find_correction_range(32, 1,
    # 64, 50000, 4096) is (8, 20); below it the frequency is kept, above it
    # divided by 64, between them blended linearly
    y = latent.Yarn(50000.0, 64, 4096, 32, 1, 1, 1)
    i = np.arange(32)
    f = 50000.0 ** (-2.0 * i / 64)
    ramp = np.clip((i - 8) / 12, 0, 1)
    return latent.yarn_frequencies(64, y), f / 64 * ramp + f * (1 - ramp)


def _rope():
    x, pos, f = rand(0, 2, 6, 8), jnp.arange(3, 9), jnp.asarray([1.0, 0.5, 0.1, 0.01])
    ang = np.asarray(pos)[:, None] * np.asarray(f)[None]
    z = (np.asarray(x)[..., 0::2] + 1j * np.asarray(x)[..., 1::2]) * np.exp(1j * ang)
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    return blocks.rope(x, pos, f), want


@pytest.mark.parametrize("piece", [_rms, _ffn, _yarn, _rope],
                         ids=["rms_norm", "gated_ffn", "yarn_frequencies", "rope"])
def test_piece_against_its_closed_form(highest, piece):
    got, want = piece()
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_yarn_scales_are_the_published_ones():
    """K2.5 sets mscale = mscale_all_dim = 1: cos and sin are scaled by 1,
    the softmax by (0.1 ln 64 + 1)^2 / sqrt(192)."""
    dims = latent.MLADims(7168, 64, 1536, 512, 128, 64, 128)
    y = latent.Yarn(50000.0, 64, 4096, 32, 1, 1, 1)
    assert latent.yarn_cos_sin_scale(y) == 1.0
    assert latent.softmax_scale(dims, y) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert (reference.yarn_find_correction_range(32, 1, 64, 50000, 4096)
            == (8, 20))


# -- latent attention -----------------------------------------------------------------


def mla_layer(seed=3):
    """One attention's parameters, as the program holds them and as the
    reference names them."""
    prog = pt.build(lambda x: latent.mla_params(DIMS, jnp.float32))
    p, _ = prog.init(jax.random.PRNGKey(seed), x=np.zeros(1))
    p = {k.split("mla/", 1)[1]: v for k, v in p.items()}
    p["attn_norm/g"], p["q_norm/g"], p["kv_norm/g"] = (
        1 + rand(1, 32, scale=0.1), 1 + rand(2, 24, scale=0.1),
        1 + rand(4, 16, scale=0.1))
    return p, family.reference_attention(lambda n: p[n.split("mla/", 1)[1]])


@pytest.mark.parametrize("s", [12, 37], ids=["whole_tiles", "padded_keys"])
def test_mla_expanded_against_reference(highest, s):
    p, ref_p = mla_layer()
    x = rand(7, 2, s, 32)
    since = profiler.time.time_ns()
    got, (c, r) = latent.mla_prefill(x, p, DIMS, YARN)
    want = reference.attention_part(x, ref_p, SHAPE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert c.shape == (2, s, 16) and r.shape == (2, 8, s)
    (plan,) = [s[4] for s in profiler.spans(since) if s[0] == "mla.plan"]
    assert plan["form"] == "expanded" and plan["kv_lora"] == 16
    assert (plan["nope_dim"], plan["rope_dim"], plan["v_dim"]) == (8, 8, 12)
    assert plan["softmax_scale"] == pytest.approx(
        16 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)


def test_mla_absorbed_step_agrees_with_expanded_at_every_position(highest):
    """Tokens fed one at a time through the latent cache give, at every
    position, what the expanded form gives for the whole sequence; the
    cache ends up holding the expanded form's latents."""
    p, _ = mla_layer()
    s = 10
    x = rand(9, 2, s, 32)
    want, (c_all, r_all) = latent.mla_prefill(x, p, DIMS, YARN)
    c, r = jnp.zeros((2, s, 16)), jnp.zeros((2, 8, s))
    step = jax.jit(lambda xt, c, r, i: latent.mla_decode(xt, p, c, r, i,
                                                         DIMS, YARN))
    for i in range(s):
        got, c, r = step(x[:, i:i + 1], c, r, jnp.asarray(i, jnp.int32))
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, i]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_all), atol=1e-6)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_all), atol=1e-6)


@pytest.mark.parametrize("dims", [DIMS, latent.MLADims(32, 2, 24, 16, 128,
                                                        64, 128)],
                         ids=["tiny", "lane_groups"])
def test_regrouped_projections_are_the_published_columns(highest, dims):
    """``regrouped``'s two halves of ``q_b`` give, column for column, what
    the published layout's one product gives a head's 128 and 64 of, and
    the flat halves of ``kv_b`` the per-head keys and values; a stack goes
    through as its layers do; what has been through comes back as it is.
    At widths that fill lane groups the expanded form then hands the
    kernel the parts apart (``flash.plan``: ``bsd``, ``rot``) and still
    agrees with the absorbed step at every position."""
    prog = pt.build(lambda x: latent.mla_params(dims, jnp.float32))
    p, _ = prog.init(jax.random.PRNGKey(3), x=np.zeros(1))
    p = {k.split("mla/", 1)[1]: v for k, v in p.items()}
    g = latent.regrouped(p, dims)
    assert "q_b/w" not in g and latent.regrouped(g, dims) is g
    h, c_q, c_kv = dims.heads, rand(5, 2, 7, dims.q_lora), rand(6, 2, 7, dims.kv_lora)
    q = jnp.matmul(c_q, p["q_b/w"]).reshape(2, 7, h, dims.qk)
    for got, want in (
            (jnp.matmul(c_q, g["q_b/w_nope"]), q[..., :dims.nope]),
            (jnp.matmul(c_q, g["q_b/w_rope"]), q[..., dims.nope:]),
            (jnp.matmul(c_kv, g["kv_b_k/w_flat"]),
             jnp.einsum("bsc,hnc->bshn", c_kv, p["kv_b_k/w"])),
            (jnp.matmul(c_kv, g["kv_b_v/w_flat"]),
             jnp.einsum("bsc,hcv->bshv", c_kv, p["kv_b_v/w"]))):
        np.testing.assert_allclose(np.asarray(got.reshape(want.shape)),
                                   np.asarray(want), atol=1e-6)
    stacked = latent.regrouped(
        {k: jnp.stack([v, 2 * v]) for k, v in p.items()}, dims)
    for k, v in g.items():
        np.testing.assert_array_equal(np.asarray(stacked[k][0]), np.asarray(v))
        np.testing.assert_array_equal(np.asarray(stacked[k][1]),
                                      np.asarray(2 * v))
    if dims.nope % 128:
        return
    s = 6
    x = rand(9, 2, s, dims.d_model)
    since = profiler.time.time_ns()
    want, (c_all, r_all) = latent.mla_prefill(x, g, dims, YARN)
    plan = [sp[4] for sp in profiler.spans(since) if sp[0] == "flash.plan"][-1]
    assert (plan["layout"], plan["rot"], plan["d"]) == ("bsd", 64, 128)
    parts = [sp[4]["score_parts"] for sp in profiler.spans(since)
             if sp[0] == "mla.plan"]
    assert parts == [[128, 64]]
    c, r = jnp.zeros((2, s, dims.kv_lora)), jnp.zeros((2, dims.rope, s))
    for i in range(s):
        got, c, r = latent.mla_decode(x[:, i:i + 1], p, c, r,
                                      jnp.asarray(i, jnp.int32), dims, YARN)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, i]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_all), atol=1e-6)


# -- routing and the held share ---------------------------------------------------------


def router_case(seed=0, t=40, d=32, total=16):
    return (rand(seed, t, d), rand(seed + 1, d, total, scale=d ** -0.5),
            rand(seed + 2, total, scale=0.05))


def test_router_against_reference(highest):
    """Sigmoid scores; the bias decides selections that the raw scores
    would not, the weights are the unbiased scores of the selected,
    normalised to 1 and scaled; the selected set is the reference's,
    exactly."""
    h, w_r, bias = router_case()
    experts, weights = moe.sigmoid_topk_route(h, w_r, bias, 3, 2.827)
    idx, w, scores = reference.route(h, {"router": w_r, "select_bias": bias},
                                     SHAPE)
    assert np.array_equal(np.sort(experts, -1), np.sort(idx, -1))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(w), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.827, rtol=1e-6)
    scores = np.asarray(scores)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(scores, np.asarray(experts), -1)
        / np.take_along_axis(scores, np.asarray(experts), -1).sum(-1, keepdims=True)
        * 2.827, rtol=1e-6)
    unbiased, _ = moe.sigmoid_topk_route(h, w_r, jnp.zeros_like(bias), 3, 2.827)
    moved = (np.sort(unbiased, -1) != np.sort(experts, -1)).any(-1)
    assert 0 < moved.sum() < len(moved)     # the bias decides some, not all


def expert_case(total=16, d=32, f=16, seed=20):
    return {"experts_gate": rand(seed, total, d, f, scale=d ** -0.5),
            "experts_up": rand(seed + 1, total, d, f, scale=d ** -0.5),
            "experts_down": rand(seed + 2, total, f, d, scale=f ** -0.5)}


def held_part(h, w_r, bias, banks, rank, held, total=16, top_k=3, offset=0):
    """The program's part for one rank, its banks ``[held, ...]`` placed
    at ``offset`` of a longer stack of groups."""
    experts, weights = moe.sigmoid_topk_route(h, w_r, bias, top_k, 2.827)

    def bank(name):
        mine = banks[name][rank * held:(rank + 1) * held]
        pad = jnp.zeros((offset,) + mine.shape[1:], mine.dtype)
        return jnp.concatenate([pad + 7.0, mine, pad - 7.0])   # never read

    return moe.moe_held(h, experts, weights, bank("experts_gate"),
                        bank("experts_up"), bank("experts_down"),
                        first_expert=rank * held, experts_held=held,
                        experts_total=total, bank_offset=offset)


@pytest.mark.parametrize("rank", range(4))
def test_expert_layer_with_a_held_block_against_reference(highest, rank,
                                                          monkeypatch):
    """Experts ``4 rank .. 4 rank + 4`` of 16: the sorted, grouped product
    against the reference's loop over the same block, with blocks of 16
    pairs so that the walk takes several."""
    monkeypatch.setattr(moe, "PAIR_BLOCK", 16)
    h, w_r, bias = router_case()
    banks = expert_case()
    since = profiler.time.time_ns()
    got = held_part(h, w_r, bias, banks, rank, 4, offset=4 * (rank % 2))
    lp = {"router": w_r, "select_bias": bias,
          **{k: v[4 * rank:4 * rank + 4] for k, v in banks.items()}}
    want = reference.routed_part(h, lp, SHAPE, rank, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(want)).max() > 0.1
    plan = [s[4] for s in profiler.spans(since) if s[0] == "moe.plan"][-1]
    assert (plan["experts_total"], plan["experts_held"], plan["first_expert"],
            plan["top_k"], plan["tokens"], plan["form"]) == (
                16, 4, 4 * rank, 3, 40, "ragged_dot")
    assert plan["expert_bytes_held"] == 4 * 3 * 32 * 16 * 4


def test_the_shares_add_up(highest):
    """16 experts over 4 ranks: the four ranks' routed parts, plus the
    shared expert and the residual counted once, are the uncut reference
    layer (every expert held by one rank)."""
    h, w_r, bias = router_case(seed=30)
    banks = expert_case(seed=40)
    shared = {"shared_gate": rand(50, 32, 16, scale=32 ** -0.5),
              "shared_up": rand(51, 32, 16, scale=32 ** -0.5),
              "shared_down": rand(52, 16, 32, scale=16 ** -0.5)}
    x = h[None]                                           # [1, t, d]
    norm = 1 + rand(53, 32, scale=0.1)
    hn = blocks.rms_norm(x, norm)[0]
    parts = sum(held_part(hn, w_r, bias, banks, rank, 4) for rank in range(4))
    got = x[0] + parts + blocks.gated_ffn(
        hn, shared["shared_gate"], shared["shared_up"], shared["shared_down"])
    whole = SHAPE._replace(held=16, rank=0)
    want = reference.ffn_part(x, {"ffn_norm": norm, "router": w_r,
                                  "select_bias": bias, **shared, **banks},
                              whole)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


@pytest.mark.parametrize("block", [8, 2048], ids=["many_blocks", "one_block"])
def test_nothing_is_dropped_when_every_token_takes_one_expert(highest, block,
                                                              monkeypatch):
    """A routing that sends all 40 tokens to expert 5 (and two others
    held elsewhere): every token gets expert 5's output at its weight,
    whatever the block."""
    monkeypatch.setattr(moe, "PAIR_BLOCK", block)
    h, _, _ = router_case()
    banks = expert_case()
    experts = jnp.tile(jnp.asarray([[5, 0, 12]], jnp.int32), (40, 1))
    weights = jnp.tile(jnp.asarray([[1.5, 0.7, 0.6]], jnp.float32), (40, 1))
    got = moe.moe_held(h, experts, weights,
                       *(banks[k][4:8] for k in ("experts_gate", "experts_up",
                                                 "experts_down")),
                       first_expert=4, experts_held=4, experts_total=16)
    want = 1.5 * reference.ffn(h, banks["experts_gate"][5],
                               banks["experts_up"][5], banks["experts_down"][5])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(got)).min(axis=-1).max() > 0   # no row left zero


# -- the whole model --------------------------------------------------------------------

# bfloat16 against the float32 reference, on log-probabilities of standard
# deviation about 1 over 61 ids: three layers of bfloat16 rounding (2**-9 a
# rounding) read 0.02 to 0.05 at worst here; an 8-bit path (2**-4 a
# rounding, 32 times coarser) reads past 0.5, and a wrong expert or
# position moves a log-probability by 0.3 or more.
BF16_LOGP_TOL = 0.12


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", BF16_LOGP_TOL)])
def test_whole_model_prefill_then_cache_against_reference(highest, dtype, tol):
    """Prefill of 9 tokens, then 5 more through the latent cache: the
    log-probabilities at every position against the reference's one full
    forward over all 14."""
    config = tiny(dtype=dtype)
    rng = np.random.RandomState(2)
    prompt = rng.randint(3, 61, (2, 9)).astype(np.int32)
    nxt = rng.randint(3, 61, (2, 5)).astype(np.int32)
    prog, params = init_params(config, prompt, new=5, scorer=True)
    out, _ = prog.apply(params, {}, training=False, prompt_ids=prompt,
                        next_ids=nxt)
    ids = np.concatenate([prompt, nxt], axis=1)
    ref = reference.logits(family.reference_params(params, config),
                           jnp.asarray(ids), SHAPE, first=8)
    want = jax.nn.log_softmax(ref, axis=-1)
    assert out["logp"].shape == want.shape == (2, 6, 61)
    assert np.abs(np.asarray(out["logp"]) - np.asarray(want)).max() <= tol
    if dtype == "bfloat16":     # weights are held in bfloat16, router in f32
        assert params["moe/experts/gate/w"].dtype == jnp.bfloat16
        assert params["moe/experts/router/w"].dtype == jnp.float32
        # the tolerance is a check: an 8-bit weight path fails it
        coarse = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                      if v.dtype == jnp.bfloat16 else v)
                  for k, v in params.items()}
        out8, _ = prog.apply(coarse, {}, training=False, prompt_ids=prompt,
                             next_ids=nxt)
        assert np.abs(np.asarray(out8["logp"]) - np.asarray(want)).max() > tol


def test_generator_emits_the_scorer_s_argmax(highest):
    """Greedy ids are the argmax of the distributions the scorer gives
    under those ids, and the trace leaves one latent ``decode.plan``."""
    prompt = np.random.RandomState(4).randint(3, 61, (2, 9)).astype(np.int32)
    gen, params = init_params(TINY, prompt, new=5)
    since = profiler.time.time_ns()
    ids = np.asarray(gen.apply(params, {}, training=False,
                               prompt_ids=prompt)[0]["ids"])
    (plan,) = [s[4] for s in profiler.spans(since) if s[0] == "decode.plan"]
    assert plan["cache_kind"] == "latent" and plan["lane_width"] == 16
    assert plan["cache_bytes"] == 3 * 2 * 14 * (16 + 8) * 4
    scorer = pt.build(decoding.make_scorer(kimi_k2._decoder,
                                           family.program_config(TINY)))
    logp = scorer.apply(params, {}, training=False, prompt_ids=prompt,
                        next_ids=ids[:, :-1])[0]["logp"]
    ended = np.cumsum(ids == 2, axis=1) - (ids == 2) > 0
    assert (np.where(ended, 2, np.argmax(logp, -1)) == ids).all()


def test_served_ids_are_the_direct_call_s(tmp_path, highest):
    """``export_decoder`` -> ``decode_server``: a bucket-sized request and
    a single prompt that coalesces and pads both return the ids of a direct
    call of the program."""
    from paddle_tpu.fleet import decode

    prompt = np.random.RandomState(6).randint(3, 61, (2, 9)).astype(np.int32)
    cfg = family.program_config(TINY)
    gen, params = init_params(TINY, prompt)
    direct = np.asarray(gen.apply(params, {}, training=False,
                                  prompt_ids=prompt)[0]["ids"])
    decode.export_decoder(str(tmp_path / "m"), cfg, 4, prompt, params=params,
                          model=kimi_k2)
    server = decode.decode_server(str(tmp_path / "m"), max_wait_ms=1)
    try:
        whole = server.submit({"prompt_ids": prompt}).result(timeout=120)
        one = server.submit({"prompt_ids": prompt[1:]}).result(timeout=120)
    finally:
        server.close(drain=False, timeout=30)
    assert np.array_equal(np.asarray(whole["ids"]), direct)
    assert np.array_equal(np.asarray(one["ids"]), direct[1:])


def test_family_check_passes_on_served_ids_and_fails_on_wrong_ones(highest):
    """The benchmark's own check at the tiny size: greedy ids pass, the
    same ids shifted by one id fail, and so does a reference that leaves
    the routed experts out."""
    prompt = np.random.RandomState(8).randint(3, 61, (4, 9)).astype(np.int32)
    weights = family.decoder_params(TINY, 3, 9, 6)
    gen = pt.build(kimi_k2.make_generator(family.program_config(TINY),
                                          max_new_tokens=6))
    params = jax.tree.map(jnp.asarray, weights.host_params())
    served = np.asarray(gen.apply(params, {}, training=False,
                                  prompt_ids=prompt)[0]["ids"])
    good = family.served_check(TINY, weights, prompt, served)
    assert good["ok"] and good["worst_logit_gap"] < 1e-3, good
    assert not family.served_check(TINY, weights, prompt,
                                   (served + 1) % 61)["ok"]
    no_experts = lambda sh, part, layer, lp: (sh._replace(held=0), lp)
    assert family.served_check(TINY, weights, prompt, served,
                               edit=no_experts)["worst_logit_gap"] > 1e-3


# -- the flash forward at unequal widths ------------------------------------------------


def _dense(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        n, m = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((n, m), bool), m - n), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal,sq,sk,d,dv,scale", [
    (True, 48, 48, 24, 16, None), (False, 40, 56, 24, 16, None),
    (True, 160, 160, 48, 32, 0.11), (True, 48, 48, 16, 24, 0.3)],
    ids=["causal", "full", "two_q_tiles_scaled", "values_wider"])
def test_flash_forward_at_unequal_widths(highest, causal, sq, sk, d, dv, scale):
    q, k, v = rand(0, 2, 3, sq, d), rand(1, 2, 3, sk, d), rand(2, 2, 3, sk, dv)
    since = profiler.time.time_ns()
    got = fa.flash_attention(q, k, v, causal=causal, scale=scale,
                             interpret=True)
    want = _dense(q, k, v, causal, d ** -0.5 if scale is None else scale)
    assert got.shape == (2, 3, sq, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    plan = [s[4] for s in profiler.spans(since) if s[0] == "flash.plan"][-1]
    assert (plan["d"], plan["dv"]) == (d, dv)


def test_flash_backward_refuses_unequal_widths():
    """The dq and dkv kernels take one width: a gradient through unequal
    widths raises, it does not mis-compute; equal widths still
    differentiate, with a scale."""
    q, k, v = rand(0, 1, 2, 32, 24), rand(1, 1, 2, 32, 24), rand(2, 1, 2, 32, 16)
    loss = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                              interpret=True).sum()
    with pytest.raises(NotImplementedError, match="values 16 wide"):
        jax.grad(loss)(q, k, v)
    v = rand(2, 1, 2, 32, 24)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, scale=0.2, interpret=True).sum())(q)
        want = jax.grad(lambda q: _dense(q, k, v, True, 0.2).sum())(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- the family's arithmetic, at the published numbers -----------------------------------


def test_family_counts_at_the_published_widths():
    """The cut of ISSUE 31 by hand: 147.9M parameters a layer outside the
    routed experts, 44.04M an expert, 1.86 of 12 experts touched by 8 rows."""
    config = family_config()
    c = family._counts(config)
    assert c["mla"] == 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 \
        + 8192 * 7168 == 101_122_048
    assert c["shared"] == c["expert"] == 44_040_192
    assert c["mla"] + c["shared"] + c["router"] == 147_914_752
    assert family.experts_touched(config, 8) == pytest.approx(1.8596, abs=1e-3)


def family_config():
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-k2.5-ep32.json")) as f:
        return json.load(f)
