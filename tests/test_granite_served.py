"""Granite-4.0-H at tiny widths: what the traced program says of itself (the
plans, the scopes), the served path (``export_decoder`` -> ``decode_server``
-> ``PredictorServer``) and the benchmark's own check at the toy size.
(``test_granite_hybrid.py`` says what the toy is.)"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from granite_toy import TINY, family, granite_hybrid, prompts, scored, seeded
from paddle_tpu.core import profiler


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (g) plans, scopes and the served path ---------------------------------------------


def test_the_plans_say_what_is_carried_and_how_the_pairs_are_walked():
    prog = pt.build(granite_hybrid.make_generator(family.program_config(TINY),
                                                  max_new_tokens=8))
    prompt = prompts(2, 200)
    shapes = jax.eval_shape(lambda k: prog.init(k, prompt_ids=prompt)[0],
                            jax.random.PRNGKey(0))
    t0 = time.time_ns()
    lowered = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]).lower(
        shapes, prompt)
    spans = profiler.spans(t0)
    (plan,) = [s[4] for s in spans if s[0] == "decode.plan"]
    assert plan["cache_kind"] == "state+kv" and plan["first_step"] == "write_switch"
    assert plan["state_bytes"] == 3 * 2 * 1 * 16 * 128 * 4
    assert plan["tail_bytes"] == 3 * 2 * 3 * 160 * 4
    assert plan["kv_bytes"] == 2 * 2 * plan["full_len"] * 32 * 4
    assert plan["cache_bytes"] == (plan["state_bytes"] + plan["tail_bytes"]
                                   + plan["kv_bytes"])
    assert (plan["state_layers"], plan["kv_layers"]) == (3, 1)
    (walk,) = [s[4] for s in spans if s[0] == "prefill.plan"]
    assert (walk["chunk"], walk["pieces"]) == (80, 3)
    ssd_plans = [s[4] for s in spans if s[0] == "ssd.plan"]
    # a traced call a Mamba-2 layer: the scanned pieces and the tail piece
    assert len(ssd_plans) == 2 * 3
    assert {(p["tokens"], p["chunk"], p["chunks"]) for p in ssd_plans} == {
        (80, 16, 5), (40, 16, 3)}
    assert all(p["heads"] == 4 and p["head_dim"] == 32 and p["d_state"] == 16
               and p["state_bytes_moved"] == 2 * p["state_bytes"]
               and p["operand_bytes"] > 0 for p in ssd_plans)
    moe_plans = [s[4] for s in spans if s[0] == "moe.plan"]
    assert moe_plans and all(
        p["routing"] == "softmax_topk"
        and (p["experts_held"], p["experts_total"], p["top_k"]) == (4, 8, 3)
        for p in moe_plans)
    # a piece's pairs are walked and come back by gathers; a step's two rows
    # go through every held expert
    assert {(p["tokens"], p["back"]) for p in moe_plans} == {
        (160, "gather"), (80, "gather"), (2, "dense")}
    text = lowered.as_text(debug_info=True)
    for scope in ("prefill/", "decode_step/", "mamba2/in_proj", "mamba2/conv",
                  "mamba2/ssd", "mamba2/gated_norm", "mamba2/out_proj", "attn",
                  "router", "moe", "shared", "head"):
        assert scope in text, scope


def test_the_served_generator_returns_what_the_scorer_scores_highest(tmp_path,
                                                                     highest):
    """``export_decoder(model=models.granite_hybrid)`` -> ``decode_server``
    -> ``PredictorServer``: a bucket-sized request and a single prompt that
    coalesces and pads both return the ids that ``make_scorer`` scores
    highest at every step, and the audit beside them."""
    from paddle_tpu.fleet import decode

    new = 6
    _, params = seeded(TINY, 19, new)
    prompt = prompts(2, 19, seed=4)
    cfg = family.program_config(TINY)
    decode.export_decoder(str(tmp_path / "m"), cfg, new, prompt, params=params,
                          model=granite_hybrid)
    server = decode.decode_server(str(tmp_path / "m"), max_wait_ms=1)
    try:
        whole = server.submit({"prompt_ids": prompt}).result(timeout=300)
        one = server.submit({"prompt_ids": prompt[1:]}).result(timeout=300)
        assert server.report()["compiles_since_warmup"] == 0
    finally:
        server.close(drain=False, timeout=30)
    ids = np.asarray(whole["ids"])
    assert ids.shape == (2, new)
    assert set(whole) == {"ids", "audit_dt", "audit_x", "audit_b", "audit_state"}
    assert np.array_equal(np.asarray(one["ids"]), ids[1:])
    logp = scored(TINY, params, prompt, ids[:, :-1])
    ended = np.cumsum(ids == 2, axis=1) - (ids == 2) > 0
    assert (np.where(ended, 2, np.argmax(logp, -1)) == ids).all()


def test_the_small_check_passes_as_stated_and_fails_in_a_lower_precision(
        highest, monkeypatch):
    """The benchmark's own check at the tiny size, under limits for the
    float32 the toy states (the cell's are bfloat16's, read on the chip):
    float32 ids pass with hardly a gap; the same weights rounded to an 8-bit
    float fail it, in the program and in the reference alike; a reference
    whose softmax runs over all the logits reads a gap."""
    monkeypatch.setattr(family, "AGREE_FLOOR", 0.9)
    monkeypatch.setattr(family, "MEAN_GAP_LIMIT", 0.01)
    new = 16
    weights, params = seeded(TINY, 40, new)
    prompt = prompts(2, 40, seed=5)
    gen = pt.build(granite_hybrid.make_generator(family.program_config(TINY),
                                                 max_new_tokens=new))
    run = lambda p: jax.tree.map(np.asarray, gen.apply(
        p, {}, training=False, prompt_ids=prompt)[0])
    served = run(params)
    good = family.served_check(TINY, weights, prompt, served["ids"], audit=served)
    assert good["ok"] and good["worst_logit_gap"] < 0.1, good
    float8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
    low = run({name: float8(a) if a.ndim >= 2 else a
               for name, a in params.items()})
    assert not family.served_check(TINY, weights, prompt, low["ids"],
                                   audit=low)["ok"]
    in_float8 = lambda sh, part, layer, kind, lp: (sh, {
        k: float8(v) if v.ndim >= 2 else v for k, v in lp.items()})
    assert not family.served_check(TINY, weights, prompt, served["ids"],
                                   audit=served, edit=in_float8)["ok"]
    over_all = lambda sh, part, layer, kind, lp: (sh._replace(top_k=8), lp)
    assert family.served_check(TINY, weights, prompt, served["ids"], audit=served,
                               edit=over_all)["mean_logit_gap"] > 1e-3
