"""``layers/decoding.py`` and ``layers/blocks.py`` on a toy served model: one
layer whose mixer is a decayed running sum (a state ``[rows, d]``, the same
at every position) and which also keeps what the sum was handed in a slab
``[rows, total, d]`` written at the token's position, so both kinds of carry
the five real models have are there: one that a first step must not fold
into, one whose position ``p`` belongs to the first generated token. Built
with either form of the first step and walked in chunks of any length; the
model files' own tests hold each real generator to its reference.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import profiler
from paddle_tpu.framework import LayerHelper, name_scope
from paddle_tpu.layers import blocks, decoding

VOCAB, D, WIDTH, DECAY, ROWS = 29, 16, 24, 0.9, 2


def _toy_decoder(cfg, prompt_ids, max_new_tokens):
    """``cfg``: ``{"form": "conditional" | "switch", "chunk": tokens a piece
    of the prefill}``."""
    rows, p_len = prompt_ids.shape
    total = p_len + max_new_tokens
    decoding.check_length(p_len, max_new_tokens, 4096)
    w_emb = decoding.token_embedding(VOCAB, D, jnp.float32)
    with name_scope("layer_0"):
        mix = blocks.params(LayerHelper("mixer", name="mixer"), {
            "attn_norm/g": ((D,), None), "in/w": ((D, D), D)}, None,
            jnp.float32)
        ffn = blocks.gated_ffn_params(D, WIDTH, jnp.float32)
    final_g, w_head = decoding.untied_head(VOCAB, D, jnp.float32)

    def head(x_last):
        return decoding.log_probs(blocks.rms_norm(x_last, final_g), w_head)

    def layer(x, s, slab, p0, write):
        """``x [rows, n, d]`` at positions ``p0 ..``: the sum a position at a
        time, what it was handed into the slab; where ``write`` is false the
        sum stays as it was."""
        u = jnp.matmul(blocks.rms_norm(x, mix["attn_norm/g"]), mix["in/w"])

        def one(s, u_t):
            s = jnp.where(write, DECAY * s + u_t, s)
            return s, s

        s, sums = jax.lax.scan(one, s, u.transpose(1, 0, 2))
        slab = jax.lax.dynamic_update_slice(slab, u, (0, p0, 0))
        x = blocks.ffn_block(x + sums.transpose(1, 0, 2), ffn, 1e-5)
        return x, s, slab, u

    def piece(carried, p0, length):
        s, slab = carried
        ids = jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1)
        x, s, slab, u = layer(w_emb[ids], s, slab, p0, True)
        return (s, slab), (x[:, -1], (u,))

    carried = (jnp.zeros((rows, D)), jnp.zeros((rows, total, D)))
    chunk = min(cfg["chunk"], p_len)
    decoding.record_plans(
        "kv+state", rows, total, 1, 1, "float32", D,
        {"kv": [carried[1]], "state": [carried[0]]},
        prefill={"chunk": chunk, "pieces": -(-p_len // chunk)}, toy=True)
    with jax.named_scope("prefill"):
        (s, slab), x_last, seen = decoding.chunked_walk(piece, carried, p_len,
                                                        chunk)
        first_logp = head(x_last)
    log = decoding.audit_log(rows, max_new_tokens, [((D,), jnp.float32)])

    def audit(state):
        return {"audit_u": decoding.audit_join(seen, state, max_new_tokens)[0],
                "slab": state["slab"], "sum": state["s"]}

    if cfg["form"] == "switch":
        def layers(tokens, carried, index, first):
            x, s, slab, u = layer(w_emb[tokens][:, None], carried["s"],
                                  carried["slab"], index, ~first)
            return x, {"s": s, "slab": slab}, (u,)

        return (decoding.start({"s": s, "slab": slab}, p_len, first_logp, log),
                decoding.step_with_write_switch(layers, head, p_len), audit)

    def layers(tokens, carried, index):
        x, s, slab, _ = layer(w_emb[tokens][:, None], carried["s"],
                              carried["slab"], index, True)
        return x, {"s": s, "slab": slab}

    return (decoding.start({"s": s, "slab": slab}, p_len, first_logp),
            decoding.step_in_conditional(layers, head),
            lambda state: {"slab": state["slab"], "sum": state["s"]})


def _prompt(p_len, seed=0):
    return np.random.RandomState(seed).randint(3, VOCAB, (ROWS, p_len)).astype(
        np.int32)


_PARAMS = {}


def _params():
    if not _PARAMS:
        prog = pt.build(decoding.make_generator(
            _toy_decoder, {"form": "switch", "chunk": 8}, 4))
        _PARAMS.update(prog.init(jax.random.PRNGKey(3),
                                 prompt_ids=_prompt(8))[0])
    return _PARAMS


def _generated(form, chunk, p_len, new):
    prog = pt.build(decoding.make_generator(
        _toy_decoder, {"form": form, "chunk": chunk}, new, eos_id=-1))
    out, _ = prog.apply(_params(), {}, training=False,
                        prompt_ids=_prompt(p_len))
    return jax.tree.map(np.asarray, out)


def _handed(ids):
    """What the sum is handed for tokens ``ids [rows, n]``, by definition."""
    p = _params()
    x = np.asarray(p["tok/embedding_0/w"])[ids]
    h = blocks.rms_norm(jnp.asarray(x), p["layer_0/mixer/attn_norm/g"])
    return np.asarray(jnp.matmul(h, p["layer_0/mixer/in/w"]))


# -- the chunked walk ----------------------------------------------------------------


@pytest.mark.parametrize("chunk,pieces", [(40, 1), (8, 5), (16, 3), (27, 2)],
                         ids=["one_piece", "scan", "scan_and_tail",
                              "piece_and_tail"])
def test_the_walk_s_shapes_agree_with_the_unchunked_pass(chunk, pieces):
    """One piece, a scan over whole chunks, a scan and a shorter tail, one
    chunk and a tail: the same first distribution, the same carried state
    and the same per-position outputs as the prompt in one piece."""
    p_len, params = 40, _params()

    def prefilled(chunk):
        def fn(prompt_ids):
            state0, _, _ = _toy_decoder({"form": "switch", "chunk": chunk},
                                        prompt_ids, 3)
            return {k: state0[k] for k in ("s", "slab", "logp0")}
        since = time.time_ns()
        out, _ = pt.build(fn).apply(params, {}, training=False,
                                    prompt_ids=_prompt(p_len))
        (plan,) = [s[4] for s in profiler.spans(since)
                   if s[0] == "prefill.plan"]
        return jax.tree.map(np.asarray, out), plan

    want, _ = prefilled(p_len)
    got, plan = prefilled(chunk)
    assert (plan["chunk"], plan["pieces"], plan["rows"]) == (chunk, pieces,
                                                             ROWS)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["slab"][:, :p_len],
                               _handed(_prompt(p_len)), rtol=1e-5, atol=1e-6)


def test_the_walk_joins_what_every_position_saw_in_order():
    """``seen``'s parts, a scan's chunks joined and then the tail's, are the
    positions in order, whatever the rank of an entry."""
    def piece(carried, p0, length):
        at = p0 + jnp.arange(length)
        return carried + length, (at[-1], (jnp.tile(at, (ROWS, 1)),
                                           jnp.tile(at[:, None], (ROWS, 1, 3))))

    carried, last, parts = decoding.chunked_walk(piece, jnp.int32(0), 23, 5)
    assert int(carried) == 23 and int(last) == 22
    assert [p[0].shape for p in parts] == [(ROWS, 20), (ROWS, 3)]
    assert [p[1].shape for p in parts] == [(ROWS, 20, 3), (ROWS, 3, 3)]
    flat = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
    wide = np.concatenate([np.asarray(p[1]) for p in parts], axis=1)
    assert (flat == np.arange(23)).all() and (wide[..., 2] == np.arange(23)).all()


# -- the first step ------------------------------------------------------------------


@pytest.mark.parametrize("form", ["conditional", "switch"])
def test_the_first_step_hands_back_the_prefill_s_and_leaves_position_p(form):
    """Either form: the first token is the argmax of the prefill's
    distribution, position ``p + j`` of the slab holds generated token
    ``j`` (none holds the token the first step was handed), and the sum is
    the definition's over the prompt and all generated tokens but the last."""
    p_len, new = 12, 5
    out = _generated(form, 5, p_len, new)
    ids, prompt = out["ids"], _prompt(p_len)
    scorer = pt.build(decoding.make_scorer(
        _toy_decoder, {"form": form, "chunk": 5}))
    logp = np.asarray(scorer.apply(
        _params(), {}, training=False, prompt_ids=prompt,
        next_ids=ids[:, :new - 1])[0]["logp"])
    assert logp.shape == (ROWS, new, VOCAB)
    assert (ids == logp.argmax(-1)).all()
    consumed = np.concatenate([prompt, ids[:, :new - 1]], axis=1)
    handed = _handed(consumed)
    np.testing.assert_allclose(out["slab"][:, :p_len + new - 1], handed,
                               rtol=1e-5, atol=1e-6)
    assert (out["slab"][:, p_len + new - 1:] == 0).all()
    want = np.zeros((ROWS, D), np.float32)
    for t in range(handed.shape[1]):
        want = DECAY * want + handed[:, t]
    np.testing.assert_allclose(out["sum"], want, rtol=1e-4, atol=1e-5)


def test_both_forms_of_the_first_step_give_the_same_tokens_and_state():
    a = _generated("conditional", 6, 15, 6)
    b = _generated("switch", 6, 15, 6)
    assert (a["ids"] == b["ids"]).all()
    np.testing.assert_allclose(a["slab"], b["slab"], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(a["sum"], b["sum"], rtol=1e-6, atol=1e-7)


def test_a_state_keeps_the_contract_s_keys_and_rejects_a_carry_that_takes_them():
    state0 = decoding.start({"s": jnp.zeros(3)}, 7, jnp.zeros((1, 4)))
    assert sorted(state0) == ["first", "given", "index", "logp0", "s"]
    assert state0["given"] == ()
    assert int(state0["index"]) == 7 and bool(state0["first"])
    logged = decoding.start({"s": jnp.zeros(3)}, 7, jnp.zeros((1, 4)),
                            decoding.audit_log(1, 4, [((2,), jnp.float32)]))
    assert logged["given"][0].shape == (1, 3, 2)
    with pytest.raises(Exception, match="carried keys"):
        decoding.start({"index": jnp.zeros(3)}, 7, jnp.zeros((1, 4)))
    with pytest.raises(Exception, match="exceeds max_len 8"):
        decoding.check_length(5, 4, 8, "max_len")


# -- the audit log -------------------------------------------------------------------


@pytest.mark.parametrize("new", [1, 2, 6])
def test_the_audit_join_has_p_plus_new_less_one_positions(new):
    """The prefill's positions, then one a step that consumed a token: what
    the sum was handed for the prompt and for every generated token but the
    last (``new`` 1: the prompt alone; the log still has a place)."""
    p_len = 13
    out = _generated("switch", 5, p_len, new)
    assert out["audit_u"].shape == (ROWS, p_len + new - 1, D)
    consumed = np.concatenate([_prompt(p_len), out["ids"][:, :new - 1]], axis=1)
    np.testing.assert_allclose(out["audit_u"], _handed(consumed), rtol=1e-5,
                               atol=1e-6)
    assert decoding.audit_log(ROWS, new, [((), jnp.float32)])[0].shape == (
        ROWS, max(new - 1, 1))


# -- the plan record -----------------------------------------------------------------


def test_the_plan_record_gives_bytes_by_part_and_their_sum():
    """Arrays or their bytes a part, ``cache_bytes`` their sum, a model's own
    fields beside them; a part named ``cache`` is the sum itself."""
    kv = [jnp.zeros((2, 8, 128), jnp.bfloat16)] * 3
    state = [(jnp.zeros((2, 4, 4), jnp.float32), jnp.zeros((2, 5), jnp.int8))]
    since = time.time_ns()
    decoding.record_plans("kv+state", 2, 8, 4, 3, "bfloat16", 128,
                          {"kv": kv, "state": state, "norm": 40},
                          prefill={"chunk": 4, "chunks": 2}, state_layers=1)
    decoding.record_plans("latent", 2, 8, 4, 3, "bfloat16", 128, {"cache": kv})
    spans = profiler.spans(since)
    first, second = [s[4] for s in spans if s[0] == "decode.plan"]
    assert [s[2] for s in spans] == [0, 0, 0]
    assert first == dict(
        rows=2, max_len=8, heads=4, layers=3, cache_kind="kv+state",
        cache_dtype="bfloat16", lane_width=128, kv_bytes=3 * 2 * 8 * 128 * 2,
        state_bytes=2 * 4 * 4 * 4 + 2 * 5, norm_bytes=40,
        cache_bytes=3 * 2 * 8 * 128 * 2 + 2 * 4 * 4 * 4 + 2 * 5 + 40,
        state_layers=1)
    assert second["cache_bytes"] == 3 * 2 * 8 * 128 * 2
    assert sorted(second) == sorted(
        ["rows", "max_len", "heads", "layers", "cache_kind", "cache_dtype",
         "lane_width", "cache_bytes"])
    (pre,) = [s[4] for s in spans if s[0] == "prefill.plan"]
    assert pre == {"rows": 2, "chunk": 4, "chunks": 2}


@pytest.mark.parametrize("model,form", [
    ("gpt", "conditional"), ("kimi_k2", "conditional"),
    ("trinity", "conditional"), ("minicpm_sala", "write_switch"),
    ("brumby", "write_switch"), ("phi4_flash", "write_switch")])
def test_a_model_s_plan_names_the_first_step_it_is_built_with(model, form):
    """``decode.plan``'s ``first_step`` is a literal beside the call that
    builds the step: each served model's ``_decoder`` names the form it
    calls and not the other (MiniCPM-SALA moved to the write switch in
    PR 48: 36 copies of its carried arrays a step went, PERF.md section 6)."""
    import importlib
    import inspect

    source = inspect.getsource(
        importlib.import_module(f"paddle_tpu.models.{model}")._decoder)
    builder = {"conditional": "decoding.step_in_conditional(",
               "write_switch": "decoding.step_with_write_switch("}
    other = "write_switch" if form == "conditional" else "conditional"
    assert builder[form] in source and builder[other] not in source
    assert f'first_step="{form}"' in source
    assert f'first_step="{other}"' not in source


# -- the one FFN block ---------------------------------------------------------------


def _was_latent(x, p, eps):
    h = blocks.rms_norm(x, p["ffn_norm/g"], eps)
    with jax.named_scope("ffn"):
        gate = jnp.matmul(h, p["gate/w"], preferred_element_type=jnp.float32)
        up = jnp.matmul(h, p["up/w"], preferred_element_type=jnp.float32)
        y = jnp.matmul((jax.nn.silu(gate) * up).astype(h.dtype), p["down/w"])
    return x + y


def _was_sala(x, p, eps, a=0.35):
    h = blocks.rms_norm(x, p["ffn_norm/g"], eps)
    gate = jnp.matmul(h, p["gate/w"], preferred_element_type=jnp.float32)
    up = jnp.matmul(h, p["up/w"], preferred_element_type=jnp.float32)
    y = jnp.matmul((jax.nn.silu(gate) * up).astype(h.dtype), p["down/w"])
    return (x.astype(jnp.float32) + a * y.astype(jnp.float32)).astype(x.dtype)


def _was_brumby(x, p, eps):
    h = blocks.rms_norm(x, p["ffn_norm/g"], eps)
    gate = jnp.matmul(h, p["gate/w"]).astype(jnp.float32)
    up = jnp.matmul(h, p["up/w"], preferred_element_type=jnp.float32)
    return x + jnp.matmul((jax.nn.silu(gate) * up).astype(x.dtype),
                          p["down/w"])


def _was_sambay(x, p, eps):
    h = blocks.layer_norm(x, p["ffn_norm/g"], p["ffn_norm/b"], eps)
    gate = jnp.matmul(h, p["gate/w"]).astype(jnp.float32)
    up = jnp.matmul(h, p["up/w"], preferred_element_type=jnp.float32)
    return x + jnp.matmul((jax.nn.silu(gate) * up).astype(x.dtype),
                          p["down/w"])


@pytest.mark.parametrize("was,arguments", [
    (_was_latent, {}),
    (_was_sala, {"scale": 0.35}),
    (_was_brumby, {"gate_dtype": jnp.bfloat16, "sum_in_scope": True}),
    (_was_sambay, {"norm": "layer", "gate_dtype": jnp.bfloat16,
                   "sum_in_scope": True}),
], ids=["kimi_k2", "minicpm_sala", "brumby", "phi4_flash"])
def test_the_ffn_block_is_each_model_s_expression_bit_for_bit(was, arguments):
    """``blocks.ffn_block`` under the arguments a model passes against the
    block that model had, in bfloat16, a prefill's rows and a step's one,
    jitted and not: equal in every bit. (The four are not one another: the
    float32 gate and the rounded one differ, as do the two sums.)"""
    rs = np.random.RandomState(1)
    bf = lambda *shape, scale=1.0: jnp.asarray(rs.randn(*shape) * scale,
                                               jnp.bfloat16)
    p = {"gate/w": bf(64, 96, scale=0.125), "up/w": bf(64, 96, scale=0.125),
         "down/w": bf(96, 64, scale=0.1),
         "ffn_norm/g": jnp.asarray(1 + 0.1 * rs.randn(64), jnp.float32),
         "ffn_norm/b": jnp.asarray(0.1 * rs.randn(64), jnp.float32)}
    for x in (bf(2, 33, 64), bf(3, 1, 64)):
        want = was(x, p, 1e-5)
        got = blocks.ffn_block(x, p, 1e-5, **arguments)
        assert got.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))
        jitted = jax.jit(lambda x: blocks.ffn_block(x, p, 1e-5, **arguments))
        assert np.array_equal(np.asarray(jitted(x), np.float32),
                              np.asarray(jax.jit(lambda x: was(x, p, 1e-5))(x),
                                         np.float32))
    x = bf(2, 33, 64)
    want = np.asarray(was(x, p, 1e-5), np.float32)
    for other in (_was_latent, _was_sala, _was_brumby, _was_sambay):
        if other is not was:
            assert not np.array_equal(
                np.asarray(other(x, p, 1e-5), np.float32), want)
