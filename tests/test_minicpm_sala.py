"""MiniCPM-SALA at tiny sizes on the CPU, against the one plain reference,
``benchmarks/reference/minicpm_sala.py``: each mixer's prefill form, the
chunked prefill against the one-piece one, prefill and then one-token steps
against the reference's full forward at every position (logits, not
tokens), the two kernels in interpret mode against their ``jnp`` forms, the
muP scalings, the cut's published indices, the served path and the
family's arithmetic. Seeded weights; float32 unless a case says otherwise.

The tiny configuration has every mechanism of the published one at a size
where selection happens: blocks of 64 keys, kernels of 32 every 16, a
window of two blocks, one first block, ``topk`` 6 (so a query chooses 3
blocks), ``dense_len`` 256 under the tests' contexts, groups of 2 heads
over 2 key heads, layers of both kinds at published indices 7, 9, 10, 16.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:        # benchmarks/ of this checkout
    sys.path.insert(0, ROOT)

import paddle_tpu as pt
from benchmarks.families import minicpm_sala as family
from benchmarks.reference import minicpm_sala as reference
from paddle_tpu.core import profiler
from paddle_tpu.layers import blocks, decoding, sala
from paddle_tpu.models import minicpm_sala
from paddle_tpu.ops import lightning_attention as la
from paddle_tpu.ops import sparse_attention as sa

VOCAB = 97
TINY = {
    "family": "minicpm_sala", "vocab_size": VOCAB, "hidden_size": 64,
    "num_hidden_layers": 4, "layer_indices": [7, 9, 10, 16],
    "mixer_types": list(minicpm_sala.PUBLISHED_MIXERS),
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "lightning_nh": 4, "lightning_head_dim": 16, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "attn_use_rope": False,
    "lightning_use_rope": True, "max_position_embeddings": 4096,
    "published": {"num_hidden_layers": 32},
    "assumed": {"sparse_config": {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 128, "topk": 6, "dense_len": 256}},
    "run": {"dtype": "float32", "prefill_chunk": 128},
}
SHAPE = reference.shape_of(TINY, query_block=64)
SPARSE = sala.SparseDims(64, 4, 2, 16, 1e-6, 32, 16, 64, 1, 128, 6, 256)
LIGHT = sala.LightningDims(64, 4, 16, 1e-6, 10000.0)
KINDS, INDICES = family.layers_of(TINY)

# float32 program against float32 reference, both at "highest": what is left
# is the order of sums (a chunked recurrence against a quadratic form, an
# online softmax against a plain one, means of two half-kernels against a
# mean of one) over four layers whose branches are scaled by 0.25; logits
# are about 0.25 apart at the top. A bfloat16 reference misses it by more
# than an order (test_a_bfloat16_reference_fails_the_tolerance).
LOGIT_TOL = 2e-4
MIXER_TOL = 2e-5


def tiny(**run):
    return dict(TINY, run=dict(TINY["run"], **run))


def rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def scored(config, prompt, nxt, params=None):
    prog = pt.build(decoding.make_scorer(minicpm_sala._decoder,
                                         family.program_config(config)))
    if params is None:
        params, _ = prog.init(jax.random.PRNGKey(5), prompt_ids=prompt,
                              next_ids=nxt)
    out, _ = prog.apply(params, {}, training=False, prompt_ids=prompt,
                        next_ids=nxt)
    return np.asarray(out["logp"]), params


def reference_logp(params, config, ids, prompt_len, first, shape=SHAPE):
    ref = family.reference_params(params, config)
    kinds, indices = family.layers_of(config)
    return np.stack([np.asarray(jax.nn.log_softmax(reference.forward(
        ref, jnp.asarray(row), shape, kinds, indices, prompt_len)[first:]))
        for row in ids])


# -- the mixers' prefill forms ------------------------------------------------------------


def layer_params(make, dims, seed):
    """One layer's parameters from the program's own table, random."""
    prog = pt.build(lambda x: {"p": make(dims, jnp.float32)})
    params, _ = prog.init(jax.random.PRNGKey(seed), x=np.zeros(1, np.float32))
    return {k.split("mixer/")[1]: rand(seed + i, *v.shape,
                                       scale=min(v.shape) ** -0.5 if v.ndim == 2
                                       else 1.0) + (1.0 if v.ndim == 1 else 0.0)
            for i, (k, v) in enumerate(sorted(params.items()))}


def as_reference(p, kind):
    """The layer's parameters under the reference's names (q, k and v cut
    out of the program's one ``[out, in]`` matrix)."""
    return family.reference_mixer(lambda n: p[n.split("mixer/")[1]], TINY, kind)


@pytest.mark.parametrize("s,selected", [(448, True), (200, False)],
                         ids=["selected", "dense"])
def test_sparse_prefill_against_reference(highest, s, selected):
    """One sparse layer over a whole prompt: beyond ``dense_len`` through
    the scorer, the top-k and the kernel; within it through the flash
    kernel with the key heads repeated."""
    p = layer_params(sala.sparse_params, SPARSE, 3)
    x = rand(1, 2, s, 64)
    total = -(-(s + 8) // 64) * 64
    cache = (jnp.zeros((2, total, 32)), jnp.zeros((2, total, 32)),
             jnp.zeros((2, total // 16, 32)))
    got, (k, v, ck) = sala.sparse_prefill(x, p, SPARSE, cache, 0, selected, 1.0)
    for row in range(2):
        want = reference.mixer_part(x[row], as_reference(p, reference.SPARSE),
                                    SHAPE._replace(scale_depth=32 ** 0.5),
                                    reference.SPARSE, 9, s)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(want),
                                   atol=MIXER_TOL)
    # the compressed keys the layer leaves: the means of 32 keys every 16
    n = (s - 32) // 16 + 1
    want_ck = np.stack([np.asarray(k[:, 16 * j:16 * j + 32]).mean(1)
                        for j in range(n)], 1)
    np.testing.assert_allclose(np.asarray(ck[:, :n]), want_ck, atol=1e-6)


def test_lightning_prefill_against_reference(highest):
    """One lightning layer over 300 tokens (a whole chunk of the kernel and
    a tail) against the reference's quadratic form; the state it leaves is
    the recurrence's."""
    p = layer_params(sala.lightning_params, LIGHT, 4)
    x = rand(2, 2, 300, 64)
    decay = sala.lightning_log_decay(4, 10, 32)
    got, state = sala.lightning_prefill(x, p, LIGHT, jnp.zeros((2, 4, 16, 16)),
                                        decay, 0, 1.0)
    for row in range(2):
        want = reference.mixer_part(x[row], as_reference(p, reference.LIGHTNING),
                                    SHAPE._replace(scale_depth=32 ** 0.5),
                                    reference.LIGHTNING, 10, 300)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(want),
                                   atol=MIXER_TOL)
    u = blocks.rms_norm(x, p["attn_norm/g"], 1e-6)
    _, k, v = sala._lightning_qkv(u, p, LIGHT, jnp.arange(300))
    lam = np.exp(np.asarray(decay))[None, :, None, None]
    want_state = np.zeros((2, 4, 16, 16))
    for t in range(300):
        want_state = lam * want_state + np.einsum(
            "bhd,bhe->bhde", np.asarray(k[:, t]), np.asarray(v[:, t]))
    np.testing.assert_allclose(np.asarray(state), want_state, rtol=2e-4,
                               atol=1e-3)


def test_decay_follows_the_published_index():
    """``lambda_h = exp(-2^(-8 (h + 1) / H) (1 - l / 31 + 1e-5))``: the
    program's and the reference's, and a cut's layer keeps its own."""
    for layer in (7, 22):
        np.testing.assert_allclose(
            -np.asarray(sala.lightning_log_decay(32, layer, 32)),
            np.asarray(reference.decay_rates(SHAPE._replace(l_heads=32), layer)),
            rtol=1e-6)
    first = float(sala.lightning_log_decay(32, 7, 32)[0])
    assert first == pytest.approx(-2 ** -0.25 * (1 - 7 / 31 + 1e-5), rel=1e-6)


# -- the kernels in interpret mode against their jnp forms ----------------------------------


def _selection(rng, b, n_kv, queries, p0, window, init, n_sel, block=64):
    sel = np.zeros((b, n_kv, queries, n_sel + 1), np.int32)
    for idx in np.ndindex(b, n_kv, queries):
        free = list(range(init, max((p0 + idx[2]) // block - window + 1, init)))
        rng.shuffle(free)
        n = min(len(free), n_sel)
        sel[idx][:n], sel[idx][n_sel] = free[:n], n
    return jnp.asarray(sel)


@pytest.mark.parametrize("p0,n_sel,group", [(0, 3, 2), (384, 3, 2), (256, 0, 4)],
                         ids=["from_the_start", "a_later_chunk", "forced_only"])
def test_sparse_kernel_against_its_jnp_form(highest, p0, n_sel, group):
    """``sparse_fwd`` over a chunk of 128 queries at ``p0``: the dense half
    over window and first block, the gathered half over each query's own
    blocks (fewer than ``n_sel`` count for the early ones), merged."""
    rng = np.random.RandomState(p0)
    q = rand(1, 2, 2, 128 * group, 32)
    k, v = rand(2, 2, 512, 64), rand(3, 2, 512, 64)
    sel = _selection(rng, 2, 2, 128, p0, 2, 1, n_sel)
    kw = dict(group=group, block=64, window_blocks=2, init_blocks=1,
              scale=32 ** -0.5)
    got = sa.sparse_attention(q, k, v, sel, jnp.int32(p0), interpret=True, **kw)
    want = sa.sparse_attention_jnp(q, k, v, sel, jnp.int32(p0), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("s", [512, 300, 40], ids=["chunks", "chunk_and_tail",
                                                  "tail_only"])
def test_lightning_kernel_against_its_jnp_form(highest, s):
    """``lightning_fwd`` from a given state: outputs and the state left."""
    q, k, v = (rand(i, 2, s, 4 * 32, scale=0.3) for i in range(3))
    state = rand(3, 2, 4, 32, 32)
    decay = -jnp.asarray([0.6, 0.1, 0.01, 0.003], jnp.float32)
    got, left = la.lightning_attention(q, k, v, decay, state, 4, interpret=True)
    heads = lambda a: a.reshape(2, s, 4, 32)
    want, want_left = la.lightning_chunk(heads(q), heads(k), heads(v), decay,
                                         state)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.reshape(2, s, -1)),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(left), np.asarray(want_left), atol=2e-5)


# -- the whole model ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len,new", [(192, 80), (384, 24)],
                         ids=["across_dense_len", "selected_prefill"])
def test_prefill_then_steps_against_reference(highest, prompt_len, new):
    """Prefill, then one-token steps through the carried state: the
    log-probabilities at every position against the reference's one full
    forward. ``across_dense_len``: a dense prefill of 192, steps that are
    dense up to a context of 256 and select beyond it (one generator holds
    both forms), with new compressed keys at 207, 223, ... ``selected_
    prefill``: 384 tokens in three chunks of 128, every query selecting,
    then steps over the chunk-built slabs (a new compressed key at 399)."""
    rng = np.random.RandomState(2)
    prompt = rng.randint(3, VOCAB, (2, prompt_len)).astype(np.int32)
    nxt = rng.randint(3, VOCAB, (2, new)).astype(np.int32)
    got, params = scored(TINY, prompt, nxt)
    want = reference_logp(params, TINY, np.concatenate([prompt, nxt], 1),
                          prompt_len, prompt_len - 1)
    assert got.shape == want.shape == (2, new + 1, VOCAB)
    assert np.abs(got - want).max() <= LOGIT_TOL


def test_chunked_prefill_equals_one_piece(highest):
    """Three chunks of 128 and one piece of 384 leave the same first
    distribution and the same carried state (both kinds)."""
    prompt = np.random.RandomState(3).randint(3, VOCAB, (2, 384)).astype(np.int32)
    states = {}
    for chunk in (128, 384):
        cfg = family.program_config(tiny(prefill_chunk=chunk))
        prog = pt.build(lambda prompt_ids, cfg=cfg: {
            k: v for k, v in minicpm_sala._decoder(cfg, prompt_ids, 8)[0].items()
            if k in ("k", "v", "ck", "s", "logp0")})
        if not states:
            params, _ = prog.init(jax.random.PRNGKey(7), prompt_ids=prompt)
        states[chunk], _ = prog.apply(params, {}, training=False,
                                      prompt_ids=prompt)
    for a, b in zip(jax.tree.leaves(states[128]), jax.tree.leaves(states[384])):
        # states and keys reach 60: a relative bound beside the absolute one
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=2e-5)
    assert len(states[128]["s"]) == len(states[128]["ck"]) == 2


def test_the_scalings_are_the_published_depth_s(highest):
    """The branches are scaled by ``scale_depth / sqrt(32)``, the published
    depth, with 4 layers held; a reference that takes the cut's depth, one
    without ``scale_emb`` and one without the head's divisor all disagree."""
    assert family.program_config(TINY).branch_scale == pytest.approx(
        1.4 / 32 ** 0.5)
    rng = np.random.RandomState(4)
    prompt = rng.randint(3, VOCAB, (1, 64)).astype(np.int32)
    nxt = rng.randint(3, VOCAB, (1, 2)).astype(np.int32)
    got, params = scored(TINY, prompt, nxt)
    ids = np.concatenate([prompt, nxt], 1)
    for wrong in (dict(published_layers=4), dict(scale_emb=1.0),
                  dict(dim_model_base=64)):
        off = reference_logp(params, TINY, ids, 64, 63, SHAPE._replace(**wrong))
        assert np.abs(got - off).max() > 50 * LOGIT_TOL, wrong


def test_the_cut_s_layer_indices_drive_the_decay(highest):
    """The same weights as layers 7, 9, 10, 16 and as layers 1, 9, 28, 16 of
    the published stack (the same kinds, other decays) differ, and each agrees
    with the reference given the same indices."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(3, VOCAB, (1, 96)).astype(np.int32)
    nxt = rng.randint(3, VOCAB, (1, 3)).astype(np.int32)
    got, params = scored(TINY, prompt, nxt)
    moved = dict(TINY, layer_indices=[1, 9, 28, 16])   # the same kinds
    assert family.layers_of(moved)[0] == KINDS
    renamed = {}
    for name, value in params.items():       # a layer's names follow its index
        for old, new in zip(TINY["layer_indices"], moved["layer_indices"]):
            if name.startswith(f"layer_{old}/"):
                name = f"layer_{new}/" + name.split("/", 1)[1]
                break
        renamed[name] = value
    got_moved, _ = scored(moved, prompt, nxt, renamed)
    assert np.abs(got - got_moved).max() > 10 * LOGIT_TOL
    want = reference_logp(renamed, moved, np.concatenate([prompt, nxt], 1), 96, 95)
    assert np.abs(got_moved - want).max() <= LOGIT_TOL


def test_a_bfloat16_reference_fails_the_tolerance():
    """The tolerance is a check: the reference computed in bfloat16 (the
    precision below the float32 the tests state) misses it."""
    rng = np.random.RandomState(2)
    prompt = rng.randint(3, VOCAB, (1, 384)).astype(np.int32)
    nxt = rng.randint(3, VOCAB, (1, 4)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, params = scored(TINY, prompt, nxt)
    ref = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                       family.reference_params(params, TINY))
    low = reference.forward(ref, jnp.asarray(np.concatenate([prompt, nxt], 1)[0]),
                            SHAPE, KINDS, INDICES, 384)[383:]
    low = np.asarray(jax.nn.log_softmax(low.astype(jnp.float32)))
    assert np.abs(got[0] - low).max() > 10 * LOGIT_TOL


def test_bfloat16_weights_are_held_in_bfloat16():
    """``run.dtype`` bfloat16: every matrix and every slab in bfloat16, the
    norms' scales and the lightning states float32; the generator runs."""
    prompt = np.random.RandomState(6).randint(3, VOCAB, (2, 384)).astype(np.int32)
    gen = pt.build(minicpm_sala.make_generator(
        family.program_config(tiny(dtype="bfloat16")), max_new_tokens=4))
    params, _ = gen.init(jax.random.PRNGKey(1), prompt_ids=prompt)
    assert {v.dtype for k, v in params.items() if k.endswith("/w")} == {
        jnp.dtype(jnp.bfloat16)}
    assert {v.dtype for k, v in params.items() if k.endswith("/g")} == {
        jnp.dtype(jnp.float32)}
    ids = gen.apply(params, {}, training=False, prompt_ids=prompt)[0]["ids"]
    assert ids.shape == (2, 4) and ids.dtype == jnp.int32


def test_generator_emits_the_scorer_s_argmax_and_records_its_plans(highest):
    """Greedy ids are the argmax of the scorer's distributions under those
    ids; the trace leaves one ``decode.plan`` of two kinds of entry, a
    ``prefill.plan``, and ``sparse.plan`` / ``lightning.plan`` a layer; a
    ``sparse.plan`` says which form of the scorer chose its blocks."""
    prompt = np.random.RandomState(4).randint(3, VOCAB, (2, 384)).astype(np.int32)
    cfg = family.program_config(TINY)
    gen = pt.build(minicpm_sala.make_generator(cfg, max_new_tokens=5))
    params, _ = gen.init(jax.random.PRNGKey(5), prompt_ids=prompt)
    since = profiler.time.time_ns()
    ids = np.asarray(gen.apply(params, {}, training=False,
                               prompt_ids=prompt)[0]["ids"])
    spans = profiler.spans(since)
    (plan,) = [s[4] for s in spans if s[0] == "decode.plan"]
    assert plan["cache_kind"] == "kv+state" and plan["lane_width"] == 32
    assert (plan["sparse_layers"], plan["state_layers"]) == (2, 2)
    assert plan["kv_bytes"] == 2 * 2 * 2 * 448 * 32 * 4      # k and v, 2 layers
    assert plan["index_bytes"] == 2 * 2 * 28 * 32 * 4
    assert plan["state_bytes"] == 2 * 2 * 4 * 16 * 16 * 4
    assert plan["cache_bytes"] == (plan["kv_bytes"] + plan["index_bytes"]
                                   + plan["state_bytes"])
    (pre,) = [s[4] for s in spans if s[0] == "prefill.plan"]
    assert (pre["chunk"], pre["chunks"]) == (128, 3)
    plans = [s[4] for s in spans if s[0] == "sparse.plan"]
    assert {p["form"] for p in plans} == {"selected"}
    # the scorer's form: a chunk's whole query tiles take the kernel (its
    # plan says a tile of 128 queries and a walk of 32 of the 28 compressed
    # keys a step), a step's one query a row the plain form
    prefill = [p for p in plans if p["row_tile"]]
    steps = [p for p in plans if not p["row_tile"]]
    assert len(prefill) == len(steps) == 2      # a record a sparse layer
    assert {p["scorer"] for p in prefill} == {"kernel"}
    assert {(p["scorer_tile"], p["scorer_key_step"], p["scorer_keys"])
            for p in prefill} == {(128, 32, 28)}
    assert {p["scorer"] for p in steps} == {"jnp"}
    assert not any(k.startswith("scorer_") for p in steps for k in p)
    assert {s[4]["state_dtype"] for s in spans
            if s[0] == "lightning.plan"} == {"float32"}
    logp, _ = scored(TINY, prompt, ids[:, :-1], params)
    ended = np.cumsum(ids == 2, axis=1) - (ids == 2) > 0
    assert (np.where(ended, 2, np.argmax(logp, -1)) == ids).all()


# -- the first step: its layers run too, and leave nothing ------------------------------------

# a prompt whose first generated token ends a stride: ``(p + 1) % 16 == 0``,
# so compressed key ``(p + 1 - 32) // 16 = 14`` covers 224..255, position
# ``p`` itself, and the first iteration writes it from that iteration's key
STRIDE_ENDS = 255


def test_lightning_decode_unwritten_keeps_its_state_bit_for_bit():
    """``write=False`` hands back the state it was given, every bit of it (a
    negative zero and a denormal among them: a product with one and a sum
    with zero would turn the first and may flush the second); ``write=True``,
    the default, is the recurrence."""
    p = layer_params(sala.lightning_params, LIGHT, 4)
    x, decay = rand(3, 2, 1, 64), sala.lightning_log_decay(4, 10, 32)
    state = np.asarray(rand(4, 2, 4, 16, 16, scale=30.0)).copy()
    state[0, 0, 0, :4] = [-0.0, 1e-40, -1e-45, 3e38]
    step = jax.jit(lambda write: sala.lightning_decode(
        x, p, LIGHT, jnp.asarray(state), decay, jnp.asarray(300, jnp.int32),
        1.0, write=write))
    _, kept = step(False)
    assert np.array_equal(np.asarray(kept).view(np.uint32),
                          state.view(np.uint32))
    out, written = step(True)
    plain, by_default = sala.lightning_decode(
        x, p, LIGHT, jnp.asarray(state), decay, jnp.asarray(300, jnp.int32), 1.0)
    np.testing.assert_allclose(np.asarray(written), np.asarray(by_default),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain), atol=1e-5)
    assert np.abs(np.asarray(written)[1] - state[1]).max() > 1e-2


@pytest.mark.parametrize("prompt_len", [STRIDE_ENDS, 384],
                         ids=["stride_ends_at_the_prompt", "selected_prefill"])
def test_the_first_iteration_leaves_nothing_that_outlasts_it(highest, prompt_len):
    """The step's layers run in the first iteration too
    (``decoding.step_with_write_switch``), on a token nothing should read.
    After that iteration and one more, the distribution and every carried
    array are the same bit for bit whatever the first was handed, and they
    are what one step from the prefill's state leaves with the first
    iteration skipped: the slabs' position ``p`` and the compressed key that
    covers it are written again, a lightning state is not folded into."""
    prompt = np.random.RandomState(9).randint(
        3, VOCAB, (2, prompt_len)).astype(np.int32)
    cfg = family.program_config(TINY)

    def two_steps(prompt_ids, unread, token):
        state0, step_fn, _ = minicpm_sala._decoder(cfg, prompt_ids, 8)
        first_logp, once = step_fn(unread, state0)
        logp, twice = step_fn(token, once)
        skipped, direct = step_fn(token, {**state0, "first": jnp.asarray(False)})
        carried = lambda st: {k: st[k] for k in ("k", "v", "ck", "s", "index")}
        return {"first": first_logp, "logp0": state0["logp0"], "logp": logp,
                "twice": carried(twice), "skipped": skipped,
                "direct": carried(direct), "once_s": once["s"],
                "s0": state0["s"], "once_index": once["index"]}

    prog = pt.build(two_steps)
    token = np.asarray([5, 11], np.int32)
    feeds = [dict(prompt_ids=prompt, unread=np.asarray(u, np.int32), token=token)
             for u in ([1, 1], [77, 40])]
    params, _ = prog.init(jax.random.PRNGKey(3), **feeds[0])
    a, b = (jax.tree.map(np.asarray, prog.apply(params, {}, training=False,
                                                **feed)[0]) for feed in feeds)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(x, y)
    assert np.array_equal(a["first"], a["logp0"])
    assert a["once_index"] == prompt_len and a["twice"]["index"] == prompt_len + 1
    for kept, was in zip(a["once_s"], a["s0"]):
        assert np.array_equal(kept.view(np.uint32), was.view(np.uint32))
    for x, y in zip(jax.tree.leaves(a["twice"]), jax.tree.leaves(a["direct"])):
        assert np.array_equal(x, y)
    np.testing.assert_allclose(a["logp"], a["skipped"], atol=1e-6)
    if prompt_len == STRIDE_ENDS:       # the key written twice is a new one
        j = (prompt_len + 1 - 32) // 16
        assert np.abs(a["twice"]["ck"][0][:, j]).max() > 0
        assert not np.abs(a["twice"]["ck"][0][:, j + 1:]).any()


def test_steps_from_a_prompt_that_ends_a_stride_against_reference(highest):
    """A dense prefill of 255 tokens, then 72 steps: the first generated
    token is the last key of compressed key 14, which the first iteration
    and the second both write; the steps select from position 256 on, and
    from 320 on block 3 (192..255, scored through keys 11 to 15) is read
    only if chosen. Log-probabilities at every position against the
    reference's one full forward."""
    test_prefill_then_steps_against_reference(highest, STRIDE_ENDS, 72)


def test_ids_do_not_depend_on_bos_id(highest):
    """``bos_id`` is the token the search hands the first iteration, whose
    distribution is the prefill's: with the layers run in that iteration
    too it is the one thing that reads it, and nothing of it is left, in
    the ids or (the sharper reading) in any bit of the last state."""
    prompt = np.random.RandomState(11).randint(
        3, VOCAB, (2, STRIDE_ENDS)).astype(np.int32)
    cfg = family.program_config(TINY)

    def with_last_state(cfg, prompt_ids, max_new_tokens):
        state0, step_fn, _ = minicpm_sala._decoder(cfg, prompt_ids,
                                                   max_new_tokens)
        return state0, step_fn, lambda state: {
            k: state[k] for k in ("k", "v", "ck", "s")}

    served = []
    for bos_id in (1, 50):
        gen = pt.build(decoding.make_generator(with_last_state, cfg, 6,
                                               bos_id=bos_id))
        if not served:
            params, _ = gen.init(jax.random.PRNGKey(5), prompt_ids=prompt)
        served.append(jax.tree.map(np.asarray, gen.apply(
            params, {}, training=False, prompt_ids=prompt)[0]))
    assert served[0]["ids"].shape == (2, 6)
    for x, y in zip(*map(jax.tree.leaves, served)):
        assert np.array_equal(x, y)
    direct = pt.build(minicpm_sala.make_generator(cfg, max_new_tokens=6, bos_id=7))
    assert np.array_equal(served[0]["ids"], np.asarray(direct.apply(
        params, {}, training=False, prompt_ids=prompt)[0]["ids"]))


def test_the_decode_plan_says_the_first_step_s_form():
    """One trace (no compile) leaves a ``decode.plan`` whose ``first_step``
    is ``"write_switch"``: the layers outside the conditional."""
    prompt = np.zeros((2, 384), np.int32)
    gen = pt.build(minicpm_sala.make_generator(family.program_config(TINY),
                                               max_new_tokens=5))
    since = profiler.time.time_ns()
    jax.eval_shape(lambda key: gen.init(key, prompt_ids=prompt)[0],
                   jax.random.PRNGKey(0))
    (plan,) = [s[4] for s in profiler.spans(since) if s[0] == "decode.plan"]
    assert plan["first_step"] == "write_switch"


def test_served_ids_are_the_direct_call_s(tmp_path, highest):
    """``export_decoder(model=minicpm_sala)`` -> ``decode_server``: a
    bucket-sized request and a single prompt that pads both return the ids
    of a direct call of the program."""
    from paddle_tpu.fleet import decode

    prompt = np.random.RandomState(6).randint(3, VOCAB, (2, 384)).astype(np.int32)
    cfg = family.program_config(TINY)
    gen = pt.build(minicpm_sala.make_generator(cfg, max_new_tokens=4))
    params, _ = gen.init(jax.random.PRNGKey(5), prompt_ids=prompt)
    direct = np.asarray(gen.apply(params, {}, training=False,
                                  prompt_ids=prompt)[0]["ids"])
    decode.export_decoder(str(tmp_path / "m"), cfg, 4, prompt, params=params,
                          model=minicpm_sala)
    server = decode.decode_server(str(tmp_path / "m"), max_wait_ms=1)
    try:
        whole = server.submit({"prompt_ids": prompt}).result(timeout=300)
        one = server.submit({"prompt_ids": prompt[1:]}).result(timeout=300)
    finally:
        server.close(drain=False, timeout=30)
    assert np.array_equal(np.asarray(whole["ids"]), direct)
    assert np.array_equal(np.asarray(one["ids"]), direct[1:])


def test_family_check_passes_on_served_ids_and_fails_on_wrong_ones(highest):
    """The benchmark's own check at the tiny size: greedy ids pass, the
    same ids shifted by one id fail, and ``edit`` reaches the reference (one
    whose scorer may choose no block gives other logits)."""
    prompt = np.random.RandomState(8).randint(3, VOCAB, (2, 384)).astype(np.int32)
    weights = family.decoder_params(TINY, 3, 384, 6)
    gen = pt.build(minicpm_sala.make_generator(family.program_config(TINY),
                                               max_new_tokens=6))
    params = jax.tree.map(jnp.asarray, weights.host_params())
    served = np.asarray(gen.apply(params, {}, training=False,
                                  prompt_ids=prompt)[0]["ids"])
    good = family.served_check(TINY, weights, prompt, served)
    assert good["ok"] and good["worst_logit_gap"] < 1e-2, good
    assert not family.served_check(TINY, weights, prompt,
                                   (served + 1) % VOCAB)["ok"]
    forced_only = lambda sh, part, layer, kind, index, lp: (
        sh._replace(topk=3), kind, index, lp)
    # (12 tokens of a vocabulary of 97 keep their argmax: the logits move)
    less = family.served_check(TINY, weights, prompt, served, edit=forced_only)
    assert abs(less["top_two_apart"] / good["top_two_apart"] - 1) > 1e-3


# -- the family's arithmetic, at the published numbers -----------------------------------------


def family_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        return json.load(f)


def test_family_counts_at_the_published_widths():
    """The cut of ISSUE 33 by hand: 253.8M parameters a sparse layer, 285.2M
    a lightning layer, 5.04B in 4 + 12 layers with embedding and head; a
    query of a 32k prompt reads 64 blocks' keys; 8.88 GFLOP of matrices a
    token."""
    config = family_config()
    c = family._counts(config)
    assert c[family.SPARSE] + c["ffn"] == 253_755_392
    assert c[family.LIGHTNING] + c["ffn"] == 285_212_672
    kinds, indices = family.layers_of(config)
    assert (kinds.count(family.SPARSE), kinds.count(family.LIGHTNING)) == (4, 12)
    assert indices == tuple(range(7, 23))
    total = family._matrix_params(config) + 2 * c["head"]
    assert total == 5_039_259_648
    assert family.selected_keys(config, 32767, 32768) == 63 * 64 + 64
    assert family.selected_keys(config, 100, 32768) == 101
    assert 2 * family._matrix_params(config) == pytest.approx(8.88e9, rel=2e-3)
    flops, moved, calls = family.kernel_counts(config, 2, 32768, "sparse_fwd")
    assert calls == 4 * 8 and flops == 4 * family.sparse_attention_flops(
        config, 2, 32768)
    assert family.kernel_counts(config, 2, 32768, "flash_fwd") is None
