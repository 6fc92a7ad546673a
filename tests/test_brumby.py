"""Brumby (power retention) at tiny sizes on the CPU, against the one plain
reference, ``benchmarks/reference/brumby.py``: the feature map, the three
forms of the recurrence against the attention form at every position, both
kernels in interpret mode against their ``jnp`` forms (the one-token kernel
with its window of deferred tokens, at every window), the grouping, the
gate's edges, prefill and then one-token steps against the reference's full
forward (logits, not tokens), the served path and the family's arithmetic.
Seeded weights; float32 unless a case says otherwise.

The tiny configuration: 2 layers (published 16, 17), hidden 64, 4 query
heads over 2 key heads of 16 (the symmetric map is 136 wide, the program
holds 192), chunks of 256 tokens as at the published size.
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
from benchmarks.families import brumby as family
from benchmarks.reference import brumby as reference
from paddle_tpu.core import profiler
from paddle_tpu.layers import decoding
from paddle_tpu.layers import retention as layer
from paddle_tpu.models import brumby
from paddle_tpu.ops import power_retention as pr

VOCAB = 97
TINY = {
    "family": "brumby", "vocab_size": VOCAB, "hidden_size": 64,
    "num_hidden_layers": 2, "layer_indices": [16, 17],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "max_position_embeddings": 4096, "published": {"num_hidden_layers": 40},
    "assumed": {"eps_n": 1e-6},
    "run": {"dtype": "float32", "chunk": 256},
}
SHAPE = reference.shape_of(TINY)
DIMS = layer.RetentionDims(64, 4, 2, 16, 1e-6, 1e6)
B, H, KV, D = 2, 4, 2, 16

# float32 program against float32 reference, both at "highest": what is left
# is the order of sums (a state of 192 products against a quadratic form).
# A bfloat16 reference misses it by more than an order
# (test_a_bfloat16_reference_fails_the_tolerance).
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


def inputs(s, seed=0, gate=4.0):
    """``q [B, s, H, D]`` (scaled), ``k, v [B, s, KV, D]``, ``log_gamma [B,
    s, KV]`` with ``-log gamma`` about ``exp(-gate)``."""
    q = rand(seed, B, s, H, D, scale=D ** -0.5)
    k, v = rand(seed + 1, B, s, KV, D), rand(seed + 2, B, s, KV, D)
    return q, k, v, jax.nn.log_sigmoid(rand(seed + 3, B, s, KV) + gate)


def attention_form(q, k, v, log_gamma, divisors=False):
    """The definition, written out: a ``[t, j]`` weight matrix a head."""
    s = q.shape[1]
    cum = jnp.cumsum(log_gamma, axis=1)
    t = jnp.arange(s)
    out = []
    for h in range(H):
        c = h // (H // KV)
        span = cum[:, :, None, c] - cum[:, None, :, c]
        a = jnp.where(t[:, None] >= t[None, :], jnp.exp(jnp.minimum(span, 0.0)),
                      0.0) * jnp.einsum("btd,bjd->btj", q[:, :, h], k[:, :, c]) ** 2
        out.append(a.sum(-1, keepdims=True) if divisors else
                   jnp.einsum("btj,bjd->btd", a, v[:, :, c])
                   / (a.sum(-1, keepdims=True) + pr.EPS))
    return jnp.stack(out, axis=2)


def off(got, q, k, v, log_gamma):
    """The largest distance of ``got`` from the attention form, a position's
    weighted by its divisor where that is under 1: numerator and divisor
    are each a sum of 192 signed products of the size of ``|q|^2 |k|^2 =
    16``, exact to a millionth of that, so the quotient is exact to ``2e-6 /
    divisor`` and no closer (a first token's divisor is its own squared
    product, which may be anything; the attention form squares one number)."""
    want = attention_form(q, k, v, log_gamma)
    small = jnp.minimum(attention_form(q, k, v, log_gamma, divisors=True), 1.0)
    return np.abs((got - want) * small).max()


flat = lambda a: a.reshape(a.shape[0], a.shape[1], -1)


# -- the feature map and the forms of the recurrence ----------------------------------


@pytest.mark.parametrize("d", [8, 16, 24, 128])
def test_the_feature_map_squares_the_product(d):
    x, y = rand(1, 5, d), rand(2, 5, d)
    got = jnp.sum(pr.features(x, True) * pr.features(y, False), axis=-1)
    # (a sum of signed products the size of |x|^2 |y|^2: float32's rounding
    # is a millionth of that, whatever is left of it)
    size = jnp.sum(x * x, axis=-1) * jnp.sum(y * y, axis=-1)
    assert (jnp.abs(got - jnp.sum(x * y, axis=-1) ** 2) <= 1e-6 * size).all()
    assert pr.features(x, False).shape == (5, pr.rows(d))
    assert pr.symmetric_rows(d) <= pr.rows(d) < d * d or d == 8
    assert (pr.rows(16), pr.symmetric_rows(16)) == (192, 136)
    assert (pr.rows(128), pr.symmetric_rows(128)) == (8704, 8256)
    # the runs of tiles the kernel multiplies at once start on a lane tile
    assert all(first % 128 == 0 for _, _, first, _ in pr._slabs(128))
    assert sum(n for _, _, _, n in pr._slabs(128)) == 8704


def _one_piece(q, k, v, lg):
    return pr.retention_chunk(q, k, v, lg, pr.empty_state(B, KV, D))[0]


def _handed_on(q, k, v, lg, cuts=(100, 228), held=lambda state: state):
    """Pieces of unequal length, the state handed from one to the next
    (as ``held`` holds it)."""
    state, outs, lo = pr.empty_state(B, KV, D), [], 0
    for hi in cuts + (q.shape[1],):
        o, state = pr.retention_chunk(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                                      lg[:, lo:hi], held(state))
        outs.append(o)
        lo = hi
    return jnp.concatenate(outs, axis=1)


def _recurrent(q, k, v, lg):
    def step(state, x):
        o, state = pr.retention_step_plain(*x, state)
        return state, o
    _, o = jax.lax.scan(step, pr.empty_state(B, KV, D),
                        tuple(a.swapaxes(0, 1) for a in (q, k, v, lg)))
    return o.swapaxes(0, 1)


def _kernel(q, k, v, lg):
    o, _ = pr.retention(flat(q), flat(k), flat(v), lg,
                        pr.empty_state(B, KV, D), H, KV)
    return o.reshape(q.shape)


# the windows the one-token form is run at: any number of tokens is admitted
# (1 folds every token; 3 leaves the 4 (row, key head) pairs of these tests
# a run of 3 and a short one; 16 is more than there are pairs); the
# program's own is among them
WINDOWS = (1, 3, pr.WINDOW, 16)
assert len(set(WINDOWS)) == 4


def deferred_steps(q, k, v, lg, state, window: int, first=0, write=None,
                   held=lambda w: w):
    """``retention_step`` token by token from ``state`` and an empty window
    of ``window`` tokens, the first at position ``first``; ``write [s]``
    (bool, all true if not given): a token with ``write`` false goes in with
    a key of 0 and a gate of 1 and the next takes its position. ``held``
    changes the window as it is handed on. Returns ``(o [b, s, h, d], the
    state with what the window still holds folded in)``."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    write = jnp.ones(s, bool) if write is None else jnp.asarray(write)

    def step(carry, x):
        state, win, at = carry
        q1, k1, v1, g1, w = x
        k1, g1 = jnp.where(w, k1, 0), jnp.where(w, g1, 0.0)
        o, state = pr.retention_step(q1.reshape(b, -1), k1.reshape(b, -1),
                                     v1.reshape(b, -1), g1, state, h, kv,
                                     win, at, w)
        win = held(pr.window_append(win, k1, v1, g1, at))
        return (state, win, at + w), o.reshape(b, h, d)

    (state, win, at), o = jax.lax.scan(
        step, (state, pr.empty_window(b, kv, d, k.dtype, window),
               jnp.asarray(first, jnp.int32)),
        tuple(a.swapaxes(0, 1) for a in (q, k, v, lg)) + (write,))
    return o.swapaxes(0, 1), pr.fold_window(state, win, at - 1)


def _deferred(window):
    form = lambda q, k, v, lg: deferred_steps(
        q, k, v, lg, pr.empty_state(B, KV, D), window)[0]
    return form, 3 * window + 2


@pytest.mark.parametrize("form,s", [
    (_one_piece, 300), (_handed_on, 300), (_recurrent, 300), (_kernel, 300),
    (_kernel, 512), (_kernel, 40)] + [_deferred(w) for w in WINDOWS],
    ids=["chunk", "chunks_handed_on", "recurrent", "kernel_and_tail",
         "kernel_two_chunks", "tail_alone"] + [
         f"step_kernel_window_{w}" for w in WINDOWS])
def test_every_form_is_the_attention_form(highest, form, s):
    """At every position: the plain chunk form in one piece and in three
    pieces of unequal length, the one-token recurrence, ``retention_fwd`` in
    interpret mode (whole chunks, then a tail that is not one) and
    ``retention_read`` with its deferred tokens, over three folds of every
    state at each window."""
    q, k, v, lg = inputs(s)
    assert off(jax.jit(form)(q, k, v, lg), q, k, v, lg) <= MIXER_TOL


def test_a_state_held_in_bfloat16_fails_the_tolerance(highest):
    """Why ``state_dtype`` is float32 and what holds it there: the same
    pieces with the state rounded to bfloat16 where it is handed on miss
    the attention form by more than ten times the tolerance (the chip's
    check of served tokens cannot tell the two apart: PERF.md section 6,
    PR 39)."""
    q, k, v, lg = inputs(300)
    low = lambda state: state.astype(jnp.bfloat16).astype(jnp.float32)
    got = jax.jit(lambda *a: _handed_on(*a, held=low))(q, k, v, lg)
    assert off(got, q, k, v, lg) > 10 * MIXER_TOL


@pytest.mark.parametrize("s", [512, 300], ids=["chunks", "chunk_and_tail"])
def test_kernel_against_its_jnp_form_from_a_state(highest, s):
    """From a state that is not empty: outputs and the state handed back,
    kernel against :func:`retention_chunk`; the step kernel against
    :func:`retention_step_plain` from the same state."""
    q, k, v, lg = inputs(s, seed=7)
    _, state0 = pr.retention_chunk(*inputs(90, seed=11), pr.empty_state(B, KV, D))
    want, s_want = jax.jit(pr.retention_chunk)(q, k, v, lg, state0)
    got, s_got = jax.jit(lambda *a: pr.retention(*a, H, KV))(
        flat(q), flat(k), flat(v), lg, state0)
    assert np.abs(got.reshape(want.shape) - want).max() <= MIXER_TOL
    assert np.abs(s_got - s_want).max() <= 1e-5 * np.abs(s_want).max()
    # the key sum rides the state: sublane D, the rest of its tile zero
    assert np.abs(s_got[:, :, D]).max() > 0 and not np.abs(s_got[:, :, D + 1:]).any()
    # (one token at position 3 of an empty window: pair 3 folds it, the
    # others keep it beside their states)
    empty = pr.empty_window(B, KV, D, jnp.float32)
    o1, s1 = pr.retention_step(flat(q)[:, 3], flat(k)[:, 3], flat(v)[:, 3],
                               lg[:, 3], s_want, H, KV, empty, 3)
    o2, s2 = pr.retention_step_plain(q[:, 3], k[:, 3], v[:, 3], lg[:, 3], s_want)
    assert np.abs(o1.reshape(o2.shape) - o2).max() <= MIXER_TOL
    assert np.array_equal(s1[0, 0], s_want[0, 0])       # pair 0: not its turn
    assert np.abs(s1[1, 1] - s2[1, 1]).max() <= 1e-5 * np.abs(s2).max()
    s1 = pr.fold_window(s1, pr.window_append(empty, k[:, 3], v[:, 3], lg[:, 3], 3), 3)
    assert np.abs(s1 - s2).max() <= 1e-5 * np.abs(s2).max()


def edge_inputs(s, heads, kv, seed=13):
    """Tokens whose gates lie at both edges of the configuration's range
    (``logsigmoid`` of about 4.85 +- 2.8: ``-log gamma`` from 1/2,000 to
    1/8) and, one token each, at the edges of the function itself (a gate
    of 1, a gate near 0, not the last ones); the first token is not to be
    written."""
    q = rand(seed, B, s, heads, D, scale=D ** -0.5)
    k, v = rand(seed + 1, B, s, kv, D), rand(seed + 2, B, s, kv, D)
    lg = jax.nn.log_sigmoid(4.85 + 2.8 * jnp.sign(rand(seed + 3, B, s, kv)))
    lg = lg.at[:, 2].set(0.0).at[:, s // 2].set(-40.0)
    return q, k, v, lg, jnp.arange(s) > 0


def plain_steps(q, k, v, lg, state, write):
    def step(state, x):
        q1, k1, v1, g1, w = x
        o, state = pr.retention_step_plain(
            q1, jnp.where(w, k1, 0), v1, jnp.where(w, g1, 0.0), state)
        return state, o
    state, o = jax.lax.scan(step, state, tuple(
        a.swapaxes(0, 1) for a in (q, k, v, lg)) + (write,))
    return o.swapaxes(0, 1), state


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("heads,kv", [(H, KV), (KV, KV)],
                         ids=["grouped", "ungrouped"])
def test_the_deferred_step_is_the_recurrent_form(highest, window, heads, kv):
    """``retention_read`` against :func:`retention_step_plain` token by
    token over ``3 W + 2`` consecutive tokens from a state that is not
    empty, starting at a position that is no multiple of ``W``: every
    output, and the state once the window's last tokens are folded in, for
    grouped and ungrouped heads. The first token is not written (a key of
    0, a gate of 1, the next token at its position), gates at both edges."""
    s = 3 * window + 2
    q, k, v, lg, write = edge_inputs(s, heads, kv)
    _, state0 = pr.retention_chunk(
        rand(3, B, 90, heads, D), rand(4, B, 90, kv, D), rand(5, B, 90, kv, D),
        jax.nn.log_sigmoid(rand(6, B, 90, kv) + 4.0), pr.empty_state(B, kv, D))
    want, s_want = jax.jit(plain_steps)(q, k, v, lg, state0, write)
    got, s_got = jax.jit(deferred_steps, static_argnums=(5, 6))(
        q, k, v, lg, state0, window, 1029, write)
    assert np.isfinite(got).all()
    # a position's error weighted by its divisor where that is under 1
    # (see ``off``): read off the yardstick's own state
    small = jnp.minimum(jnp.abs(jax.jit(divisors)(q, k, lg, state0, write)), 1.0)
    assert np.abs((got - want) * small).max() <= MIXER_TOL
    assert np.abs(s_got - s_want).max() <= 1e-5 * np.abs(s_want).max()


def divisors(q, k, lg, state, write):
    """``phi(q_t)^T z_t [b, s, h, 1]`` of the plain recurrence."""
    def step(z, x):
        q1, k1, g1, w = x
        z = (z * jnp.exp(jnp.where(w, g1, 0.0))[..., None]
             + pr.features(jnp.where(w, k1, 0), False))
        b, h, d = q1.shape
        pq = pr.features(q1.reshape(b, z.shape[1], -1, d), True)
        return z, jnp.einsum("bcgf,bcf->bcg", pq, z,
                             precision=jax.lax.Precision.HIGHEST).reshape(b, h, 1)
    _, out = jax.lax.scan(step, state[:, :, state.shape[2] - pr.NORM_ROWS],
                          tuple(a.swapaxes(0, 1) for a in (q, k, lg)) + (write,))
    return out.swapaxes(0, 1)


@pytest.mark.parametrize("fault", ["log_gate_in_bfloat16", "fold_drops_key_sum"])
def test_a_faulty_window_fails_the_tolerance(highest, monkeypatch, fault):
    """What holds the window to float32 and the fold to the whole chunked
    form: a window whose running log-gate is rounded to bfloat16 as it is
    handed on, and a fold that leaves the key sum out, each miss the
    recurrent form by more than ten times the tolerance."""
    window = pr.WINDOW
    s = 3 * window + 2
    q, k, v, _ = inputs(s, seed=17)
    lg = jax.nn.log_sigmoid(rand(23, B, s, KV) + 2.0)
    state0 = pr.empty_state(B, KV, D)
    held = lambda w: w
    if fault == "log_gate_in_bfloat16":
        held = lambda w: w[:2] + (jax.lax.reduce_precision(w[2], 8, 7),)
    else:
        sound = pr._fold_terms

        def no_sum(*a):
            turn, fade, vd, pk = sound(*a)
            return turn, fade, vd.at[:, D].set(0.0), pk
        monkeypatch.setattr(pr, "_fold_terms", no_sum)
    # (the step is jitted: a trace of the sound one at these shapes must
    # not stand in for the faulty one, nor the faulty one be left behind)
    pr._read_step.clear_cache()
    try:
        got, _ = deferred_steps(q, k, v, lg, state0, window, held=held)
    finally:
        monkeypatch.undo()
        pr._read_step.clear_cache()
    assert off(got, q, k, v, lg) > 10 * MIXER_TOL


def test_a_query_head_reads_its_group_s_state(highest):
    """Query head ``h`` reads key head ``h // (heads / kv_heads)``: with the
    other key head's keys, values and gates replaced, its output stays."""
    q, k, v, lg = inputs(300, seed=3)
    base = _kernel(q, k, v, lg)
    for c in range(KV):
        other = 1 - c
        k2 = k.at[:, :, other].set(rand(20, B, 300, D))
        v2 = v.at[:, :, other].set(rand(21, B, 300, D))
        lg2 = lg.at[:, :, other].set(-0.5)
        moved = _kernel(q, k2, v2, lg2)
        mine = slice(c * (H // KV), (c + 1) * (H // KV))
        theirs = slice(other * (H // KV), (other + 1) * (H // KV))
        assert np.array_equal(moved[:, :, mine], base[:, :, mine])
        assert np.abs(moved[:, :, theirs] - base[:, :, theirs]).max() > 1e-2


@pytest.mark.parametrize("log_gamma,what", [(0.0, "sum"), (-40.0, "own")],
                         ids=["gate_of_1", "gate_near_0"])
def test_the_gate_s_edges(highest, log_gamma, what):
    """A gate of 1 forgets nothing (the weights are the squared products
    alone); a gate near 0 forgets all but the token itself (``o_t = v_t``),
    with nothing overflowing on the way."""
    q, k, v, _ = inputs(300, seed=5)
    lg = jnp.full((B, 300, KV), log_gamma)
    got = _kernel(q, k, v, lg)
    assert np.isfinite(got).all()
    assert off(got, q, k, v, lg) <= MIXER_TOL
    if what == "own":
        own = jnp.repeat(v, H // KV, axis=2)
        weight = jnp.einsum("bthd,bthd->bth", q, jnp.repeat(k, H // KV, axis=2)) ** 2
        assert np.abs((got - own) * jnp.minimum(weight, 1.0)[..., None]).max() <= 1e-5


# -- the layer and the model against the reference ------------------------------------


def layer_params(seed):
    """One layer's parameters from the program's own table, random."""
    prog = pt.build(lambda x: {"p": layer.retention_params(DIMS, jnp.float32)})
    params, _ = prog.init(jax.random.PRNGKey(seed), x=np.zeros(1, np.float32))
    out = {}
    for i, (name, v) in enumerate(sorted(params.items())):
        n = name.split("mixer/")[1]
        out[n] = (rand(seed + i, *v.shape, scale=min(v.shape) ** -0.5)
                  if v.ndim == 2 else 1.0 + rand(seed + i, *v.shape, scale=0.1))
    out["gate/b"] = out["gate/b"] + 3.0
    return out


def test_layer_prefill_against_reference(highest):
    """The mixer over 300 tokens from an empty state, and over its last 44
    from the state the first 256 leave, against the reference's one pass."""
    p = layer_params(3)
    x = rand(9, B, 300, 64)
    ref = family.reference_mixer(lambda n: p[n.split("mixer/")[1]], TINY)
    want = jax.jit(jax.vmap(lambda row: reference.mixer_part(row, ref, SHAPE)))(x)
    prefill = jax.jit(lambda x, state, p0: layer.retention_prefill(
        x, p, DIMS, state, p0), static_argnums=2)
    got, _, given = prefill(x, pr.empty_state(B, KV, D), 0)
    assert np.abs(got - want).max() <= MIXER_TOL
    assert [a.shape for a in given] == [(B, 300, KV * D), (B, 300, KV * D),
                                        (B, 300, KV)]
    _, state, _ = prefill(x[:, :256], pr.empty_state(B, KV, D), 0)
    tail, _, _ = prefill(x[:, 256:], state, 256)
    assert np.abs(tail - want[:, 256:]).max() <= MIXER_TOL


def seeded_params(config, prompt_len, seed=3):
    """The family's seeded weights (its gate lets the state reach across a
    chunk), as the program's parameter dict."""
    weights = family.decoder_params(config, seed, prompt_len, 4)
    return weights, jax.tree.map(jnp.asarray, weights.host_params())


def scored(config, prompt, nxt, params):
    prog = pt.build(decoding.make_scorer(brumby._decoder,
                                         family.program_config(config)))
    out, _ = prog.apply(params, {}, training=False, prompt_ids=prompt,
                        next_ids=nxt)
    return np.asarray(out["logp"])


def reference_logp(params, config, ids, first):
    ref = family.reference_params(params, config)
    forward = jax.jit(jax.vmap(lambda row: jax.nn.log_softmax(
        reference.forward(ref, row, SHAPE)[first:])))
    return np.asarray(forward(jnp.asarray(ids)))


@pytest.mark.parametrize("prompt_len,new", [
    (300, 8), (512, 4), (256, 1), (40, 6), (300, 2 * pr.WINDOW + 4),
    (40, 3 * pr.WINDOW + 1)],
    ids=["chunk_and_tail", "two_chunks", "one_chunk_one_step", "one_piece",
         "chunk_and_tail_across_two_folds", "one_piece_across_three_folds"])
def test_prefill_then_steps_against_reference(highest, prompt_len, new):
    """Prefill (a whole chunk and a tail; a scan over two chunks; one chunk;
    one short piece), then one-token steps through the carried states and
    their windows (the last two long enough for every state to be folded
    twice and three times): the
    log-probabilities at every position against the reference's one full
    forward. (The kernel handing its own state from chunk to chunk inside
    one call: ``test_kernel_against_its_jnp_form_from_a_state``.)"""
    rng = np.random.RandomState(2)
    prompt = rng.randint(3, VOCAB, (2, prompt_len)).astype(np.int32)
    nxt = rng.randint(3, VOCAB, (2, new)).astype(np.int32)
    _, params = seeded_params(TINY, prompt_len)
    got = scored(TINY, prompt, nxt, params)
    want = reference_logp(params, TINY, np.concatenate([prompt, nxt], 1),
                          prompt_len - 1)
    assert got.shape == want.shape == (2, new + 1, VOCAB)
    assert np.abs(got - want).max() <= LOGIT_TOL


def test_a_bfloat16_reference_fails_the_tolerance():
    """The tolerance is a check: the reference computed in bfloat16 (the
    precision below the float32 the tests state) misses it."""
    rng = np.random.RandomState(2)
    prompt = rng.randint(3, VOCAB, (1, 60)).astype(np.int32)
    nxt = rng.randint(3, VOCAB, (1, 4)).astype(np.int32)
    _, params = seeded_params(TINY, 60)
    with jax.default_matmul_precision("highest"):
        got = scored(TINY, prompt, nxt, params)
    ref = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                       family.reference_params(params, TINY))
    low = jax.jit(lambda ids: reference.forward(ref, ids, SHAPE))(
        jnp.asarray(np.concatenate([prompt, nxt], 1)[0]))[59:]
    low = np.asarray(jax.nn.log_softmax(low.astype(jnp.float32)))
    assert np.abs(got[0] - low).max() > 10 * LOGIT_TOL


def test_bfloat16_weights_are_held_in_bfloat16():
    """``run.dtype`` bfloat16: every matrix in bfloat16, the norms' scales
    and the gate's bias float32; the generator runs."""
    prompt = np.random.RandomState(6).randint(3, VOCAB, (2, 40)).astype(np.int32)
    gen = pt.build(brumby.make_generator(
        family.program_config(tiny(dtype="bfloat16")), max_new_tokens=4))
    params, _ = gen.init(jax.random.PRNGKey(1), prompt_ids=prompt)
    assert {v.dtype for k, v in params.items() if k.endswith("/w")} == {
        jnp.dtype(jnp.bfloat16)}
    assert {v.dtype for k, v in params.items() if k.endswith(("/g", "/b"))} == {
        jnp.dtype(jnp.float32)}
    ids = gen.apply(params, {}, training=False, prompt_ids=prompt)[0]["ids"]
    assert ids.shape == (2, 4) and ids.dtype == jnp.int32


def test_generator_emits_the_scorer_s_argmax_and_records_its_plans(highest):
    """Greedy ids are the argmax of the scorer's distributions under those
    ids; the trace leaves one ``decode.plan`` that carries a state and no
    key or value, a ``prefill.plan``, and a ``retention.plan`` a mixer in
    each form."""
    prompt = np.random.RandomState(4).randint(3, VOCAB, (2, 556)).astype(np.int32)
    _, params = seeded_params(TINY, 556)
    gen = pt.build(brumby.make_generator(family.program_config(TINY),
                                         max_new_tokens=5))
    since = profiler.time.time_ns()
    ids = np.asarray(gen.apply(params, {}, training=False,
                               prompt_ids=prompt)[0]["ids"])
    spans = profiler.spans(since)
    (plan,) = [s[4] for s in spans if s[0] == "decode.plan"]
    assert plan["cache_kind"] == "state" and plan["kv_bytes"] == 0
    assert (plan["state_layers"], plan["state_dtype"]) == (2, "float32")
    # 2 layers x 2 rows x 2 key heads x (16 + 8 sublanes) x 192 products
    assert plan["state_bytes"] == 2 * 2 * 2 * 16 * 192 * 4
    assert plan["norm_bytes"] == 2 * 2 * 2 * 8 * 192 * 4
    assert plan["cache_bytes"] == plan["state_bytes"] + plan["norm_bytes"]
    (pre,) = [s[4] for s in spans if s[0] == "prefill.plan"]
    assert (pre["chunk"], pre["chunks"], pre["rows"]) == (256, 2, 2)
    # a step writes back one state of the four (a run's one), and carries
    # 8 tokens a key head: keys and values of 16, a running log-gate
    assert plan["state_write_bytes"] == plan["cache_bytes"] // 4
    assert plan["window_bytes"] == 2 * 2 * 2 * pr.WINDOW * (2 * 16 + 1) * 4
    plans = [s[4] for s in spans if s[0] == "retention.plan"]
    # a layer: the scan's chunk, the tail of 44, the step
    assert [(p["form"], p["seq"], p["chunks"], p["tail"]) for p in plans] == 2 * [
        ("chunked", 256, 1, 0)] + 2 * [("chunked", 44, 0, 44)] + 2 * [
        ("step", 1, 0, 0)]
    assert [(p.get("window"), p.get("fold_share")) for p in plans] == 4 * [
        (None, None)] + 2 * [(pr.WINDOW, 0.25)]
    assert {(p["heads"], p["kv_heads"], p["head_dim"], p["degree"], p["chunk"],
             p["state_rows"], p["state_rows_symmetric"], p["state_dtype"],
             p["gate"]) for p in plans} == {
        (4, 2, 16, 2, 256, 192, 136, "float32", "token")}
    nxt = ids[:, :-1]
    logp = scored(TINY, prompt, nxt, params)
    ended = np.cumsum(ids == 2, axis=1) - (ids == 2) > 0
    assert (np.where(ended, 2, np.argmax(logp, -1)) == ids).all()


def test_served_ids_are_the_direct_call_s(tmp_path, highest):
    """``export_decoder(model=brumby)`` -> ``load_inference_model`` ->
    ``PredictorServer``: a bucket-sized request and a single prompt that
    pads both return the ids of a direct call of the program."""
    from paddle_tpu.fleet import decode

    prompt = np.random.RandomState(6).randint(3, VOCAB, (2, 40)).astype(np.int32)
    cfg = family.program_config(TINY)
    _, params = seeded_params(TINY, 40)
    gen = pt.build(brumby.make_generator(cfg, max_new_tokens=4))
    direct = np.asarray(gen.apply(params, {}, training=False,
                                  prompt_ids=prompt)[0]["ids"])
    decode.export_decoder(str(tmp_path / "m"), cfg, 4, prompt, params=params,
                          model=brumby)
    server = decode.decode_server(str(tmp_path / "m"), max_wait_ms=1)
    try:
        whole = server.submit({"prompt_ids": prompt}).result(timeout=300)
        one = server.submit({"prompt_ids": prompt[1:]}).result(timeout=300)
    finally:
        server.close(drain=False, timeout=30)
    assert np.array_equal(np.asarray(whole["ids"]), direct)
    assert np.array_equal(np.asarray(one["ids"]), direct[1:])
    # the audit comes back with the rows it belongs to
    assert sorted(whole) == ["audit_k", "audit_log_gamma", "audit_sums",
                             "audit_v", "ids"]
    assert np.asarray(one["audit_sums"]).shape == (1, D + pr.NORM_ROWS, D)
    assert np.array_equal(np.asarray(one["audit_k"]),
                          np.asarray(whole["audit_k"])[1:])


def generated(prompt, params, new=6, fault=None):
    """A direct call's outputs in numpy, traced under a fault of the
    benchmark's sensitivity tool if one is named."""
    from benchmarks.tools import brumby_sensitivity as tool

    sound = lambda fn: fn
    gen = pt.build(brumby.make_generator(family.program_config(TINY),
                                         max_new_tokens=new))
    with tool.faulted(tool.faults()[fault] if fault else (sound, sound)):
        out = gen.apply(params, {}, training=False, prompt_ids=prompt)[0]
    return {k: np.asarray(v) for k, v in out.items()}


def test_family_check_passes_on_served_ids_and_fails_on_wrong_ones(highest):
    """The benchmark's own check at the tiny size: greedy ids pass, the
    same ids shifted by one id fail."""
    prompt = np.random.RandomState(8).randint(3, VOCAB, (2, 40)).astype(np.int32)
    weights, params = seeded_params(TINY, 40)
    audit = generated(prompt, params)
    good = family.served_check(TINY, weights, prompt, audit["ids"], audit=audit)
    assert good["ok"] and good["worst_logit_gap"] < 1e-2, good
    assert good["carried"]["ids_as_served"] == 1.0
    assert good["carried"]["positions"] == 40 + 6 - 1
    assert not family.served_check(TINY, weights, prompt,
                                   (audit["ids"] + 1) % VOCAB, audit=audit)["ok"]


@pytest.mark.parametrize("fault", [
    None, "state_in_bfloat16", "key_sum_dropped", "gate_of_1",
    "state_zeroed_between_chunks"])
def test_the_audit_tells_how_the_state_was_carried(highest, fault):
    """A request's audit against the definition in float64
    (``reference.carried_sums``): a sound generator reads float32 rounding,
    a hundred times under the limit; a state rounded to bfloat16 at every
    hand-over, which serves tokens as good as the sound ones, reads over it,
    as do a dropped key sum, a gate of 1 and a state zeroed between the
    prompt's chunks."""
    prompt = np.random.RandomState(8).randint(3, VOCAB, (2, 600)).astype(np.int32)
    _, params = seeded_params(TINY, 600)
    got = family.carried_check(generated(prompt, params, new=40, fault=fault))
    assert got["positions"] == 600 + 40 - 1
    if fault is None:
        assert got["ok"] and got["carried_error"] < family.CARRIED_ERROR_LIMIT / 100
    else:
        # (a state is rounded where it is handed on, and a step hands on what
        # it wrote, one state in ``pr.WINDOW``: 40 steps here read 3.5e-3
        # under a bfloat16 state, and 8.3e-3 when every step wrote every
        # state; the chip's 255 steps: PERF.md section 6, PR 40)
        margin = 1.2 if fault == "state_in_bfloat16" else 2
        assert not got["ok"]
        assert got["carried_error"] > margin * family.CARRIED_ERROR_LIMIT
    if fault == "key_sum_dropped":
        assert got["state_error"] < family.CARRIED_ERROR_LIMIT < got["key_sum_error"]


@pytest.mark.parametrize("pending,added", [
    (0, True), (1, True), (pr.WINDOW - 1, True), (pr.WINDOW - 1, False)],
    ids=["none_pending", "one_pending", "all_but_one_pending",
         "pending_left_out"])
def test_the_audit_adds_what_the_window_still_holds(highest, monkeypatch,
                                                    pending, added):
    """``audit_sums`` is the audited head's slice with its window's pending
    tokens added: at generation lengths that leave row 0's audited head
    (pair 0, whose turn comes at every position that is a multiple of the
    window) nothing, one token and all but one token unfolded after two
    folds, the sums are the definition's to float32 rounding; the slice
    of the state alone, with seven tokens still beside it, is not."""
    prompt = np.random.RandomState(8).randint(3, VOCAB, (2, 40)).astype(np.int32)
    _, params = seeded_params(TINY, 40)
    if not added:
        monkeypatch.setattr(pr, "folded_first_products",
                            lambda state, window, position, head:
                            pr.first_products(state, head))
    # the last token goes in at position 40 + new - 2: past two folds of
    # pair 0, ``pending`` positions after one of its turns
    new = 2 * pr.WINDOW + 2 + (pending - 40) % pr.WINDOW
    assert (40 + new - 2) % pr.WINDOW == pending
    got = family.carried_check(generated(prompt, params, new=new))
    assert got["positions"] == 40 + new - 1
    if added:
        assert got["ok"] and got["carried_error"] < family.CARRIED_ERROR_LIMIT / 100
    else:
        assert not got["ok"] and got["carried_error"] > 2 * family.CARRIED_ERROR_LIMIT


def test_the_check_asks_the_live_server_for_its_audit(tmp_path, highest):
    """``served_audit``: the one ready server of the process serves the rows
    again, in requests of its largest bucket, and hands back every output
    (three rows through a two-row bucket: two requests, the second padded)."""
    from paddle_tpu.fleet import decode

    prompt = np.random.RandomState(6).randint(3, VOCAB, (3, 40)).astype(np.int32)
    _, params = seeded_params(TINY, 40)
    want = generated(prompt, params, new=4)
    decode.export_decoder(str(tmp_path / "m"), family.program_config(TINY), 4,
                          prompt[:2], params=params, model=brumby)
    for left in family.ready_servers():     # by an earlier test of this process
        left.close(drain=False, timeout=30)
    with pytest.raises(RuntimeError, match="0 ready servers"):
        family.served_audit(prompt)
    server = decode.decode_server(str(tmp_path / "m"), max_wait_ms=1)
    try:
        got = family.served_audit(prompt)
    finally:
        server.close(drain=False, timeout=30)
    assert np.array_equal(got["ids"], want["ids"])
    assert got["audit_sums"].shape == (3, D + pr.NORM_ROWS, D)
    assert family.carried_check(got)["ok"]


# -- the family's arithmetic, at the published numbers ----------------------------------


def family_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "brumby-14b-pp5.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_family_s_parameter_table_is_the_program_s(dtype):
    """``family.parameter_table`` writes the program's parameters down by
    arithmetic (no trace of the generator in a run's set-up): names, order,
    shapes and dtypes are those of the program's own init."""
    config = tiny(dtype=dtype)
    gen = pt.build(brumby.make_generator(family.program_config(config),
                                         max_new_tokens=4))
    own = jax.eval_shape(lambda key: gen.init(
        key, prompt_ids=np.zeros((1, 8), np.int32))[0], jax.random.PRNGKey(0))
    table = family.parameter_table(config)
    assert list(table) == list(own)
    assert all((table[n].shape, table[n].dtype) == (own[n].shape, own[n].dtype)
               for n in own)


def test_family_counts_at_the_published_widths():
    """The cut of ISSUE 39 by hand: 330.34M parameters a layer, 4.199B in 8
    layers with embedding and head; 0.2727 GB of state a row; a step moves
    15.6 GB, 56% of it states; a token and layer needs 765 MFLOP, 104 of
    them the retention's."""
    config = family_config()
    assert config["run"]["chunk"] == pr.CHUNK
    c = family._counts(config)
    assert c["mixer"] + c["ffn"] == 330_342_400
    assert 8 * (c["mixer"] + c["ffn"]) + 2 * c["head"] == pytest.approx(
        4.199e9, rel=1e-3)
    assert family.symmetric_rows(config) == 8256
    assert 8 * family.state_bytes(config, 1) == pytest.approx(0.2727e9, rel=1e-3)
    step = family.decode_step_bytes(config, 16, 1100)
    assert step == family.decode_step_bytes(config, 16, 31000)
    states = 8 * 2 * family.state_bytes(config, 16)
    assert step == 2 * (8 * (c["mixer"] + c["ffn"]) + c["head"]) + states
    assert step == pytest.approx(15.57e9, rel=1e-3) and 0.55 < states / step < 0.57
    a_token = family.retention_flops(config, 1, 1)
    assert a_token == 40 * (2 * 8256 * 128 + 2 * 128 * 257) + 8 * 2 * 8256 * 128
    assert a_token == pytest.approx(104.1e6, rel=1e-3)
    assert 2 * (c["mixer"] + c["ffn"]) + a_token == pytest.approx(764.8e6, rel=1e-3)
    assert family.prefill_flops(config, 16, 1024) == pytest.approx(
        16 * 1024 * 8 * (2 * (c["mixer"] + c["ffn"]) + a_token) + 2 * 16 * c["head"])
    assert family.prefill_flops(config, 16, 1024) == pytest.approx(100.3e12, rel=1e-2)
    ops, moved, calls = family.kernel_counts(config, 16, 1024, "retention_fwd")
    assert calls == 32 and ops == 8 * 16 * 1024 * a_token
    assert ops / 197e12 > moved / 819e9             # compute-bound
    ops, moved = family.step_kernel_counts(config, 16, "retention_step")
    assert moved == 2 * family.state_bytes(config, 16)
    assert moved / 819e9 > ops / 197e12             # bandwidth-bound
    assert family.kernel_counts(config, 16, 1024, "flash_fwd") is None
