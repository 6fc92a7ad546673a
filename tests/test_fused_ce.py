"""Chunked (logits-free) softmax CE (ops/fused_ce.py): value+grad
equivalence vs the dense path, with and without label smoothing, plus
the transformer integration flag; the training form (the weighted sum
whose gradients are made where its logits are) against the dense float32
head, on one device and as a dp x tp mesh walks it."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import debugger, optimizer as opt
from paddle_tpu.core import profiler
from paddle_tpu.framework import mesh_mode
from paddle_tpu.models import gpt
from paddle_tpu.ops.fused_ce import (chunked_softmax_cross_entropy,
                                     softmax_cross_entropy_sum)
from paddle_tpu.profiling.fusion import scope_table


@pytest.mark.parametrize("eps", [0.0, 0.1])
@pytest.mark.parametrize("with_bias", [True, False])
def test_fused_ce_matches_dense(eps, with_bias):
    rng = np.random.RandomState(0)
    n, d, v = 12, 16, 50           # v=50 with chunk=16 -> ragged last chunk
    h = jnp.asarray(rng.randn(n, d).astype(np.float32))
    w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(v).astype(np.float32) * 0.1) if with_bias else None
    lab = jnp.asarray(rng.randint(0, v, n))

    def dense(h, w, b):
        logits = h @ w + (b if b is not None else 0.0)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, lab[:, None], 1)[:, 0]
        return ((1 - eps) * nll - eps * jnp.mean(logp, -1)).sum()

    def fused(h, w, b):
        return chunked_softmax_cross_entropy(h, w, b, lab, eps, 16).sum()

    argnums = (0, 1, 2) if with_bias else (0, 1)
    v1, g1 = jax.value_and_grad(dense, argnums)(h, w, b)
    v2, g2 = jax.value_and_grad(fused, argnums)(h, w, b)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=2e-4, atol=1e-5)


def test_fused_ce_bf16_inputs_close_to_f32():
    rng = np.random.RandomState(1)
    n, d, v = 8, 16, 32
    h = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, v).astype(np.float32) * 0.1
    lab = jnp.asarray(rng.randint(0, v, n))
    f32 = chunked_softmax_cross_entropy(jnp.asarray(h), jnp.asarray(w), None, lab, 0.0, 16)
    bf = chunked_softmax_cross_entropy(jnp.asarray(h, jnp.bfloat16),
                                       jnp.asarray(w, jnp.bfloat16), None, lab, 0.0, 16)
    assert bf.dtype == jnp.float32  # loss always reduces in f32
    np.testing.assert_allclose(np.asarray(f32), np.asarray(bf), rtol=0.05, atol=0.05)


def test_transformer_fused_ce_equals_dense():
    rng = np.random.RandomState(0)
    from paddle_tpu.models import transformer
    feed = {"src_ids": rng.randint(3, 64, (2, 8)).astype(np.int64),
            "trg_ids": rng.randint(3, 64, (2, 8)).astype(np.int64),
            "labels": rng.randint(0, 64, (2, 8)).astype(np.int64)}
    losses, grads = {}, {}
    for fused in (False, True):
        cfg = transformer.base_config(
            src_vocab=64, trg_vocab=64, d_model=16, d_inner=32, num_heads=2,
            num_encoder_layers=1, num_decoder_layers=1, dropout=0.0,
            fused_ce=fused, ce_chunk=16)
        prog = pt.build(transformer.make_model(cfg))
        params, state = prog.init(jax.random.PRNGKey(0), **feed)

        def loss_fn(p):
            out, _ = prog.apply(p, state, **feed)
            return out["loss"]

        losses[fused], grads[fused] = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(float(losses[False]), float(losses[True]), rtol=1e-5)
    for k in grads[False]:
        np.testing.assert_allclose(np.asarray(grads[False][k]),
                                   np.asarray(grads[True][k]), rtol=5e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# the training form


def _dense_sum(h, w, b, lab, tw, eps):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, lab[:, None], 1)[:, 0]
    return jnp.sum(((1 - eps) * nll - eps * jnp.mean(logp, -1)) * tw)


# (rows, vocab, rows a chunk, eps, bias, dtype, scalar cotangent)
SUM_CASES = {
    "plain": (12, 128, 4, 0.0, False, "float32", 1.0),
    "smoothed": (12, 128, 4, 0.1, False, "float32", 1.0),
    "bias": (12, 128, 4, 0.0, True, "float32", 1.0),
    "smoothed_bias": (12, 128, 4, 0.1, True, "float32", 1.0),
    "ragged_rows": (13, 128, 5, 0.1, True, "float32", 1.0),
    "ragged_vocab": (12, 50, 4, 0.1, True, "float32", 1.0),
    "one_chunk": (12, 50, 4096, 0.0, False, "float32", 1.0),
    "loss_scale": (13, 50, 5, 0.1, True, "float32", 1024.0),
    "bfloat16": (13, 50, 5, 0.0, True, "bfloat16", 1.0),
    "bfloat16_loss_scale": (13, 50, 5, 0.1, False, "bfloat16", 1024.0),
    "float16_loss_scale": (13, 50, 5, 0.0, True, "float16", 1024.0),
}


@pytest.mark.parametrize("case", list(SUM_CASES))
def test_ce_sum_matches_the_dense_float32_head(case):
    """Value and the gradients of hidden, weight and bias, under a scalar
    cotangent of 1 or of a loss scale, with pad tokens (weights of 0)."""
    n, v, rows, eps, with_bias, dtype, scale = SUM_CASES[case]
    rng = np.random.RandomState(3)
    d = 16
    h = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, v).astype(np.float32) * 0.1
    b = rng.randn(v).astype(np.float32) * 0.1 if with_bias else None
    lab = jnp.asarray(rng.randint(0, v, n))
    keep = (rng.rand(n) > 0.3).astype(np.float32)
    tw = jnp.asarray(keep / max(keep.sum(), 1.0))
    ref = [jnp.asarray(x) if x is not None else None for x in (h, w, b)]
    got = [x.astype(dtype) if x is not None else None for x in ref]

    argnums = (0, 1, 2) if with_bias else (0, 1)
    v1, g1 = jax.value_and_grad(
        lambda *a: scale * _dense_sum(*a, lab, tw, eps), argnums)(*ref)
    v2, g2 = jax.value_and_grad(
        lambda *a: scale * softmax_cross_entropy_sum(*a, lab, tw, eps, rows),
        argnums)(*got)
    exact = dtype == "float32"
    np.testing.assert_allclose(float(v2), float(v1),
                               rtol=1e-5 if exact else 2e-2)
    for a, bb, x in zip(g1, g2, got):
        assert bb.dtype == x.dtype and bb.shape == x.shape
        a, bb = np.asarray(a), np.asarray(bb, np.float32)
        if exact:
            np.testing.assert_allclose(bb, a, rtol=2e-4, atol=1e-5 * scale)
        else:   # 8 (bfloat16) or 11 (float16) bits of each rounding
            assert np.linalg.norm(bb - a) <= 3e-2 * np.linalg.norm(a)
    # a pad token's row gets no gradient
    assert not np.asarray(g2[0], np.float32)[keep == 0].any()


def _dots(jaxpr):
    """dot_general equations of a jaxpr, those of its sub-jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _dots(sub)
    return n


def test_ce_sum_makes_one_product_a_chunk_and_three_under_grad():
    """The primal call walks the loss alone; the differentiated call makes
    the logits, dh and dW of a chunk and nothing twice: the backward pass
    is a scaling."""
    h, w = jnp.ones((12, 16)), jnp.ones((16, 50))
    lab, tw = jnp.zeros((12,), jnp.int32), jnp.ones((12,))

    def loss(h, w):
        return softmax_cross_entropy_sum(h, w, None, lab, tw, 0.0, 4)

    since = time.time_ns()
    assert _dots(jax.make_jaxpr(loss)(h, w).jaxpr) == 1
    plan, = [s[4] for s in profiler.spans(since) if s[0] == "ce.plan"]
    assert _dots(jax.make_jaxpr(jax.grad(loss, (0, 1)))(h, w).jaxpr) == 3
    assert plan == {
        "rows": 12, "rows_per_chunk": 4, "chunks": 3, "vocab": 50,
        "vocab_padded": 128, "products_per_chunk": 3,
        "logits_block_bytes": 4 * 128 * 4, "form": "grad_in_forward",
        "sharded_over": "", "why": "one device"}
    since = time.time_ns()
    chunked_softmax_cross_entropy(h, w, None, lab, 0.0, 16)
    plan, = [s[4] for s in profiler.spans(since) if s[0] == "ce.plan"]
    assert (plan["form"], plan["chunks"], plan["vocab_padded"],
            plan["products_per_chunk"]) == ("per_token", 4, 64, 4)


def test_dp_tp_mesh_walks_its_own_rows_and_sums_the_head_once():
    """A tiny GPT step on CPU devices as a dp2 x tp2 mesh equals the
    single-device step; the head runs per data shard (``ce.plan``), and
    the lowered step all-reduces the head's gradient over ``dp`` once,
    under scope ``ce``, outside the loop over its chunks."""
    d, vocab = 32, 200
    cfg = gpt.base_config(vocab_size=vocab, max_len=16, d_model=d, d_inner=64,
                          num_heads=4, num_layers=2, use_flash=False,
                          fused_ce=True, ce_chunk=16)
    rng = np.random.RandomState(0)
    ids = rng.randint(1, vocab, (8, 17)).astype(np.int32)
    ids[:, -3:] = 0                                       # pad tokens
    feed = {"ids": ids[:, :-1], "labels": ids[:, 1:]}

    def trainer(mesh):
        tr = pt.Trainer(
            pt.build(gpt.make_model(cfg)), opt.SGD(0.5), loss_name="loss",
            mesh=mesh, sharding_rules=(
                pt.parallel.transformer_tp_rules() if mesh else None))
        tr.startup(sample_feed=feed)
        return tr

    one = trainer(None)
    loss = float(one.step(feed)["loss"])
    since = time.time_ns()
    four = trainer(pt.make_mesh({"dp": 2, "tp": 2},
                                devices=jax.devices("cpu")[:4]))
    text = debugger._lower_step(four, feed).compile().as_text()
    np.testing.assert_allclose(float(four.step(feed)["loss"]), loss, rtol=1e-5)
    for k, v in one.scope.params.items():
        np.testing.assert_allclose(np.asarray(four.scope.params[k]),
                                   np.asarray(v), rtol=2e-4, atol=2e-6)

    # startup's trace of the initializers has no mesh; the step's has
    plan = [s[4] for s in profiler.spans(since) if s[0] == "ce.plan"][-1]
    assert (plan["sharded_over"], plan["rows"], plan["chunks"], plan["why"]
            ) == ("dp", 4 * 16, 4, ""), plan

    # as traced: one psum of the head's gradient, over dp, under scope
    # ``ce``, at the shard_map's exit and not in the scan over its chunks
    params = one.scope.params

    def traced(p):
        with mesh_mode(four.mesh):
            return four.program.apply(p, {}, training=True, **feed)[0]["loss"]

    head = [(axes, scope, in_scan)
            for axes, shapes, scope, in_scan in _psums(
                jax.make_jaxpr(jax.grad(traced))(params).jaxpr)
            if (d, vocab) in shapes]
    assert len(head) == 1 and head[0][0] == ("dp",), head
    assert "(ce)" in head[0][1] and not head[0][2], head

    # as compiled: the partitioner added none of its own, in a loop or out
    # (it may combine the head's with the blocks')
    table = scope_table(text, tuple(four.mesh.shape.items()))
    shape = rf"f32\[{d},{vocab}\]"
    reduces = [ln for lines in _computations(text).values() for ln in lines
               if re.search(r" all-reduce(-start)?\(", ln)
               and re.search(shape, ln.split(" all-reduce")[0])]
    assert len(reduces) == 1, [ln[:200] for ln in reduces]
    name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", reduces[0]).group(1)
    assert table[name].axes == "dp"
    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text))
    assert not any(reduces[0] in lines for body, lines
                   in _computations(text).items() if body in bodies)


def _psums(jaxpr, outer="", in_scan=False):
    """``(axes, operand shapes, name stack, inside a scan)`` of every psum
    of a jaxpr, those of its sub-jaxprs included (whose name stacks start
    at the equation that holds them)."""
    out = []
    for eqn in jaxpr.eqns:
        scope = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "psum":
            out.append((tuple(eqn.params["axes"]),
                        [v.aval.shape for v in eqn.invars], scope, in_scan))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _psums(sub, scope,
                          in_scan or eqn.primitive.name in ("scan", "while"))
    return out


def _computations(text):
    """``{name: lines}`` of an HLO module's computations."""
    out, name = {}, None
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", ln)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(ln)
    return out
