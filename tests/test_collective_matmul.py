"""The tensor-parallel block with its activation sharded over ``tp`` between
matmuls (layers/stacked.py ``_batch_sharded``, parallel/collective_matmul.py)
on four virtual devices:
the same loss and the same gradients as one device and as the GSPMD form it
replaces, the fallbacks it must take, and no trace of it in a program that
has no mesh.
"""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.core import profiler
from paddle_tpu.framework import cast_compute, mesh_mode
from paddle_tpu.layers import stacked as S
from paddle_tpu.models import gpt
from paddle_tpu.parallel import collective_matmul as cm

D, INNER, HEADS, LAYERS, BATCH = 32, 64, 4, 2, 8


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return pt.make_mesh(axes, devices=jax.devices("cpu")[:n])


def _stack_program(decoder=False, key_bias=False, seq=16, remat=True,
                   batch=BATCH):
    """x -> a stack of blocks -> a scalar, with the side inputs the zoo's
    encoder (a key bias) and decoder (encoder output and its bias) pass."""
    def net(x, enc, bias):
        if decoder:
            stack = S.decoder_stack_params(LAYERS, D, INNER)
            extras = {"enc": enc, "enc_bias": bias}
        else:
            stack = S.encoder_stack_params(LAYERS, D, INNER)
            extras = bias if key_bias else None
        y = S.apply_stacked(
            x, stack, S.make_decoder_block if decoder else S.make_encoder_block,
            extras=extras, num_heads=HEADS, causal=not key_bias, remat=remat)
        return {"loss": jnp.mean(jnp.square(y))}

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(batch, seq, D).astype(np.float32),
            "enc": rng.randn(batch, 12, D).astype(np.float32),
            "bias": np.where(rng.rand(batch, 12 if decoder else seq) < 0.2,
                             S.NEG_INF, 0.0).astype(np.float32)}
    prog = pt.build(net)
    params, _ = prog.init(jax.random.PRNGKey(0), **feed)
    return prog, params, feed


def _loss_and_grads(prog, params, feed, mesh, gspmd=False):
    """Loss, gradients by parameter and by input, and the ``tp.plan`` spans
    the trace left; ``gspmd`` keeps the stack from the sharded form."""
    def loss(p, f):
        with mesh_mode(mesh):
            return prog.apply(p, {}, training=True, **f)[0]["loss"]

    if mesh is not None:
        rules = pt.parallel.transformer_tp_rules().adapted_to(mesh)
        params = rules.shard_params(mesh, params)
    since = time.time_ns()
    with pytest.MonkeyPatch.context() as patch:
        if gspmd:
            patch.setattr(S, "_batch_sharded_why_not",
                          lambda *a, **k: "forced by the test")
        out = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, feed)
    plans = [s[4] for s in profiler.spans(since) if s[0] == "tp.plan"]
    return out, plans


def _assert_same(got, want, tol=2e-5):
    (loss, (gp, gf)), (ref_loss, (ref_gp, ref_gf)) = got, want
    np.testing.assert_allclose(loss, ref_loss, rtol=tol)
    for k in ref_gp:
        np.testing.assert_allclose(gp[k], ref_gp[k], atol=tol, rtol=tol,
                                   err_msg=k)
    for k in ("x", "enc"):
        np.testing.assert_allclose(gf[k], ref_gf[k], atol=tol, rtol=tol,
                                   err_msg=k)


# name -> (mesh axes, program options, the form tp.plan must report)
CASES = {
    "dp2tp2": ({"dp": 2, "tp": 2}, {}, "windowed"),
    "dp2tp2_no_remat": ({"dp": 2, "tp": 2}, {"remat": False}, "windowed"),
    "odd_sequence": ({"dp": 2, "tp": 2}, {"seq": 15}, "windowed"),
    "rows_not_divisible": ({"dp": 2, "tp": 2}, {"batch": 6}, "all_reduce"),
    "key_bias": ({"dp": 2, "tp": 2}, {"key_bias": True}, "windowed"),
    "tp4_ring": ({"tp": 4}, {}, "windowed"),
    "decoder_cross_attention": ({"dp": 2, "tp": 2}, {"decoder": True},
                                "windowed"),
}


@pytest.mark.parametrize("against", ["one_device", "gspmd"])
@pytest.mark.parametrize("case", list(CASES))
def test_batch_sharded_stack_matches(case, against):
    axes, options, form = CASES[case]
    prog, params, feed = _stack_program(**options)
    mesh = _mesh(axes)
    got, plans = _loss_and_grads(prog, params, feed, mesh)
    assert [p["form"] for p in plans] == [form], plans
    plan = plans[0]
    batch, seq = feed["x"].shape[:2]
    local = batch // axes.get("dp", 1)
    assert plan["tp"] == axes["tp"] and plan["seq"] == seq
    if form == "windowed":
        rows = 3 if options.get("decoder") else 2
        remat = options.get("remat", True)
        assert plan["chunk_rows"] == local // axes["tp"] and plan["why"] == ""
        assert plan["exchanges_per_layer"] == {
            "forward": 2 * rows, "remat": (2 * rows - 1) * remat,
            "backward": 2 * rows}
        assert plan["bytes_per_exchange"] == plan["chunk_rows"] * seq * D * 4
    else:
        assert plan["chunk_rows"] == local and "divide" in plan["why"]
        assert plan["exchanges_per_layer"] == {"forward": 2, "remat": 1,
                                               "backward": 2}
    if against == "gspmd":
        want, ref_plans = _loss_and_grads(prog, params, feed, mesh, gspmd=True)
        assert [p["form"] for p in ref_plans] == ["all_reduce"]
    else:
        want, ref_plans = _loss_and_grads(prog, params, feed, None)
        assert ref_plans == []          # no mesh, no plan
    _assert_same(got, want)


@pytest.mark.parametrize("why,setup", [
    ("dropout", dict(dropout=0.1)),
    ("sequence parallelism", dict(sp=True)),
])
def test_batch_sharded_falls_back(why, setup):
    """Training dropout (masks are not folded per shard) and an active
    sequence-parallel context keep the GSPMD form, and ``tp.plan`` says
    why."""
    from paddle_tpu.framework import sp_mode

    mesh = _mesh({"sp": 2, "tp": 2} if setup.get("sp") else {"dp": 2, "tp": 2})

    def net(x):
        stack = S.encoder_stack_params(LAYERS, D, INNER)
        return {"y": S.apply_stacked(x, stack, S.make_encoder_block,
                                     num_heads=HEADS, causal=True,
                                     dropout_rate=setup.get("dropout", 0.0))}

    prog = pt.build(net)
    x = np.ones((BATCH, 16, D), np.float32)
    params, _ = prog.init(jax.random.PRNGKey(0), x=x)
    since = time.time_ns()
    with mesh_mode(mesh), (sp_mode(mesh, impl="ulysses") if setup.get("sp")
                           else contextlib.nullcontext()):
        jax.jit(lambda p: prog.apply(p, {}, training=True,
                                     rng=jax.random.PRNGKey(1), x=x)[0]["y"]
                ).lower(params)
    (plan,) = [s[4] for s in profiler.spans(since) if s[0] == "tp.plan"]
    assert plan["form"] == "all_reduce" and why in plan["why"], plan


def test_pipeline_stage_keeps_psum():
    """A stack inside the pipeline's shard_map closes partial sums with
    ``psum`` as before; the plan names the form."""
    from paddle_tpu.framework import pipeline_mode

    mesh = _mesh({"pp": 2, "tp": 2})
    prog, params, feed = _stack_program()
    since = time.time_ns()
    with pipeline_mode(mesh, microbatches=2):
        text = jax.jit(lambda p: prog.apply(p, {}, training=True, **feed)[0]
                       ["loss"]).lower(params).as_text()
    (plan,) = [s[4] for s in profiler.spans(since) if s[0] == "tp.plan"]
    assert plan["form"] == "all_reduce" and "psum" in plan["why"]
    assert "all_reduce" in text and "collective_permute" in text  # pp hops


@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_helpers_are_the_dense_matmuls(n):
    """``gather_matmul`` and ``matmul_scatter`` on a ring of n against the
    matmuls they decompose, and ``ring_order`` against the order in which
    ``gather_matmul`` holds the chunks."""
    mesh = _mesh({"tp": n})
    rng = np.random.RandomState(n)
    x = rng.randn(2 * n, 5, 8).astype(np.float32)      # [b, s, d]
    w1 = rng.randn(8, 3 * n).astype(np.float32)        # columns over tp
    w2 = rng.randn(3 * n, 8).astype(np.float32)        # rows over tp

    def shard(x_own, x_whole, w1_, w2_):
        h = cm.gather_matmul(x_own, lambda c: jnp.tanh(c @ w1_), "tp")
        rows = jnp.concatenate(cm.gather_matmul(x_own, lambda c: c, "tp"))
        y = cm.matmul_scatter(h, lambda c: c @ w2_, "tp")
        return y, rows - cm.ring_order(x_whole, "tp")

    y, gap = jax.jit(jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P("tp"), P(), P(None, "tp"), P("tp")),
        out_specs=(P("tp"), P("tp")), check_vma=False))(x, x, w1, w2)
    np.testing.assert_allclose(y, np.tanh(x @ w1) @ w2, atol=1e-4)
    assert not np.asarray(gap).any()


# -- the program without a mesh is the parent's ------------------------------
#
# The block as it stood before the sequence-sharded form existed, kept here
# as the yardstick: a GPT train step lowered through it and through
# layers/stacked.py must be the same text.


def _parent_encoder_block(num_heads, use_flash, causal, tp_axis, sp_cfg,
                          dropout_rate=0.0):
    def attn_out(x, p, o):
        o, ow = cast_compute(o, p["out/w"])
        o = jnp.matmul(o, ow)
        return x + S._drop(o + p["out/b"].astype(o.dtype), dropout_rate)

    def ffn(x, p):
        h = S._ln(x, p["ln2/scale"], p["ln2/bias"])
        h, w1, w2 = cast_compute(h, p["ffn_in/w"], p["ffn_out/w"])
        h = jax.nn.relu(jnp.matmul(h, w1) + p["ffn_in/b"].astype(h.dtype))
        h = S._drop(h, dropout_rate)
        h = jnp.matmul(h, w2)
        return x + S._drop(h + p["ffn_out/b"].astype(h.dtype), dropout_rate)

    def block(x, p, key_bias=None):
        with jax.named_scope("attn"):
            head_dim = x.shape[-1] // num_heads
            h = S._ln(x, p["ln1/scale"], p["ln1/bias"])
            h, w = cast_compute(h, p["qkv/w"])
            qkv = jnp.einsum("bsd,dke->bske", h, w) \
                + p["qkv/b"].astype(h.dtype)
            q, k, v = (S._split_heads(qkv[:, :, i], head_dim)
                       for i in range(3))
            o = S._sdpa(q, k, v, key_bias, causal, use_flash, sp_cfg,
                        dropout_rate=dropout_rate)
            x = attn_out(x, p, S._merge_heads(o))
        with jax.named_scope("ffn"):
            return ffn(x, p)

    return block


@pytest.mark.parametrize("remat", [False, True])
def test_one_device_train_step_is_the_parents(remat, monkeypatch):
    cfg = gpt.base_config(vocab_size=64, max_len=32, d_model=D, d_inner=INNER,
                          num_heads=HEADS, num_layers=LAYERS, use_flash=False,
                          remat=remat)
    prog = pt.build(gpt.make_model(cfg))
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(3, 64, (BATCH, 16)).astype(np.int32),
            "labels": rng.randint(3, 64, (BATCH, 16)).astype(np.int32)}
    params, state = prog.init(jax.random.PRNGKey(0), **feed)

    def lowered():
        return jax.jit(jax.value_and_grad(
            lambda p: prog.apply(p, state, training=True, **feed)[0]["loss"])
        ).lower(params).as_text()

    now = lowered()
    monkeypatch.setattr(S, "make_encoder_block", _parent_encoder_block)
    assert lowered() == now
    assert "collective_permute" not in now and "shard_map" not in now
