"""Pipeline-parallel schedule tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel.pipeline import pipeline_apply, stack_layer_params


def _layer_fn(x, p):
    return jnp.tanh(x @ p["w"] + p["b"])


def _stacked(layers, d, seed=0):
    rng = np.random.RandomState(seed)
    per_layer = [{"w": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.3),
                  "b": jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)}
                 for _ in range(layers)]
    return stack_layer_params(per_layer)


def _ref(x, stacked):
    def one(a, lp):
        return _layer_fn(a, lp), None
    out, _ = jax.lax.scan(one, x, stacked)
    return out


def test_pipeline_matches_sequential():
    mesh = pt.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    d = 8
    stacked = _stacked(8, d)
    x = jnp.asarray(np.random.RandomState(1).randn(16, d).astype(np.float32))
    out = pipeline_apply(x, stacked, _layer_fn, mesh, microbatches=4, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, stacked)),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_with_dp():
    mesh = pt.make_mesh({"dp": 2, "pp": 4})
    d = 8
    stacked = _stacked(4, d, seed=2)
    x = jnp.asarray(np.random.RandomState(3).randn(8, d).astype(np.float32))
    out = pipeline_apply(x, stacked, _layer_fn, mesh, microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, stacked)),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_degenerate_no_pp_axis():
    mesh = pt.make_mesh({"dp": 8})
    d = 4
    stacked = _stacked(3, d, seed=4)
    x = jnp.asarray(np.random.RandomState(5).randn(6, d).astype(np.float32))
    out = pipeline_apply(x, stacked, _layer_fn, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, stacked)),
                               atol=1e-6)


def test_pipeline_differentiable():
    mesh = pt.make_mesh({"pp": 2}, devices=jax.devices()[:2])
    d = 4
    stacked = _stacked(4, d, seed=6)
    x = jnp.asarray(np.random.RandomState(7).randn(8, d).astype(np.float32))

    g1 = jax.grad(lambda s: jnp.sum(
        pipeline_apply(x, s, _layer_fn, mesh, microbatches=2, batch_axes=()) ** 2))(stacked)
    g2 = jax.grad(lambda s: jnp.sum(_ref(x, s) ** 2))(stacked)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=1e-4, rtol=1e-3)


def test_interleaved_matches_sequential():
    """Megatron virtual-stage schedule (interleave=2): same numerics as
    the sequential scan, bubble ticks halved per bubble_fraction."""
    mesh = pt.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    d = 8
    stacked = _stacked(8, d)  # 8 layers = pp4 × v2 × 1 layer/chunk
    x = jnp.asarray(np.random.RandomState(1).randn(16, d).astype(np.float32))
    out = pipeline_apply(x, stacked, _layer_fn, mesh, microbatches=4,
                         interleave=2, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, stacked)),
                               atol=1e-5, rtol=1e-5)


def test_interleaved_uneven_microbatch_group():
    """m not divisible by pp: the last group is partial but the schedule
    still routes every microbatch through every chunk."""
    mesh = pt.make_mesh({"pp": 2}, devices=jax.devices()[:2])
    d = 8
    stacked = _stacked(8, d, seed=11)  # pp2 × v2 × 2 layers/chunk
    x = jnp.asarray(np.random.RandomState(12).randn(12, d).astype(np.float32))
    out = pipeline_apply(x, stacked, _layer_fn, mesh, microbatches=3,
                         interleave=2, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, stacked)),
                               atol=1e-5, rtol=1e-5)


def test_interleaved_differentiable():
    mesh = pt.make_mesh({"pp": 2}, devices=jax.devices()[:2])
    d = 4
    stacked = _stacked(8, d, seed=6)

    x = jnp.asarray(np.random.RandomState(7).randn(8, d).astype(np.float32))
    g1 = jax.grad(lambda s: jnp.sum(
        pipeline_apply(x, s, _layer_fn, mesh, microbatches=4, interleave=2,
                       batch_axes=()) ** 2))(stacked)
    g2 = jax.grad(lambda s: jnp.sum(_ref(x, s) ** 2))(stacked)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=1e-4, rtol=1e-3)


def test_interleaved_with_dp_and_extras():
    mesh = pt.make_mesh({"dp": 2, "pp": 4})
    d = 8
    stacked = _stacked(8, d, seed=2)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, d).astype(np.float32))
    bias = jnp.asarray(rng.randn(8, d).astype(np.float32))

    def layer_with_extra(a, p, e):
        return jnp.tanh(a @ p["w"] + p["b"]) + 0.1 * e

    out = pipeline_apply(x, stacked, layer_with_extra, mesh, microbatches=2,
                         interleave=2, extras=bias)

    def one(a, lp):
        return layer_with_extra(a, lp, bias), None
    ref, _ = jax.lax.scan(one, x, stacked)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_interleave_perm_roundtrip_and_correctness():
    """param_layout="interleaved": rows pre-permuted by interleave_perm
    give the same result with no in-step re-layout; argsort inverts."""
    from paddle_tpu.parallel.pipeline import interleave_perm

    L, p, v = 8, 4, 2
    perm = interleave_perm(L, p, v)
    assert sorted(perm) == list(range(L))
    # row r·v + c (chunk c of rank r) holds global chunk c·p + r
    Lc = L // (p * v)
    for r in range(p):
        for c in range(v):
            assert perm[(r * v + c) * Lc] == (c * p + r) * Lc
    inv = np.argsort(perm)
    mesh = pt.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    d = 8
    stacked = _stacked(L, d)
    x = jnp.asarray(np.random.RandomState(1).randn(16, d).astype(np.float32))
    pre = jax.tree.map(lambda leaf: leaf[perm], stacked)
    out = pipeline_apply(x, pre, _layer_fn, mesh, microbatches=4,
                         interleave=v, batch_axes=(),
                         param_layout="interleaved")
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(x, stacked)),
                               atol=1e-5, rtol=1e-5)
    # and the inverse permutation restores logical order
    np.testing.assert_array_equal(np.asarray(pre["w"][inv]),
                                  np.asarray(stacked["w"]))


def test_interleaved_layout_step_has_no_param_relayout_collective():
    """round-4 verdict #6 Done-criterion: with the Megatron rest layout
    the compiled interleaved step contains NO all-to-all — the stacked-
    layout step pays one per leaf (re-layout fwd) plus the inverse in
    backward. Activation ppermutes remain in both."""
    from paddle_tpu.parallel.pipeline import interleave_perm
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = pt.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    L, d, v = 8, 8, 2
    stacked = _stacked(L, d)
    x = jnp.asarray(np.random.RandomState(1).randn(16, d).astype(np.float32))

    def hlo(params, layout):
        params = jax.tree.map(
            lambda leaf: jax.device_put(leaf, NamedSharding(mesh, P("pp"))),
            params)

        def loss(s, xv):
            return jnp.sum(pipeline_apply(
                xv, s, _layer_fn, mesh, microbatches=4, interleave=v,
                batch_axes=(), param_layout=layout) ** 2)
        return jax.jit(jax.grad(loss)).lower(params, x).compile().as_text()

    h_inter = hlo(jax.tree.map(
        lambda leaf: leaf[interleave_perm(L, 4, v)], stacked), "interleaved")
    h_stack = hlo(stacked, "stacked")
    assert "all-to-all" not in h_inter, "param re-layout survived"
    assert "collective-permute" in h_inter  # activation ring still there
    assert "all-to-all" in h_stack  # the cost the new layout removes


def test_bubble_fraction_interleave():
    from paddle_tpu.parallel.pipeline import bubble_fraction

    assert bubble_fraction(4, 16) == pytest.approx(3 / 19)
    assert bubble_fraction(4, 16, interleave=4) == pytest.approx(3 / 67)
    # layer-count guard
    mesh = pt.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    stacked = _stacked(4, 4)
    x = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(Exception, match="divisible by pp"):
        pipeline_apply(x, stacked, _layer_fn, mesh, microbatches=2,
                       interleave=2, batch_axes=())


def test_pipeline_3d_dp_tp_pp():
    """dp2 × tp2 × pp2 in one pipeline_apply call: Megatron MLP stage
    (w1 column-sharded, w2 row-sharded, psum over tp) pipelined over
    stacked layers, batch sharded on dp."""
    from jax.sharding import PartitionSpec as P

    mesh = pt.make_mesh({"dp": 2, "tp": 2, "pp": 2})
    d, h = 4, 8
    rng = np.random.RandomState(7)
    per_layer = [{"w1": jnp.asarray(rng.randn(d, h).astype(np.float32) * 0.3),
                  "w2": jnp.asarray(rng.randn(h, d).astype(np.float32) * 0.3)}
                 for _ in range(4)]
    stacked = stack_layer_params(per_layer)

    def mlp_layer(x, p):
        y = jax.nn.relu(x @ p["w1"])              # tp-local columns of h
        return jax.lax.psum(y @ p["w2"], "tp") + x  # Megatron row-parallel

    def mlp_layer_ref(x, p):
        return x + jax.nn.relu(x @ p["w1"]) @ p["w2"]

    x = jnp.asarray(np.random.RandomState(8).randn(8, d).astype(np.float32))

    out = pipeline_apply(
        x, stacked, mlp_layer, mesh, microbatches=2,
        param_specs={"w1": P(None, "tp"), "w2": P("tp")})

    def one(a, lp):
        return mlp_layer_ref(a, lp), None
    ref, _ = jax.lax.scan(one, x, stacked)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
