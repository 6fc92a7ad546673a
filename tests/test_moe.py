"""MoE expert parallelism: EP shard_map path vs dense path on the
8-device CPU mesh (multi-place in-process fixture pattern, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.parallel.moe import moe


def _build(mesh, b=8, s=4, d=16, E=8, ff=32, top_k=2, cf=8.0):
    def fn(x):
        out, aux = moe(x, num_experts=E, d_ff=ff, top_k=top_k,
                       capacity_factor=cf, mesh=mesh)
        return {"out": out, "aux": aux}
    return pt.build(fn)


def _input(b=8, s=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(b, s, d).astype(np.float32)


def test_ep_matches_dense():
    x = _input()
    dense = _build(None)
    params, _ = dense.init(jax.random.PRNGKey(0), x)

    mesh = pt.make_mesh({"ep": 8})
    ep = _build(mesh)
    out_d, _ = dense.apply(params, {}, x)
    out_e, _ = ep.apply(params, {}, x)
    # ample capacity → no drops → EP and dense agree exactly (the combine
    # is order-independent within an expert)
    np.testing.assert_allclose(np.asarray(out_e["out"]), np.asarray(out_d["out"]),
                               atol=1e-5, rtol=1e-5)
    # aux is per-token-group (GShard semantics): the EP value is the mean of
    # per-device group losses, not the global-batch loss — same scale though
    assert np.isfinite(float(out_e["aux"])) and float(out_e["aux"]) >= 1.0 - 1e-5


def test_ep_with_dp_axis():
    x = _input(b=8)
    dense = _build(None)
    params, _ = dense.init(jax.random.PRNGKey(0), x)

    mesh = pt.make_mesh({"dp": 2, "ep": 4})
    ep = _build(mesh)
    out_d, _ = dense.apply(params, {}, x)
    out_e, _ = ep.apply(params, {}, x)
    np.testing.assert_allclose(np.asarray(out_e["out"]), np.asarray(out_d["out"]),
                               atol=1e-5, rtol=1e-5)


def test_ep_gradients_match_dense():
    x = _input()
    dense = _build(None)
    params, _ = dense.init(jax.random.PRNGKey(0), x)
    mesh = pt.make_mesh({"ep": 8})
    ep = _build(mesh)

    # loss over out only: the aux term is group-local by design so its
    # router grads differ between groupings
    def loss(prog):
        def f(p):
            out, _ = prog.apply(p, {}, x)
            return jnp.sum(out["out"] ** 2)
        return f

    gd = jax.grad(loss(dense))(params)
    ge = jax.grad(loss(ep))(params)
    for k in gd:
        np.testing.assert_allclose(np.asarray(ge[k]), np.asarray(gd[k]),
                                   atol=1e-4, rtol=1e-4, err_msg=k)


def test_capacity_drops_tokens():
    # capacity_factor → tiny capacity: some tokens dropped, out stays finite,
    # dropped tokens produce zero output rows
    x = _input(b=4, s=4)
    prog = _build(None, b=4, cf=0.25, top_k=1)
    params, _ = prog.init(jax.random.PRNGKey(0), x)
    out, _ = prog.apply(params, {}, x)
    assert np.all(np.isfinite(np.asarray(out["out"])))


def test_aux_loss_balanced_uniform():
    # uniform router (zero weights) → perfectly balanced → aux ≈ 1.0
    x = _input()
    prog = _build(None)
    params, _ = prog.init(jax.random.PRNGKey(0), x)
    params = dict(params)
    for k in params:
        if k.endswith("router_w"):
            params[k] = jnp.zeros_like(params[k])
    out, _ = prog.apply(params, {}, x)
    np.testing.assert_allclose(float(out["aux"]), 1.0, atol=1e-5)
