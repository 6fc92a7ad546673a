"""Ring attention (sequence parallel) vs single-device reference, on the
8-device CPU mesh — the multi-place in-process fixture pattern."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel.ring_attention import ring_attention


def _ref(q, k, v, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sl = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sl, sl), jnp.bool_)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand(b=2, h=2, s=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
                 for _ in range(3))


def test_ring_matches_reference():
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand()
    out = ring_attention(q, k, v, mesh, causal=False, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_ring_causal_matches_reference():
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand(seed=1)
    out = ring_attention(q, k, v, mesh, causal=True, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_ring_with_dp_batch_sharding():
    mesh = pt.make_mesh({"dp": 2, "sp": 4})
    q, k, v = _rand(b=4, s=32, seed=2)
    out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_ring_gradients():
    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(b=1, h=1, s=32, d=8, seed=3)

    g1 = jax.grad(lambda a: jnp.sum(ring_attention(a, k, v, mesh, causal=True,
                                                   batch_axes=()) ** 2))(q)
    g2 = jax.grad(lambda a: jnp.sum(_ref(a, k, v, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4, rtol=1e-3)

    gk1 = jax.grad(lambda b_: jnp.sum(ring_attention(q, b_, v, mesh, causal=True,
                                                     batch_axes=()) ** 2))(k)
    gk2 = jax.grad(lambda b_: jnp.sum(_ref(q, b_, v, True) ** 2))(k)
    np.testing.assert_allclose(np.asarray(gk1), np.asarray(gk2), atol=1e-4, rtol=1e-3)


def test_zigzag_causal_matches_reference():
    """Default causal schedule is the balanced zigzag; numerics must be
    identical to dense causal attention."""
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand(seed=6)
    out = ring_attention(q, k, v, mesh, causal=True, batch_axes=(),
                         schedule="zigzag")
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_is_default_for_causal():
    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(s=32, seed=7)
    auto = ring_attention(q, k, v, mesh, causal=True, batch_axes=())
    zz = ring_attention(q, k, v, mesh, causal=True, batch_axes=(),
                        schedule="zigzag")
    np.testing.assert_allclose(np.asarray(auto), np.asarray(zz), atol=1e-6)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_with_dp_batch_sharding():
    mesh = pt.make_mesh({"dp": 2, "sp": 4})
    q, k, v = _rand(b=4, s=32, seed=8)
    out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_gradients():
    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(b=1, h=1, s=32, d=8, seed=9)

    for wrt, arrs in (("q", (q,)), ("k", (k,)), ("v", (v,))):
        def f(a):
            qq, kk, vv = (a if wrt == "q" else q, a if wrt == "k" else k,
                          a if wrt == "v" else v)
            return jnp.sum(ring_attention(qq, kk, vv, mesh, causal=True,
                                          batch_axes=(), schedule="zigzag") ** 2)

        def fr(a):
            qq, kk, vv = (a if wrt == "q" else q, a if wrt == "k" else k,
                          a if wrt == "v" else v)
            return jnp.sum(_ref(qq, kk, vv, True) ** 2)

        g1 = jax.grad(f)(arrs[0])
        g2 = jax.grad(fr)(arrs[0])
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-3, err_msg=f"d{wrt}")


def test_zigzag_persistent_layout():
    """layout='zigzag': caller keeps activations in zigzag order across
    the stack — no per-call gathers; output comes back in zigzag order."""
    from paddle_tpu.parallel.ring_attention import zigzag_order

    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(s=32, seed=10)
    order = zigzag_order(32, 4)
    qz, kz, vz = (jnp.take(a, order, axis=2) for a in (q, k, v))
    out_z = ring_attention(qz, kz, vz, mesh, causal=True, batch_axes=(),
                           schedule="zigzag", layout="zigzag")
    ref = jnp.take(_ref(q, k, v, True), order, axis=2)
    np.testing.assert_allclose(np.asarray(out_z), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_bad_schedule_rejected():
    from paddle_tpu.core.errors import EnforceError

    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(s=32, seed=11)
    with pytest.raises(EnforceError):
        ring_attention(q, k, v, mesh, causal=True, schedule="zig-zag")
    with pytest.raises(EnforceError):
        ring_attention(q, k, v, mesh, causal=True, layout="weird")


def test_causal_work_balance():
    """The schedule accounting the zigzag exists for: per-rank FLOP
    balance. Plain ring is maximally skewed (last rank 2n-1 x the
    first); zigzag is flat; both do the same total work."""
    from paddle_tpu.parallel.ring_attention import causal_work_per_rank

    for n in (2, 4, 8, 16):
        ring = causal_work_per_rank(n, "ring")
        zz = causal_work_per_rank(n, "zigzag")
        assert sum(ring) == sum(zz) == 2 * n * n
        assert max(zz) == min(zz), "zigzag must be perfectly balanced"
        assert max(ring) / min(ring) == 2 * n - 1


def test_zigzag_order_roundtrip():
    from paddle_tpu.parallel.ring_attention import zigzag_order

    order = np.asarray(zigzag_order(16, 4))
    assert sorted(order.tolist()) == list(range(16))
    # rank r's shard = blocks (r, 2n-1-r) of the 2n-block split
    assert order[:4].tolist() == [0, 1, 14, 15]
    assert order[4:8].tolist() == [2, 3, 12, 13]


def test_degenerate_single_shard():
    mesh = pt.make_mesh({"dp": 8})  # no sp axis
    q, k, v = _rand(s=16, seed=4)
    out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_ring_inside_jit():
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand(seed=5)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, mesh, causal=False, batch_axes=())

    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
