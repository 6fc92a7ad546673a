"""Ulysses all-to-all sequence parallelism vs single-device attention."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel.ulysses import ulysses_attention


def _ref(q, k, v, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sl = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sl, sl), jnp.bool_)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand(b=2, h=8, s=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
                 for _ in range(3))


def test_ulysses_matches_reference():
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand()
    out = ulysses_attention(q, k, v, mesh, causal=False, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_causal():
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand(seed=1)
    out = ulysses_attention(q, k, v, mesh, causal=True, batch_axes=())
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_with_dp():
    mesh = pt.make_mesh({"dp": 2, "sp": 4})
    q, k, v = _rand(b=4, h=4, s=32, seed=2)
    out = ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v, True)),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_gradients():
    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(b=1, h=4, s=32, d=8, seed=3)
    g1 = jax.grad(lambda a: jnp.sum(ulysses_attention(
        a, k, v, mesh, causal=True, batch_axes=()) ** 2))(q)
    g2 = jax.grad(lambda a: jnp.sum(_ref(a, k, v, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=2e-4, rtol=2e-4)


def test_ulysses_head_divisibility_error():
    mesh = pt.make_mesh({"sp": 8})
    q, k, v = _rand(h=4)  # 4 heads, sp=8 → error
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, mesh, batch_axes=())
