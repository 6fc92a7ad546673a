"""``ops/ssd.py``: the chunked kernel (interpret mode) and the one-token form
against the token-by-token scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssd


def _inputs(rows, s, heads, hd, n, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, s, heads)) - 2.0)
    x = jax.random.normal(ks[0], (rows, s, heads * hd), jnp.float32).astype(dtype)
    b = jax.random.normal(ks[2], (rows, s, n), jnp.float32).astype(dtype)
    c = jax.random.normal(ks[3], (rows, s, n), jnp.float32).astype(dtype)
    a = -jnp.exp(jax.random.uniform(ks[4], (heads,), minval=0.0, maxval=2.7))
    state = jax.random.normal(ks[5], (rows, heads // (128 // hd), n, 128))
    return x, dt, b, c, a, state


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want) < tol


# (rows, tokens, heads, head_dim, d_state): several chunks; a ragged last
# chunk; a run shorter than a chunk; four heads a lane group; one
CASES = [(2, 512, 8, 64, 128), (1, 600, 8, 64, 128), (2, 40, 8, 64, 16),
         (1, 300, 16, 32, 32), (1, 256, 4, 128, 16)]


@pytest.mark.parametrize("case", CASES, ids=lambda c: "x".join(map(str, c)))
def test_kernel_against_the_scan(case):
    x, dt, b, c, a, state = _inputs(*case)
    y, new = ssd.ssd(dt, x, b, c, a, state, interpret=True)
    want_y, want = ssd.ssd_scan(dt, x, b, c, a, state)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert new.dtype == jnp.float32 and new.shape == state.shape
    assert _close(y, want_y, 1e-2)
    assert _close(new, want, 1e-4)


def test_state_is_float32_exact_given_its_inputs():
    """The carried state is the scan's to float32 rounding: no bfloat16
    rounding of the decay weights reaches it."""
    x, dt, b, c, a, state = _inputs(1, 512, 8, 64, 128, seed=3)
    _, new = ssd.ssd(dt, x, b, c, a, state, interpret=True)
    _, want = ssd.ssd_scan(dt, x, b, c, a, state)
    assert _close(new, want, 2e-5)
    rounded = jax.lax.reduce_precision(want, 8, 7)          # a bfloat16 state
    assert not _close(rounded, want, 1e-3)


def test_state_handed_from_one_call_to_the_next():
    x, dt, b, c, a, state = _inputs(2, 768, 8, 64, 128, seed=1)
    whole_y, whole = ssd.ssd(dt, x, b, c, a, state, interpret=True)
    cut = 512
    y0, s0 = ssd.ssd(dt[:, :cut], x[:, :cut], b[:, :cut], c[:, :cut], a, state,
                     interpret=True)
    y1, s1 = ssd.ssd(dt[:, cut:], x[:, cut:], b[:, cut:], c[:, cut:], a, s0,
                     interpret=True)
    assert _close(jnp.concatenate([y0, y1], axis=1), whole_y, 1e-2)
    assert _close(s1, whole, 1e-5)


def test_a_step_after_a_run_is_the_longer_run():
    x, dt, b, c, a, state = _inputs(2, 257, 8, 64, 128, seed=2)
    _, before = ssd.ssd(dt[:, :256], x[:, :256], b[:, :256], c[:, :256], a,
                        state, interpret=True)
    y, after = ssd.ssd_step(dt[:, 256], x[:, 256], b[:, 256], c[:, 256], a,
                            before)
    want_y, want = ssd.ssd_scan(dt, x, b, c, a, state)
    assert y.dtype == jnp.float32 and after.dtype == jnp.float32
    assert _close(y, want_y[:, 256], 1e-2)
    assert _close(after, want, 1e-4)


def test_step_against_the_published_layout():
    """One token by the definition, on ``[rows, heads, head_dim, d_state]``
    states in float64."""
    x, dt, b, c, a, state = _inputs(3, 1, 8, 64, 16, seed=4, dtype=jnp.float32)
    y, new = ssd.ssd_step(dt[:, 0], x[:, 0], b[:, 0], c[:, 0], a, state)
    s = np.asarray(ssd.head_states(state, 64), np.float64)
    xs = np.asarray(x[:, 0], np.float64).reshape(3, 8, 64)
    d = np.asarray(dt[:, 0], np.float64)
    s = (np.exp(d * np.asarray(a, np.float64))[:, :, None, None] * s
         + (d[:, :, None] * xs)[..., None] * np.asarray(b[:, 0], np.float64)[:, None, None, :])
    want_y = np.einsum("rhpn,rn->rhp", s, np.asarray(c[:, 0], np.float64))
    assert _close(ssd.head_states(new, 64), s, 1e-6)
    assert _close(np.asarray(y).reshape(3, 8, 64), want_y, 1e-5)


def test_empty_state_and_the_plan_span():
    from paddle_tpu.core import profiler

    state = ssd.empty_state(2, 8, 64, 16)
    assert state.shape == (2, 4, 16, 128) and state.dtype == jnp.float32
    x, dt, b, c, a, _ = _inputs(2, 40, 8, 64, 16)
    import time
    t0 = time.time_ns()
    ssd.ssd(dt, x, b, c, a, state, interpret=True)
    spans = [s for s in profiler.spans(t0) if s[0] == "ssd.plan"]
    assert len(spans) == 1
    f = spans[0][4]
    assert f["rows"] == 2 and f["tokens"] == 40 and f["heads"] == 8
    assert f["state_bytes"] == state.size * 4
