"""Int8-quantized ring all-reduce (parallel.quantized_collectives) —
EQuARX-inspired compressed collective for bandwidth-limited axes.
Numerics vs exact lax.psum on the 8-device CPU mesh + wire evidence
(the traced hops carry int8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.parallel import quantized_collectives as qc
from paddle_tpu.parallel import quantized_pmean, quantized_psum


def _run(fn, per_rank, mesh_axes={"dp": 8}, jit=True):
    mesh = pt.make_mesh(mesh_axes)
    stacked = jnp.stack(per_rank)  # [p, ...] — one slice per rank
    ring = jax.shard_map(
        lambda s: fn(s[0], "dp"), mesh=mesh,
        in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    # jitted, as the Trainer runs it: an eager shard_map dispatches every
    # hop's every op across the eight devices one at a time (25 s a call
    # against under one second compiled)
    return (jax.jit(ring) if jit else ring)(stacked)


def test_exact_when_quantization_grid_is_stable():
    """With identical per-rank inputs on the int8 grid, every partial
    sum k·v re-quantizes to the same int8 code (scale scales with k),
    so the ring is bit-exact — pins that NO error source exists beyond
    quantization itself (indexing/schedule bugs would break equality)."""
    rng = np.random.RandomState(0)
    v = rng.randint(-127, 128, (24,)).astype(np.float32) / 127.0
    v[::3] = 1.0  # every ring chunk's abs-max is exactly 1.0, so each
    # hop's scale is k·1 and k·(m/127)/scale·127 = m: requantization is
    # integer-exact at every step
    per_rank = [v.copy() for _ in range(8)]
    got = np.asarray(_run(quantized_psum, per_rank)).reshape(8, 24)
    want = 8.0 * v
    for r in range(8):  # every rank holds the identical full sum
        np.testing.assert_allclose(got[r], want, rtol=0, atol=1e-6)


def test_close_to_exact_psum_on_random_data():
    rng = np.random.RandomState(1)
    per_rank = [rng.randn(1000).astype(np.float32) for _ in range(8)]
    got = np.asarray(_run(quantized_psum, per_rank)).reshape(8, 1000)
    want = np.sum(per_rank, axis=0)
    scale = np.abs(want).max()
    for r in range(8):
        err = np.abs(got[r] - want).max() / scale
        assert err < 0.05, err


def test_padding_and_dtype_roundtrip():
    """Sizes not divisible by the ring size pad internally; bf16 in →
    bf16 out."""
    rng = np.random.RandomState(2)
    per_rank = [rng.randn(13).astype(np.float32) for _ in range(8)]
    got = np.asarray(_run(quantized_psum,
                          [p.astype(jnp.bfloat16) for p in per_rank])
                     .astype(np.float32)).reshape(8, 13)
    want = np.sum(per_rank, axis=0)
    assert got.shape[1] == 13
    np.testing.assert_allclose(got[0], want, rtol=0.1, atol=0.1)


def test_pmean_averages():
    per_rank = [np.full((8,), float(r), np.float32) for r in range(8)]
    got = np.asarray(_run(quantized_pmean, per_rank)).reshape(8, 8)
    np.testing.assert_allclose(got[0], np.full(8, 3.5), atol=0.05)


def test_hops_carry_int8_on_the_wire():
    """The point of the component: ppermute payloads in the traced
    program are int8 vectors plus f32 SCALAR scales — no f32 vector
    rides the ring."""
    import re

    mesh = pt.make_mesh({"dp": 8})
    x = jnp.zeros((8, 64), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        lambda s: quantized_psum(s[0], "dp"), mesh=mesh,
        in_specs=P("dp"), out_specs=P("dp"), check_vma=False))(x))
    # output dtype of each ppermute: i8[...] data or f32[] scalar scale
    out_types = re.findall(r"\w+:(\w+\[[\d,]*\]) = ppermute\[", jaxpr)
    assert out_types, jaxpr[:500]
    assert any(t.startswith("i8[") for t in out_types), out_types
    for t in out_types:
        assert t.startswith("i8[") or t == "f32[]", out_types
    # 2(P-1) hops, each one i8 payload + one f32[] scale
    assert len(out_types) == 2 * 7 * 2, out_types


def test_all_ranks_bitwise_identical():
    """The all-reduce contract DP replicas rely on: every rank must end
    with the SAME array, bit for bit — including the chunk each rank
    owns (which must store the quantized roundtrip, not its exact f32).
    Bitwise identity catches both schedule and divergence regressions;
    the jaxpr test above pins the wire structure."""
    rng = np.random.RandomState(4)
    per_rank = [rng.randn(96).astype(np.float32) for _ in range(8)]
    got = np.asarray(_run(quantized_psum, per_rank)).reshape(8, 96)
    for r in range(1, 8):
        np.testing.assert_array_equal(got[r], got[0])


def test_block_scales_ride_the_ring():
    """block_size=B upgrades the per-hop scale from f32[] to a f32
    VECTOR of per-block scales — still tiny next to the i8 payload.
    Pins the traced wire structure without paying a compile."""
    import re

    mesh = pt.make_mesh({"dp": 8})
    x = jnp.zeros((8, 8 * 64), jnp.float32)  # chunk=64 -> 2 blocks of 32
    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        lambda s: quantized_psum(s[0], "dp", block_size=32), mesh=mesh,
        in_specs=P("dp"), out_specs=P("dp"), check_vma=False))(x))
    out_types = re.findall(r"\w+:(\w+\[[\d,]*\]) = ppermute\[", jaxpr)
    assert len(out_types) == 2 * 7 * 2, out_types
    assert any(t.startswith("i8[") for t in out_types), out_types
    for t in out_types:
        assert t.startswith("i8[") or t == "f32[2]", out_types


def test_int4_packs_two_codes_per_byte():
    """bits=4 halves the payload: ppermute data hops are u8[chunk/2]
    (two bias-8 nibbles per byte), scales stay f32 per block."""
    import re

    mesh = pt.make_mesh({"dp": 8})
    x = jnp.zeros((8, 8 * 64), jnp.float32)  # chunk=64 -> u8[32]
    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        lambda s: quantized_psum(s[0], "dp", bits=4, block_size=64),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False))(x))
    out_types = re.findall(r"\w+:(\w+\[[\d,]*\]) = ppermute\[", jaxpr)
    assert len(out_types) == 2 * 7 * 2, out_types
    assert any(t == "u8[32]" for t in out_types), out_types
    for t in out_types:
        assert t in ("u8[32]", "f32[1]"), out_types


def test_zero_and_nonfinite_safe_scales():
    """Satellite regression: an all-zero block must encode EXACTLY to
    zeros (no 0/0 NaN from the abs-max scale), and a block containing a
    non-finite value poisons only ITSELF — the neighboring block's
    values survive bit-exact."""
    x = np.zeros(64, np.float32)
    out = np.asarray(qc.block_roundtrip(jnp.asarray(x), block_size=32))
    np.testing.assert_array_equal(out, x)  # zeros stay exact zeros

    y = np.linspace(-1, 1, 64).astype(np.float32)
    ref = np.asarray(qc.block_roundtrip(jnp.asarray(y), block_size=32))
    assert np.isfinite(ref).all()
    for bad in (np.nan, np.inf):
        z = y.copy()
        z[3] = bad  # poisons block 0 only
        out = np.asarray(qc.block_roundtrip(jnp.asarray(z), block_size=32))
        assert not np.isfinite(out[:32]).all(), out[:32]
        # block 1 is untouched: bit-identical to the clean roundtrip
        np.testing.assert_array_equal(out[32:], ref[32:])


def test_wire_codec_matches_device_roundtrip():
    """The numpy host codec (encode_wire_blocks/decode_wire_blocks —
    the PUSHQB payload) must dequantize to EXACTLY what the in-graph
    block_roundtrip produces: the pserver's view of a gradient equals
    the trainer's own quantized view."""
    rng = np.random.RandomState(7)
    g = (rng.randn(700) * 3).astype(np.float32)  # not a block multiple
    for bits in (8, 4):
        payload, scales = qc.encode_wire_blocks(g, bits=bits,
                                                block_size=128)
        pb, sb = qc.wire_block_bytes(g.size, bits=bits, block_size=128)
        assert (len(payload), len(scales.tobytes())) == (pb, sb)
        host = qc.decode_wire_blocks(payload, scales, g.size, bits=bits,
                                     block_size=128)
        dev = np.asarray(qc.block_roundtrip(jnp.asarray(g), bits=bits,
                                            block_size=128))
        np.testing.assert_array_equal(host, dev)


def test_ring_wire_bytes_attribution():
    """The collective-bytes accounting the acceptance gate reads: int8
    block-256 cuts ring bytes >= 3.5x vs the fp32 baseline; int4 cuts
    deeper than int8."""
    n, p = 199_210, 8  # the MNIST MLP grad size the bench row uses
    fp32 = qc.ring_wire_bytes(n, p)
    assert fp32 == 2 * (p - 1) * -(-n // p) * 4
    i8 = qc.ring_wire_bytes(n, p, bits=8, block_size=256)
    i4 = qc.ring_wire_bytes(n, p, bits=4, block_size=256)
    assert fp32 / i8 >= 3.5, fp32 / i8
    assert i4 < i8 < fp32


def test_stochastic_rounding_deterministic_and_unbiased():
    """rng=key makes the roundtrip stochastic-rounding: reproducible
    under the same key, and E[deq] ~ x (the bias of round-to-nearest
    vanishes in expectation — what error feedback relies on)."""
    x = jnp.full((64,), 0.3, jnp.float32)  # 0.3*127/1.27... off-grid
    x = x.at[::16].set(1.27)  # pin each block's abs-max on the grid
    k = jax.random.PRNGKey(3)
    a = np.asarray(qc.block_roundtrip(x, block_size=16, rng=k))
    b = np.asarray(qc.block_roundtrip(x, block_size=16, rng=k))
    np.testing.assert_array_equal(a, b)  # same key -> same draw
    det = np.asarray(qc.block_roundtrip(x, block_size=16))
    outs = np.stack([np.asarray(qc.block_roundtrip(
        x, block_size=16, rng=jax.random.fold_in(k, i)))
        for i in range(64)])
    assert (outs.std(axis=0) > 0).any()  # actually stochastic
    mean_err = abs(outs.mean() - 0.3 * 60 / 64 - 1.27 * 4 / 64)
    det_err = abs(det.mean() - 0.3 * 60 / 64 - 1.27 * 4 / 64)
    assert mean_err <= det_err + 1e-4, (mean_err, det_err)


def test_block_scaled_ring_numerics():
    """Block scales localize the quantization grid: per-rank random
    data with a large outlier still reduces close to exact psum, and
    every rank stays bitwise identical (same contract as per-chunk)."""
    rng = np.random.RandomState(11)
    per_rank = [rng.randn(512).astype(np.float32) for _ in range(8)]
    per_rank[0][17] = 80.0  # outlier wrecks a PER-CHUNK grid
    got = np.asarray(_run(lambda v, ax: quantized_psum(
        v, ax, block_size=64), per_rank)).reshape(8, 512)
    want = np.sum(per_rank, axis=0)
    err = np.abs(got[0] - want)
    err[17] = 0.0  # the outlier's own block absorbs its coarse grid
    assert np.median(np.abs(got[0] - want)) < 0.05
    for r in range(1, 8):
        np.testing.assert_array_equal(got[r], got[0])


@pytest.mark.slow  # 70 s under -n 6 (50 s alone): the one eager ring left
def test_int4_ring_close_to_exact():
    """bits=4 is coarse (qmax=7) but must still track the exact psum
    within its grid and keep cross-rank bitwise identity. Run eagerly:
    compiled by XLA:CPU the int4 ring's ranks differ in the last one or
    two ulp (the int8 ring's do not), which ROADMAP D3 carries."""
    rng = np.random.RandomState(12)
    per_rank = [rng.randn(256).astype(np.float32) for _ in range(8)]
    got = np.asarray(_run(lambda v, ax: quantized_psum(
        v, ax, bits=4, block_size=64), per_rank, jit=False)).reshape(8, 256)
    want = np.sum(per_rank, axis=0)
    scale = np.abs(want).max()
    assert np.abs(got[0] - want).max() / scale < 0.35
    for r in range(1, 8):
        np.testing.assert_array_equal(got[r], got[0])


def test_degenerate_single_rank():
    x = jnp.arange(5, dtype=jnp.float32)
    # p==1 on an axis of size 1: identity
    mesh1 = pt.make_mesh({"one": 1, "dp": 8})
    out = jax.shard_map(lambda v: quantized_psum(v, "one"), mesh=mesh1,
                        in_specs=P(), out_specs=P(), check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
