"""The scorer's two forms. Kernel ``select_fwd`` (``ops/block_select.py``)
in interpret mode against its oracle, ``layers/sala.select_blocks``, on the
same inputs: the seats a query fills, in order, and how many count. And the
oracle itself against the scorer of the one plain reference,
``benchmarks/reference/minicpm_sala.py``, in float32.

The middle configuration has the published one's mechanisms at a size the
interpreter walks in seconds: 8 heads in groups of 4 over 2 key heads of 32,
kernels of 32 keys every 16, blocks of 64, one first block, a window of 8
blocks, ``topk`` 16 (a query chooses 7), 4,160 keys of context (65 blocks,
260 compressed keys: no multiple of 8 or 128).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:        # benchmarks/ of this checkout
    sys.path.insert(0, ROOT)

from benchmarks.reference import minicpm_sala as reference
from paddle_tpu.layers import sala
from paddle_tpu.ops import block_select as bs

MIDDLE = sala.SparseDims(64, 8, 2, 32, 1e-6, 32, 16, 64, 1, 512, 16, 256)
TOY = sala.SparseDims(64, 4, 2, 16, 1e-6, 32, 16, 64, 1, 128, 6, 256)


def inputs(seed, dims, queries, total, dtype, rows=2):
    """Queries of norm 4 sqrt(d) a head (``q_norm``'s scale in the cell) and
    compressed keys as means of 32 unit keys."""
    rng = np.random.RandomState(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True) * np.sqrt(
        a.shape[-1])
    q = 4.0 * unit(rng.randn(rows, queries, dims.heads, dims.head_dim))
    keys = unit(rng.randn(rows, total + dims.kernel_size, dims.kv_heads,
                          dims.head_dim))
    ck = np.stack([keys[:, dims.kernel_stride * j:dims.kernel_stride * j
                        + dims.kernel_size].mean(1)
                   for j in range(total // dims.kernel_stride)], 1)
    return (jnp.asarray(q, dtype),
            jnp.asarray(ck.reshape(rows, ck.shape[1], -1), dtype))


def both_forms(q, ck, p0, dims):
    """``(kernel's sel, oracle's sel, oracle's block scores, plan)``."""
    b, s = q.shape[:2]
    positions = p0 + jnp.arange(s)
    got, plan = bs.block_select(
        q.reshape(b, s, -1), ck, jnp.int32(p0), group=dims.group,
        head_dim=dims.head_dim, kernel_size=dims.kernel_size,
        stride=dims.kernel_stride, block=dims.block_size,
        init_blocks=dims.init_blocks, window_blocks=dims.window_blocks,
        n_sel=dims.n_sel, scale=dims.scale, interpret=True)
    return (np.asarray(got), np.asarray(sala.select_blocks(q, ck, positions, dims)),
            np.asarray(sala.block_scores(q, ck, positions, dims)), plan)


def assert_same_seats(got, want, scores, group):
    """Equal counts and equal seats, but that two blocks whose oracle scores
    differ by less than float32 rounding of a sum over ``group`` heads may
    stand in each other's seat."""
    assert got.shape == want.shape and got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got[..., -1], want[..., -1])
    differ = np.argwhere(got[..., :-1] != want[..., :-1])
    for at in differ:
        row = scores[tuple(at[:-1])]
        a, b = row[got[tuple(at)]], row[want[tuple(at)]]
        assert abs(a - b) <= 4 * group * np.finfo(np.float32).eps * max(
            abs(a), abs(b)), (at, a, b)
    return len(differ)


# (name, dims, first position, queries, keys of context, dtype, blocks a
# piece, the least and the most seats that count)
CASES = [
    # a prompt's first chunk: queries before the first compressed key, none
    # with a block before its window; it visits one piece of five
    ("first_chunk", MIDDLE, 0, 256, 4160, jnp.bfloat16, 16, 0, 0),
    ("middle_chunk", MIDDLE, 2048, 256, 4160, jnp.bfloat16, 16, 7, 7),
    ("last_chunk", MIDDLE, 3840, 256, 4160, jnp.bfloat16, 16, 7, 7),
    # all of the blocks in one piece, as a short context has them
    ("one_piece", MIDDLE, 3840, 256, 4160, jnp.bfloat16, 128, 7, 7),
    # blocks 1 .. 4 to 1 .. 7 lie before the window: fewer than n_sel
    ("few_free_blocks", MIDDLE, 768, 256, 1088, jnp.bfloat16, 8, 4, 7),
    # the tests' toy dimensions, float32, a tile of one block
    ("toy_float32", TOY, 384, 64, 448, jnp.float32, 128, 3, 3),
    ("toy_whole_prompt", TOY, 0, 448, 448, jnp.float32, 128, 0, 3),
]


@pytest.mark.parametrize("name,dims,p0,queries,total,dtype,piece,least,most",
                         CASES, ids=[c[0] for c in CASES])
def test_kernel_against_select_blocks(monkeypatch, name, dims, p0, queries,
                                      total, dtype, piece, least, most):
    monkeypatch.setattr(bs, "KEY_BLOCKS", piece)
    q, ck = inputs(len(name), dims, queries, total, dtype)
    with jax.default_matmul_precision("highest"):
        got, want, scores, plan = both_forms(q, ck, p0, dims)
    assert_same_seats(got, want, scores, dims.group)
    assert (want[..., -1].min(), want[..., -1].max()) == (least, most)
    assert plan["keys"] == total // dims.kernel_stride
    assert plan["tile"] == max(t for t in (256, 128, 64) if queries % t == 0)


def test_kernel_seats_equal_scores_by_the_lower_index():
    """Compressed keys that repeat every block give every block the same
    kernels, so whole runs of blocks tie exactly; the kernel seats them as
    the oracle does, the lower index first."""
    q, ck = inputs(11, MIDDLE, 128, 4160, jnp.bfloat16)
    per = MIDDLE.block_size // MIDDLE.kernel_stride
    ck = jnp.tile(ck[:, :per], (1, ck.shape[1] // per, 1))
    got, want, scores, _ = both_forms(q, ck, 3968, MIDDLE)
    tied = scores[0, 0, -1, 1:40]
    assert (tied == tied[0]).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[0, 0, -1, :MIDDLE.n_sel],
                                  1 + np.arange(MIDDLE.n_sel))


SHAPE = reference.Shape(
    hidden=64, heads=8, kv_heads=2, head_dim=32, l_heads=4, l_head_dim=16,
    eps=1e-6, theta=10000.0, scale_emb=12, scale_depth=1.4, dim_model_base=16,
    published_layers=32, kernel_size=32, kernel_stride=16, block_size=64,
    init_blocks=1, window_size=512, topk=16, dense_len=256)

# of the blocks a query chooses, the share select_blocks may seat otherwise
# than the reference: in float32 none was (three seeds), and the limit leaves
# room for 3 seats of 7,168 that tie to rounding; under bfloat16 operands
# 0.31-0.40% were (three seeds; PERF.md section 6, PR 33: 0.35% at the
# published shapes), held to 1%
DIFFERENT_SEATS = {jnp.float32: 0.0005, jnp.bfloat16: 0.01}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_select_blocks_against_the_reference_scorer(dtype):
    """The blocks a query reads (forced and chosen) by ``select_blocks`` are
    the reference's, computed in float32 from the same numbers."""
    total, s = 4160, 512
    q, ck = inputs(5, MIDDLE, s, total, jnp.float32, rows=1)
    positions = total - 64 - s + jnp.arange(s)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.selected_blocks(
            q[0], positions, ck[0].reshape(-1, 2, 32), total // 64, SHAPE))
        sel = np.asarray(sala.select_blocks(q.astype(dtype), ck.astype(dtype),
                                            positions, MIDDLE))[0]
    blocks = np.arange(total // 64)
    own = np.asarray(positions) // 64
    forced = ((blocks < 1) | (blocks > (own - 8)[:, None])) & (
        blocks <= own[:, None])
    live = np.arange(MIDDLE.n_sel) < sel[..., -1:]
    chosen = np.zeros((2, s, total // 64), bool)
    for c, i in np.ndindex(2, s):
        chosen[c, i, sel[c, i, :-1][live[c, i]]] = True
    got = forced[None] | chosen
    assert got.sum() == want.sum() == 2 * s * 16
    different = (got & ~want).sum() / (2 * s * MIDDLE.n_sel)
    assert different <= DIFFERENT_SEATS[dtype], different
