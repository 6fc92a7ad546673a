"""Tests for the layer-parity batch: vision/misc ops, sequence conv
family, RNN units, RoI/RPN detection family, control-flow classes,
layers.io surface — each against a numpy brute-force reference
(SURVEY §4 op_test pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import metrics as M

from test_layers import run_layer


# ---------------------------------------------------------------------------
# misc nn ops
# ---------------------------------------------------------------------------


def test_affine_channel():
    x = np.random.randn(2, 3, 4, 5).astype(np.float32)
    s = np.random.randn(3).astype(np.float32)
    b = np.random.randn(3).astype(np.float32)
    out = L.affine_channel(jnp.asarray(x), jnp.asarray(s), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), x * s[None, :, None, None] + b[None, :, None, None], rtol=1e-6)


def test_affine_grid_identity_sampling():
    # identity theta -> grid_sampler reproduces the input
    x = np.random.randn(2, 3, 5, 7).astype(np.float32)
    theta = np.tile(np.array([[1.0, 0, 0], [0, 1.0, 0]], np.float32), (2, 1, 1))
    grid = L.affine_grid(jnp.asarray(theta), (2, 3, 5, 7))
    out = L.grid_sampler(jnp.asarray(x), grid)
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5, atol=1e-5)


def test_crop():
    x = np.arange(24).reshape(2, 3, 4).astype(np.float32)
    out = L.crop(jnp.asarray(x), shape=(1, 2, 2), offsets=(1, 0, 1))
    np.testing.assert_allclose(np.asarray(out), x[1:2, 0:2, 1:3])


def test_random_crop_shape_and_content():
    x = np.arange(100).reshape(1, 10, 10).astype(np.float32)
    out = np.asarray(L.random_crop(jnp.asarray(x), (4, 4), seed=3))
    assert out.shape == (1, 4, 4)
    # rows must be contiguous slices of the original
    flat = set(x.reshape(-1).tolist())
    assert set(out.reshape(-1).tolist()) <= flat


def test_dice_loss_matches_numpy():
    probs = np.random.rand(4, 3).astype(np.float32)
    probs /= probs.sum(-1, keepdims=True)
    label = np.random.randint(0, 3, (4, 1))
    out = float(L.dice_loss(jnp.asarray(probs), jnp.asarray(label), epsilon=1e-5))
    oh = np.eye(3, dtype=np.float32)[label[:, 0]]
    inse = (probs * oh).sum(1)
    ref = np.mean(1 - 2 * inse / ((probs.sum(1) + oh.sum(1)) + 1e-5))
    np.testing.assert_allclose(out, ref, rtol=1e-5)


def test_mean_iou():
    pred = np.array([0, 1, 1, 2, 2, 2])
    lab = np.array([0, 1, 2, 2, 2, 1])
    miou, wrong, correct = L.mean_iou(jnp.asarray(pred), jnp.asarray(lab), 3)
    # class0: i=1 u=1; class1: i=1 u=3; class2: i=2 u=4
    np.testing.assert_allclose(float(miou), (1 + 1 / 3 + 0.5) / 3, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(correct), [1, 1, 2])


def test_hash_deterministic_in_range():
    ids = np.random.randint(0, 1000, (6, 3)).astype(np.int64)
    h1 = np.asarray(L.hash(jnp.asarray(ids), hash_size=97, num_hash=4))
    h2 = np.asarray(L.hash(jnp.asarray(ids), hash_size=97, num_hash=4))
    assert h1.shape == (6, 4)
    np.testing.assert_array_equal(h1, h2)
    assert h1.min() >= 0 and h1.max() < 97
    # different seeds give different hashes somewhere
    assert (h1[:, 0] != h1[:, 1]).any()


def test_add_position_encoding():
    x = np.zeros((1, 4, 6), np.float32)
    out = np.asarray(L.add_position_encoding(jnp.asarray(x), alpha=1.0, beta=1.0))
    # position 0: sin(0)=0, cos(0)=1
    np.testing.assert_allclose(out[0, 0, :3], 0.0, atol=1e-6)
    np.testing.assert_allclose(out[0, 0, 3:], 1.0, atol=1e-6)


def test_multiplex():
    a = np.random.randn(4, 3).astype(np.float32)
    b = np.random.randn(4, 3).astype(np.float32)
    idx = np.array([[0], [1], [1], [0]])
    out = np.asarray(L.multiplex([jnp.asarray(a), jnp.asarray(b)], jnp.asarray(idx)))
    ref = np.stack([a[0], b[1], b[2], a[3]])
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_pool3d_max_and_avg():
    x = np.random.randn(1, 2, 4, 4, 4).astype(np.float32)
    out = np.asarray(L.pool3d(jnp.asarray(x), pool_size=2, pool_type="max", pool_stride=2))
    ref = x.reshape(1, 2, 2, 2, 2, 2, 2, 2).max((3, 5, 7))
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    out_a = np.asarray(L.pool3d(jnp.asarray(x), pool_size=2, pool_type="avg", pool_stride=2))
    ref_a = x.reshape(1, 2, 2, 2, 2, 2, 2, 2).mean((3, 5, 7))
    np.testing.assert_allclose(out_a, ref_a, rtol=1e-5)


def test_conv3d_transpose_shape_and_grad():
    x = np.random.randn(1, 2, 3, 3, 3).astype(np.float32)
    out, params = run_layer(L.conv3d_transpose, x, num_filters=4, filter_size=2, stride=2)
    assert out.shape == (1, 4, 6, 6, 6)


def test_im2sequence():
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    vals, lengths = L.im2sequence(jnp.asarray(x), filter_size=2, stride=2)
    assert vals.shape == (4, 4)
    np.testing.assert_array_equal(np.asarray(lengths), [4])
    np.testing.assert_allclose(np.asarray(vals)[0], [0, 1, 4, 5])


def test_row_conv_matches_numpy():
    b, t, d, k = 2, 5, 3, 2
    x = np.random.randn(b, t, d).astype(np.float32)
    lengths = np.array([5, 3])
    out, params = run_layer(L.row_conv, x, future_context_size=k,
                            lengths=jnp.asarray(lengths))
    w = np.asarray(params["row_conv_0/w"])
    ref = np.zeros_like(x)
    xm = x.copy()
    xm[1, 3:] = 0
    for bb in range(b):
        for tt in range(t):
            for i in range(k + 1):
                if tt + i < t:
                    ref[bb, tt] += xm[bb, tt + i] * w[i]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_image_resize_short():
    x = np.random.randn(1, 3, 8, 16).astype(np.float32)
    out = L.image_resize_short(jnp.asarray(x), 4)
    assert out.shape == (1, 3, 4, 8)


def test_gaussian_random_batch_size_like():
    x = np.zeros((7, 2), np.float32)
    prog = pt.build(lambda a: L.gaussian_random_batch_size_like(a, [-1, 5]))
    params, state = prog.init(jax.random.PRNGKey(0), x)
    out, _ = prog.apply(params, state, x, rng=jax.random.PRNGKey(1))
    assert out.shape == (7, 5)


# ---------------------------------------------------------------------------
# sequence family
# ---------------------------------------------------------------------------


def test_sequence_conv_matches_bruteforce():
    # two sequences of lengths 3 and 2 packed into 5 rows
    vals = np.random.randn(5, 4).astype(np.float32)
    seg = np.array([0, 0, 0, 1, 1], np.int32)
    out, params = run_layer(
        lambda v: L.sequence_conv(v, jnp.asarray(seg), num_filters=6, filter_size=3,
                                  bias_attr=False), vals)
    w = np.asarray(params["sequence_conv_0/w"])  # [3*4, 6]
    ref = np.zeros((5, 6), np.float32)
    seqs = [(0, 3), (3, 5)]
    for start, end in seqs:
        for t in range(start, end):
            ctx = []
            for off in (-1, 0, 1):
                s = t + off
                ctx.append(vals[s] if start <= s < end else np.zeros(4, np.float32))
            ref[t] = np.concatenate(ctx) @ w
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_sequence_expand_as_and_reshape_and_scatter():
    x = np.array([[1.0], [2.0]], np.float32)
    out = L.sequence_expand_as(jnp.asarray(x), jnp.asarray([2, 3]), 5)
    np.testing.assert_allclose(np.asarray(out)[:, 0], [1, 1, 2, 2, 2])

    vals = np.arange(12, dtype=np.float32).reshape(3, 4)
    out2, lens2 = L.sequence_reshape(jnp.asarray(vals), jnp.asarray([1, 2]), 2)
    assert out2.shape == (6, 2)
    np.testing.assert_array_equal(np.asarray(lens2), [2, 4])

    x3 = np.zeros((2, 5), np.float32)
    ids = np.array([0, 2, 1], np.int32)
    seg = np.array([0, 0, 1], np.int32)
    upd = np.array([1.0, 2.0, 3.0], np.float32)
    out3 = L.sequence_scatter(jnp.asarray(x3), ids, seg, jnp.asarray(upd))
    ref3 = np.zeros((2, 5), np.float32)
    ref3[0, 0], ref3[0, 2], ref3[1, 1] = 1, 2, 3
    np.testing.assert_allclose(np.asarray(out3), ref3)


def test_lod_reset_and_reorder_by_rank():
    x = np.random.randn(6, 2).astype(np.float32)
    _, seg = L.lod_reset(jnp.asarray(x), [2, 4])
    np.testing.assert_array_equal(np.asarray(seg), [0, 0, 1, 1, 1, 1])

    padded = np.random.randn(3, 4, 2).astype(np.float32)
    lengths = np.array([2, 4, 3])
    p2, l2, perm = L.reorder_lod_tensor_by_rank(jnp.asarray(padded), jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(l2), [4, 3, 2])
    np.testing.assert_allclose(np.asarray(p2[0]), padded[1])
    inv = np.argsort(np.asarray(perm))
    np.testing.assert_allclose(np.asarray(p2)[inv], padded)


# ---------------------------------------------------------------------------
# rnn units
# ---------------------------------------------------------------------------


def test_lstm_unit_and_gru_unit():
    x = np.random.randn(3, 4).astype(np.float32)
    h = np.random.randn(3, 5).astype(np.float32)
    c = np.random.randn(3, 5).astype(np.float32)
    prog = pt.build(lambda a, hh, cc: L.lstm_unit(a, hh, cc))
    params, state = prog.init(jax.random.PRNGKey(0), x, h, c)
    (h2, c2), _ = prog.apply(params, state, x, h, c)
    assert h2.shape == (3, 5) and c2.shape == (3, 5)
    assert np.isfinite(np.asarray(h2)).all()

    xg = np.random.randn(3, 15).astype(np.float32)  # gru_unit takes projected input 3*dim
    hg = np.random.randn(3, 5).astype(np.float32)
    prog2 = pt.build(lambda a, hh: L.gru_unit(a, hh, 15))
    params2, state2 = prog2.init(jax.random.PRNGKey(0), xg, hg)
    (nh, rhp, gate), _ = prog2.apply(params2, state2, xg, hg)
    assert nh.shape == (3, 5) and rhp.shape == (3, 5) and gate.shape == (3, 15)


def test_dynamic_lstmp_shapes_and_masking():
    x = np.random.randn(2, 6, 3).astype(np.float32)
    lengths = np.array([6, 4])
    prog = pt.build(lambda a: L.dynamic_lstmp(a, size=8, proj_size=4,
                                              sequence_length=jnp.asarray(lengths)))
    params, state = prog.init(jax.random.PRNGKey(0), x)
    (outs, (r_last, c_last)), _ = prog.apply(params, state, x)
    assert outs.shape == (2, 6, 4)
    # state frozen past sequence end for row 1
    np.testing.assert_allclose(np.asarray(outs[1, 3]), np.asarray(r_last[1]), rtol=1e-5)


# ---------------------------------------------------------------------------
# tensor / lr helpers
# ---------------------------------------------------------------------------


def test_create_global_var_and_step_counter():
    def f(x):
        g = L.create_global_var([1], 3.0)
        step = L.autoincreased_step_counter()
        return x + g, step

    prog = pt.build(f)
    x = np.zeros((1,), np.float32)
    params, state = prog.init(jax.random.PRNGKey(0), x)
    (out, step), new_state = prog.apply(params, state, x)
    assert float(out[0]) == 3.0
    assert int(step[0]) == 1
    (out2, step2), new_state2 = prog.apply(params, new_state, x)
    assert int(step2[0]) == 2


def test_sums():
    xs = [np.random.randn(3).astype(np.float32) for _ in range(3)]
    out = L.sums([jnp.asarray(x) for x in xs])
    np.testing.assert_allclose(np.asarray(out), sum(xs), rtol=1e-6)


def test_append_LARS():
    from paddle_tpu import lr_scheduler as lrs
    p = jnp.ones((4,)) * 2.0
    g = jnp.ones((4,)) * 0.5
    (lr,) = lrs.append_LARS([(p, g)], 0.1, weight_decay=0.0)
    np.testing.assert_allclose(float(lr), 0.1 * 4.0 / 1.0, rtol=1e-5)


def test_auc_layer_streams_state():
    preds = np.array([[0.9, 0.1], [0.2, 0.8], [0.3, 0.7], [0.6, 0.4]], np.float32)
    labels = np.array([0, 1, 1, 0])

    prog = pt.build(lambda p, l: M.auc(p, l, num_thresholds=200))
    params, state = prog.init(jax.random.PRNGKey(0), preds, labels)
    (auc_v, batch_auc), new_state = prog.apply(params, state, preds, labels)
    # perfectly separable -> AUC 1.0 (endpoint-anchored sweep is exact here)
    np.testing.assert_allclose(float(auc_v), 1.0, atol=1e-5)
    # feed a second, inverted batch: accumulated auc drops, state advanced
    (auc_v2, _), _ = prog.apply(params, new_state, preds, 1 - labels)
    assert float(auc_v2) < 0.8


# ---------------------------------------------------------------------------
# beam_search_decode
# ---------------------------------------------------------------------------


def test_beam_search_decode_backtracks():
    # T=3, B=1, K=2.  parents[t][k] = lane at t-1 that token (t,k) extended.
    # lane0 path: 9 <- lane1@t1 (8) <- lane0@t0 (5)
    ids = np.array([[[5, 6]], [[7, 8]], [[9, 4]]], np.int32)      # [T,1,2]
    parents = np.array([[[0, 0]], [[0, 0]], [[1, 0]]], np.int32)
    seqs, valid = L.beam_search_decode(ids, parents, end_id=8)
    seqs, valid = np.asarray(seqs), np.asarray(valid)
    assert seqs.shape == (1, 2, 3)
    np.testing.assert_array_equal(seqs[0, 0], [5, 8, 9])  # backtracked through lane1
    np.testing.assert_array_equal(seqs[0, 1], [5, 7, 4])
    # valid covers tokens up to and including the first end_id
    np.testing.assert_array_equal(valid[0, 0], [True, True, False])
    np.testing.assert_array_equal(valid[0, 1], [True, True, True])


# ---------------------------------------------------------------------------
# detection: RoI / RPN family
# ---------------------------------------------------------------------------


def test_roi_pool_bruteforce():
    x = np.random.randn(1, 2, 8, 8).astype(np.float32)
    rois = np.array([[0, 0, 3, 3], [2, 2, 7, 7]], np.float32)
    bidx = np.array([0, 0])
    out = np.asarray(L.roi_pool(jnp.asarray(x), jnp.asarray(rois), jnp.asarray(bidx),
                                pooled_height=2, pooled_width=2, spatial_scale=1.0))
    assert out.shape == (2, 2, 2, 2)
    # roi0 spans rows/cols 0..3 -> bins are 2x2 blocks
    ref00 = x[0, :, 0:2, 0:2].max((1, 2))
    np.testing.assert_allclose(out[0, :, 0, 0], ref00, rtol=1e-5)
    ref11 = x[0, :, 2:4, 2:4].max((1, 2))
    np.testing.assert_allclose(out[0, :, 1, 1], ref11, rtol=1e-5)


def test_roi_align_constant_map():
    x = np.full((1, 3, 6, 6), 2.5, np.float32)
    rois = np.array([[1.0, 1.0, 4.0, 4.0]], np.float32)
    out = np.asarray(L.roi_align(jnp.asarray(x), jnp.asarray(rois), jnp.asarray([0]),
                                 pooled_height=2, pooled_width=2))
    np.testing.assert_allclose(out, 2.5, rtol=1e-5)


def test_anchor_generator():
    x = np.zeros((1, 8, 4, 6), np.float32)
    anchors, variances = L.anchor_generator(jnp.asarray(x), anchor_sizes=[64, 128],
                                            aspect_ratios=[0.5, 1.0], stride=[16, 16])
    assert anchors.shape == (4, 6, 4, 4)
    assert variances.shape == (4, 6, 4, 4)
    a = np.asarray(anchors)
    # centers advance by stride along w
    np.testing.assert_allclose(a[0, 1, 0, 0] - a[0, 0, 0, 0], 16.0, rtol=1e-5)
    # aspect 1.0 anchors are square
    widths = a[..., 2] - a[..., 0]
    heights = a[..., 3] - a[..., 1]
    np.testing.assert_allclose(widths[0, 0, 2:], heights[0, 0, 2:], rtol=1e-4)


def test_generate_proposals():
    np.random.seed(1)
    h = w = 4
    a = 2
    scores = np.random.rand(1, a, h, w).astype(np.float32)
    deltas = (np.random.randn(1, 4 * a, h, w) * 0.1).astype(np.float32)
    im_info = np.array([[64.0, 64.0, 1.0]], np.float32)
    x = np.zeros((1, 8, h, w), np.float32)
    anchors, variances = L.anchor_generator(jnp.asarray(x), anchor_sizes=[16, 32],
                                            aspect_ratios=[1.0], stride=[16, 16])
    rois, probs, valid = L.generate_proposals(
        jnp.asarray(scores), jnp.asarray(deltas), jnp.asarray(im_info),
        anchors, variances, pre_nms_top_n=20, post_nms_top_n=5, nms_thresh=0.7)
    assert rois.shape == (1, 5, 4)
    r = np.asarray(rois)[np.asarray(valid)]
    assert (r[:, 0] >= 0).all() and (r[:, 2] <= 63).all()
    assert (r[:, 2] >= r[:, 0]).all() and (r[:, 3] >= r[:, 1]).all()


def test_rpn_target_assign_caps_and_labels():
    x = np.zeros((1, 8, 4, 4), np.float32)
    anchors, _ = L.anchor_generator(jnp.asarray(x), anchor_sizes=[32],
                                    aspect_ratios=[1.0], stride=[16, 16])
    anchors = anchors.reshape(-1, 4)
    gt = np.array([[[8.0, 8.0, 40.0, 40.0]]], np.float32)
    gtv = np.array([[True]])
    im_info = np.array([[64.0, 64.0, 1.0]], np.float32)
    labels, tgt, fg, bg = L.rpn_target_assign(
        anchors, jnp.asarray(gt), jnp.asarray(gtv), jnp.asarray(im_info),
        rpn_batch_size_per_im=8, rng_key=jax.random.PRNGKey(0))
    labels = np.asarray(labels)[0]
    assert (np.asarray(fg)[0].sum() + np.asarray(bg)[0].sum()) <= 8
    assert (labels == 1).sum() >= 1  # best anchor for the gt is fg
    assert set(np.unique(labels)) <= {-1, 0, 1}


def test_generate_proposal_labels():
    rois = np.array([[[8, 8, 40, 40], [0, 0, 10, 10], [50, 50, 60, 60]]], np.float32)
    rv = np.array([[True, True, True]])
    gcls = np.array([[3]], np.int32)
    gbox = np.array([[[10, 10, 38, 38]]], np.float32)
    gv = np.array([[True]])
    labels, tgt, fg, sampled = L.generate_proposal_labels(
        jnp.asarray(rois), jnp.asarray(rv), jnp.asarray(gcls), jnp.asarray(gbox),
        jnp.asarray(gv), batch_size_per_im=3, fg_fraction=0.5,
        rng_key=jax.random.PRNGKey(0))
    labels = np.asarray(labels)[0]
    assert labels[0] == 3          # high-IoU roi gets the gt class
    assert (labels[1:] <= 0).all()  # others are bg or unsampled


def test_target_assign():
    x = np.random.randn(2, 3, 4).astype(np.float32)
    mi = np.array([[0, -1], [2, 1]], np.int32)
    out, wt = L.target_assign(jnp.asarray(x), jnp.asarray(mi), mismatch_value=9.0)
    np.testing.assert_allclose(np.asarray(out[0, 0]), x[0, 0], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out[0, 1]), 9.0)
    np.testing.assert_allclose(np.asarray(out[1, 0]), x[1, 2], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(wt[:, :, 0]), [[1, 0], [1, 1]])


def test_polygon_box_transform():
    x = np.random.randn(1, 4, 3, 5).astype(np.float32)
    out = np.asarray(L.polygon_box_transform(jnp.asarray(x)))
    wi, hi = np.meshgrid(np.arange(5), np.arange(3))
    for g in range(4):
        ref = (4.0 * wi - x[0, g]) if g % 2 == 0 else (4.0 * hi - x[0, g])
        np.testing.assert_allclose(out[0, g], ref, rtol=1e-5)


def test_roi_perspective_transform_axis_aligned():
    # an axis-aligned quad == plain resize-crop of that rect
    x = np.random.randn(1, 1, 8, 8).astype(np.float32)
    quad = np.array([[1, 1, 4, 1, 4, 4, 1, 4]], np.float32)  # corners cw
    out = np.asarray(L.roi_perspective_transform(
        jnp.asarray(x), jnp.asarray(quad), jnp.asarray([0]), 4, 4))
    assert out.shape == (1, 1, 4, 4)
    np.testing.assert_allclose(out[0, 0, 0, 0], x[0, 0, 1, 1], rtol=1e-4)
    np.testing.assert_allclose(out[0, 0, 3, 3], x[0, 0, 4, 4], rtol=1e-4)


def test_detection_output():
    priors = np.array([[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]], np.float32)
    pvar = np.full((2, 4), 0.1, np.float32)
    loc = np.zeros((1, 2, 4), np.float32)
    scores = np.array([[[0.1, 0.9], [0.8, 0.2]]], np.float32)
    out, valid = L.detection_output(jnp.asarray(loc), jnp.asarray(scores),
                                    jnp.asarray(priors), jnp.asarray(pvar),
                                    keep_top_k=3)
    out = np.asarray(out)
    valid = np.asarray(valid)
    # both class-1 detections survive (background suppressed)
    assert valid[0].sum() == 2
    best = out[0, 0]
    assert best[0] == 1.0  # class label
    np.testing.assert_allclose(best[1], 0.9, rtol=1e-5)
    np.testing.assert_allclose(best[2:], priors[0], atol=1e-4)


def test_multi_box_head_shapes():
    f1 = np.random.randn(2, 8, 4, 4).astype(np.float32)
    f2 = np.random.randn(2, 8, 2, 2).astype(np.float32)
    img = np.zeros((2, 3, 64, 64), np.float32)

    prog = pt.build(lambda a, b, im: L.detection.multi_box_head(
        [a, b], im, base_size=64, num_classes=4,
        aspect_ratios=[[2.0], [2.0]], min_sizes=[10.0, 30.0], max_sizes=[20.0, 60.0]))
    params, state = prog.init(jax.random.PRNGKey(0), f1, f2, img)
    (locs, confs, boxes, variances), _ = prog.apply(params, state, f1, f2, img)
    total = boxes.shape[0]
    assert locs.shape == (2, total, 4)
    assert confs.shape == (2, total, 4)
    assert variances.shape == (total, 4)


def test_detection_map_function():
    dets = [[(0, 0.9, 0, 0, 10, 10)]]
    gt_label = [[0]]
    gt_box = [[(0, 0, 10, 10)]]
    mAP = L.detection_map(dets, gt_label, gt_box, class_num=1)
    np.testing.assert_allclose(mAP, 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# control-flow classes + io surface
# ---------------------------------------------------------------------------


def test_while_class():
    out = L.While(lambda v: v[0] < 5)(lambda v: (v[0] + 1, v[1] * 2.0), (0, 1.0))
    assert out[0] == 5 and float(out[1]) == 32.0


def test_ifelse_rowwise():
    x = np.array([[1.0], [2.0], [3.0]], np.float32)
    cond = np.array([True, False, True])
    out = L.IfElse(cond)(lambda a: a * 10, lambda a: a - 1, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out)[:, 0], [10, 1, 30])


def test_switch_class():
    lr = L.Switch().case(jnp.asarray(False), lambda: jnp.float32(0.1)) \
                   .case(jnp.asarray(True), lambda: jnp.float32(0.2)) \
                   .default(lambda: jnp.float32(0.3))()
    np.testing.assert_allclose(float(lr), 0.2)


def test_static_and_dynamic_rnn_classes():
    x = np.random.randn(2, 4, 3).astype(np.float32)

    def cell(state, x_t):
        new = state + x_t.sum(-1)
        return new, new

    outs, last = L.StaticRNN()(cell, jnp.asarray(x), jnp.zeros((2,)))
    assert outs.shape == (2, 4)
    np.testing.assert_allclose(np.asarray(last), x.sum((1, 2)), rtol=1e-5)

    outs2, last2 = L.DynamicRNN()(cell, jnp.asarray(x), jnp.zeros((2,)),
                                  sequence_length=jnp.asarray([4, 2]))
    np.testing.assert_allclose(np.asarray(last2)[1], x[1, :2].sum(), rtol=1e-5)


def test_layers_io_surface():
    def reader():
        for i in range(10):
            yield (np.full((2,), i, np.float32),)

    b = L.batch(reader, 4)
    batches = list(b())
    assert len(batches) == 3 and len(batches[0]) == 4

    s = L.shuffle(reader, buffer_size=10)
    assert len(list(s())) == 10

    first = L.read_file(reader)
    np.testing.assert_allclose(first[0], 0.0)

    r = L.random_data_generator(0.0, 1.0, shapes=[(2, 3)])
    sample = L.read_file(r)
    assert sample[0].shape == (2, 3)

    pre = L.Preprocessor(reader)(lambda t: (t[0] * 2,))
    np.testing.assert_allclose(L.read_file(pre)[0], 0.0)

    pr = L.py_reader(capacity=4, shapes=[(2,)], dtypes=["float32"],
                     use_double_buffer=False)
    pr.decorate_paddle_reader(reader)
    got = list(pr.start())
    assert len(got) == 10

    ph = L.data("x", shape=[3, 4], dtype="float32")
    assert tuple(ph.shape) == (1, 3, 4)


# ---------------------------------------------------------------------------
# FD grad checks for new ops (op_test.py check_grad pattern)
# ---------------------------------------------------------------------------

from op_test import check_grad


def test_grad_roi_align():
    x = np.random.randn(1, 2, 6, 6).astype(np.float32)
    rois = np.array([[1.0, 1.0, 4.0, 4.0]], np.float32)
    check_grad(lambda im: L.roi_align(im, jnp.asarray(rois), jnp.asarray([0]), 2, 2),
               [x])


def test_grad_roi_pool():
    x = np.random.randn(1, 2, 6, 6).astype(np.float32)
    rois = np.array([[0.0, 0.0, 3.0, 3.0]], np.float32)
    check_grad(lambda im: L.roi_pool(im, jnp.asarray(rois), jnp.asarray([0]), 2, 2),
               [x])


def test_grad_row_conv_weights():
    # FD-check the REAL layer: grad wrt its created filter param
    x = np.random.randn(2, 4, 3).astype(np.float32)
    prog = pt.build(lambda a: L.row_conv(a, 2))
    params, state = prog.init(jax.random.PRNGKey(0), x)
    (wname,) = params.keys()

    def fn(wv):
        out, _ = prog.apply({wname: wv}, state, jnp.asarray(x))
        return out
    check_grad(fn, [np.asarray(params[wname])])


def test_grad_sequence_conv_input_and_weights():
    # FD-check the REAL layer: grads wrt input and created weight
    seg = jnp.asarray(np.array([0, 0, 1, 1, 1], np.int32))
    vals = np.random.randn(5, 3).astype(np.float32)
    prog = pt.build(lambda v: L.sequence_conv(v, seg, num_filters=4, filter_size=3,
                                              bias_attr=False))
    params, state = prog.init(jax.random.PRNGKey(0), vals)
    (wname,) = params.keys()

    def fn_input(v):
        out, _ = prog.apply(params, state, v)
        return out
    check_grad(fn_input, [vals])

    def fn_weight(wv):
        out, _ = prog.apply({wname: wv}, state, jnp.asarray(vals))
        return out
    check_grad(fn_weight, [np.asarray(params[wname])])


def test_grad_polygon_and_affine():
    x = np.random.randn(1, 2, 3, 4).astype(np.float32)
    check_grad(lambda a: L.polygon_box_transform(a), [x])
    theta = np.tile(np.array([[1.0, 0.1, 0], [0, 1.0, -0.1]], np.float32), (1, 1, 1))
    check_grad(lambda t: L.affine_grid(t, (1, 2, 3, 4)), [theta])


def test_grad_fused_ce_hidden():
    from paddle_tpu.ops.fused_ce import chunked_softmax_cross_entropy
    h = np.random.randn(4, 6).astype(np.float32)
    w = jnp.asarray(np.random.randn(6, 10).astype(np.float32))
    lab = jnp.asarray(np.array([1, 3, 9, 0]))
    check_grad(lambda hv: chunked_softmax_cross_entropy(hv, w, None, lab, 0.1, 4), [h])


def test_random_crop_oversize_raises():
    from paddle_tpu.core.errors import EnforceError
    x = np.zeros((1, 4, 4), np.float32)
    with pytest.raises(EnforceError):
        L.random_crop(jnp.asarray(x), (8, 8), seed=0)


def test_step_counter_int32_no_x64_warning():
    import warnings

    def f(x):
        return x, L.autoincreased_step_counter()

    prog = pt.build(f)
    x = np.zeros((1,), np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any truncation UserWarning fails
        params, state = prog.init(jax.random.PRNGKey(0), x)
        (_, step), _ = prog.apply(params, state, x)
    assert int(np.asarray(step)[0]) == 1
