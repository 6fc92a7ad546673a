"""Analytic model-FLOP accounting for MFU reporting.

MFU = model FLOPs (the math the model *defines* — excluding remat
recompute and XLA bookkeeping) / step time / chip peak FLOP/s. This is
the utilization denominator, in place of throughput-vs-2018-Xeon ratios.

Conventions (PaLM appendix-B style, Megatron matmul accounting):
- dense matmul train FLOPs = 6 · (matmul params) · tokens
  (forward 2N, backward 4N);
- attention adds fwd 4·s·d per token per layer (QK^T + AV), ×3 for
  train = 12·L·s·d per token; *causal* attention is halved because the
  flash kernel computes only the lower triangle — counting the full
  square would inflate MFU;
- elementwise/norm/gather FLOPs are excluded (undercount, never
  overcount).

Reference analog: the fluid benchmark suite reported raw imgs/sec only
(benchmark/fluid/fluid_benchmark.py); FLOP/utilization accounting has
no reference counterpart and is TPU-first by design.
"""

from __future__ import annotations

from typing import Optional, Sequence

# -- chip peak ---------------------------------------------------------------

# bf16 dense peak per *jax device*, by device_kind substring (first match
# wins — order matters: "v5p" before "v5", "v5 lite"/"v5e" before "v5").
# Sources: public TPU spec sheets (How to Scale Your Model, cloud docs).
_PEAK_BF16 = [
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 61.5e12),   # one jax device = one core on v2/v3 (2 cores/chip)
    ("v2", 22.5e12),
]


# -- transformer family ------------------------------------------------------


def _attn_train_flops(tokens: int, seq: int, d_model: int, layers: int,
                      causal: bool) -> float:
    f = 12.0 * layers * seq * d_model * tokens
    return f / 2 if causal else f


def transformer_train_flops(bs: int, seq: int, cfg) -> float:
    """Train-step FLOPs of the encoder-decoder transformer
    (models/transformer.py). Encoder: full self-attn. Decoder: causal
    self-attn (halved) + full cross-attn, whose q/kv/out projections add
    ~4·d² params per decoder layer on top of the self-attn 4·d². Vocab
    projection counted on decoder tokens only."""
    d, di = cfg.d_model, cfg.d_inner
    tokens = bs * seq
    enc_layer_params = 4 * d * d + 2 * d * di
    dec_layer_params = 8 * d * d + 2 * d * di  # + cross q/kv/out projections
    f = 6.0 * tokens * (enc_layer_params * cfg.num_encoder_layers +
                        dec_layer_params * cfg.num_decoder_layers)
    f += _attn_train_flops(tokens, seq, d, cfg.num_encoder_layers, causal=False)
    f += _attn_train_flops(tokens, seq, d, cfg.num_decoder_layers, causal=True)
    f += _attn_train_flops(tokens, seq, d, cfg.num_decoder_layers, causal=False)  # cross
    f += 6.0 * d * cfg.trg_vocab * tokens  # output projection
    return f


def gpt_train_flops(bs: int, seq: int, cfg) -> float:
    """Train-step FLOPs of the decoder-only LM (models/gpt.py): causal
    stack (attention halved) + LM head over every token."""
    d, di, L = cfg.d_model, cfg.d_inner, cfg.num_layers
    tokens = bs * seq
    f = 6.0 * (4 * d * d + 2 * d * di) * tokens * L
    f += _attn_train_flops(tokens, seq, d, L, causal=True)
    f += 6.0 * d * cfg.vocab_size * tokens  # lm head
    return f


def gpt_decode_flops(bs: int, prompt: int, new_tokens: int, cfg) -> float:
    """Forward-only FLOPs of prefill(prompt) + the incremental decode
    steps the generator actually runs: the first generated token comes
    from the prefill's own head eval (no stack step), so only
    new_tokens-1 incremental stack steps execute, with new_tokens head
    evals total (fwd only, no ×3; undercount-never-overcount)."""
    d, di, L = cfg.d_model, cfg.d_inner, cfg.num_layers
    params = (4 * d * d + 2 * d * di) * L
    inc = max(new_tokens - 1, 0)
    tokens = bs * (prompt + inc)
    f = 2.0 * params * tokens
    f += 2.0 * d * cfg.vocab_size * bs * new_tokens  # head: prefill + inc steps
    # prefill causal attention (halved, fwd-only) + per-step cache attention
    f += _attn_train_flops(bs * prompt, prompt, d, L, causal=True) / 3.0
    avg_ctx = prompt + inc / 2.0
    f += 4.0 * L * avg_ctx * d * bs * inc
    return f


def bert_train_flops(bs: int, seq: int, num_masked: int, cfg) -> float:
    """Train-step FLOPs of BERT pretraining (models/bert.py): encoder
    stack + MLM head (transform + vocab proj over masked positions) +
    pooler/NSP head."""
    d, di, L = cfg.d_model, cfg.d_inner, cfg.num_layers
    tokens = bs * seq
    f = 6.0 * (4 * d * d + 2 * d * di) * tokens * L
    f += _attn_train_flops(tokens, seq, d, L, causal=False)
    f += 6.0 * (d * d + d * cfg.vocab_size) * bs * num_masked  # MLM head
    f += 6.0 * (d * d + 2 * d) * bs  # pooler + NSP
    return f


# -- convnets ----------------------------------------------------------------


def _conv_flops(cin: int, cout: int, k: int, hout: int, wout: int) -> float:
    return 2.0 * k * k * cin * cout * hout * wout


def resnet_fwd_flops(depth: int = 50, image_size: int = 224,
                     class_num: int = 1000) -> float:
    """Per-image forward FLOPs of ResNet-50/101/152 (bottleneck blocks,
    models/resnet.py architecture). Validated ≈8.2 GFLOPs for
    50/224 (2 FLOPs per MAC)."""
    blocks = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}[depth]
    s = image_size
    f = _conv_flops(3, 64, 7, s // 2, s // 2)  # stem, stride 2
    s //= 4  # stem stride 2 + maxpool stride 2
    cin = 64
    for stage, n in enumerate(blocks):
        width = 64 * (2 ** stage)
        cout = width * 4
        stride = 1 if stage == 0 else 2
        for b in range(n):
            st = stride if b == 0 else 1
            so = s // st
            f += _conv_flops(cin, width, 1, s, s)  # 1×1 at input res (v1.5: stride on the 3×3)
            f += _conv_flops(width, width, 3, so, so)
            f += _conv_flops(width, cout, 1, so, so)
            if b == 0:
                f += _conv_flops(cin, cout, 1, so, so)  # projection shortcut
            cin, s = cout, so
    f += 2.0 * cin * class_num  # fc
    return f


def vgg_fwd_flops(depth: int = 16, image_size: int = 224,
                  class_num: int = 1000) -> float:
    """Per-image forward FLOPs of VGG-16/19. ≈31 GFLOPs for 16/224."""
    cfgs = {16: (2, 2, 3, 3, 3), 19: (2, 2, 4, 4, 4)}[depth]
    chans = (64, 128, 256, 512, 512)
    s, cin, f = image_size, 3, 0.0
    for n, c in zip(cfgs, chans):
        for _ in range(n):
            f += _conv_flops(cin, c, 3, s, s)
            cin = c
        s //= 2
    flat = cin * s * s
    for dims in ((flat, 4096), (4096, 4096), (4096, class_num)):
        f += 2.0 * dims[0] * dims[1]
    return f


def alexnet_fwd_flops(image_size: int = 224, class_num: int = 1000) -> float:
    """Per-image forward FLOPs of AlexNet (models/convnets.make_alexnet).
    ≈1.4 GFLOPs at 224 (2 FLOPs per MAC; the classic ~720M-MAC figure)."""
    s = (image_size + 2 * 2 - 11) // 4 + 1          # conv1 k11 s4 p2
    f = _conv_flops(3, 64, 11, s, s)
    s = (s - 3) // 2 + 1                             # pool 3/2
    f += _conv_flops(64, 192, 5, s, s)
    s = (s - 3) // 2 + 1
    f += _conv_flops(192, 384, 3, s, s)
    f += _conv_flops(384, 256, 3, s, s)
    f += _conv_flops(256, 256, 3, s, s)
    s = (s - 3) // 2 + 1
    for dims in ((256 * s * s, 4096), (4096, 4096), (4096, class_num)):
        f += 2.0 * dims[0] * dims[1]
    return f


# GoogLeNet v1 inception parameter table (models/convnets.make_googlenet):
# (c1, c3r, c3, c5r, c5, proj) per block, grouped by spatial stage.
_GOOGLENET_STAGES = (
    ((64, 96, 128, 16, 32, 32), (128, 128, 192, 32, 96, 64)),
    ((192, 96, 208, 16, 48, 64), (160, 112, 224, 24, 64, 64),
     (128, 128, 256, 24, 64, 64), (112, 144, 288, 32, 64, 64),
     (256, 160, 320, 32, 128, 128)),
    ((256, 160, 320, 32, 128, 128), (384, 192, 384, 48, 128, 128)),
)


def googlenet_fwd_flops(image_size: int = 224, class_num: int = 1000) -> float:
    """Per-image forward FLOPs of GoogLeNet v1. ≈3 GFLOPs at 224."""
    s = image_size // 2                              # stem conv7 s2
    f = _conv_flops(3, 64, 7, s, s)
    s = (s + 2 - 3) // 2 + 1                         # pool 3/2 p1
    f += _conv_flops(64, 64, 1, s, s)
    f += _conv_flops(64, 192, 3, s, s)
    s = (s + 2 - 3) // 2 + 1
    cin = 192
    for stage in _GOOGLENET_STAGES:
        for (c1, c3r, c3, c5r, c5, proj) in stage:
            f += _conv_flops(cin, c1, 1, s, s)
            f += _conv_flops(cin, c3r, 1, s, s) + _conv_flops(c3r, c3, 3, s, s)
            f += _conv_flops(cin, c5r, 1, s, s) + _conv_flops(c5r, c5, 5, s, s)
            f += _conv_flops(cin, proj, 1, s, s)
            cin = c1 + c3 + c5 + proj
        s = (s + 2 - 3) // 2 + 1                     # inter-stage pool 3/2 p1
    f += 2.0 * cin * class_num
    return f


def se_resnext_fwd_flops(depth: int = 50, image_size: int = 224,
                         class_num: int = 1000, cardinality: int = 32,
                         reduction: int = 16) -> float:
    """Per-image forward FLOPs of SE-ResNeXt-50/101
    (models/convnets.make_se_resnext): grouped 3×3 divides that conv's
    FLOPs by cardinality-groups; SE adds two tiny FCs per block.
    ≈8.4 GFLOPs for 50/224."""
    stages = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}[depth]
    s = image_size // 2                       # stem conv7 s2
    f = _conv_flops(3, 64, 7, s, s)
    s = (s + 2 - 3) // 2 + 1                  # maxpool 3/2 p1
    cin = 64
    for stage, n in enumerate(stages):
        filters = 128 * (2 ** stage)
        cout = filters * 2
        for b in range(n):
            st = 2 if stage > 0 and b == 0 else 1
            so = s // st
            f += _conv_flops(cin, filters, 1, s, s)
            # grouped conv: in-channels per group × total out-channels
            f += _conv_flops(filters // cardinality, filters, 3, so, so)
            f += _conv_flops(filters, cout, 1, so, so)
            se_mid = max(cout // reduction, 4)
            f += 2.0 * (cout * se_mid + se_mid * cout)          # SE FCs
            if cin != cout or st != 1:
                f += _conv_flops(cin, cout, 1, so, so)          # projection
            cin, s = cout, so
    f += 2.0 * cin * class_num
    return f


def convnet_train_flops(fwd_flops_per_image: float, bs: int) -> float:
    """Train = fwd + bwd ≈ 3× fwd (bwd does ~2× fwd work)."""
    return 3.0 * fwd_flops_per_image * bs


# -- small models ------------------------------------------------------------


def mlp_train_flops(bs: int, dims: Sequence[int]) -> float:
    params = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return 6.0 * params * bs


def lstm_train_flops(bs: int, seq: int, hidden: int, num_layers: int,
                     emb_dim: Optional[int] = None) -> float:
    """2 matmuls (input + recurrent) of 4 gates per step per layer."""
    emb_dim = emb_dim or hidden
    f = 0.0
    for layer in range(num_layers):
        xin = emb_dim if layer == 0 else hidden
        f += 6.0 * (4 * hidden * (xin + hidden)) * bs * seq
    return f


def seq2seq_train_flops(bs: int, src_len: int, trg_len: int, emb_dim: int,
                        hidden: int, trg_vocab: int) -> float:
    """GRU seq2seq with additive attention (models/seq2seq.py — the
    book machine-translation model; benchmark/fluid machine_translation
    analog). Counts the gate/attention/output matmuls at the train
    factor 6 (fwd + 2x bwd); embedding gathers, softmaxes, and
    elementwise attention math are excluded (undercounts, never
    inflates)."""
    f = 0.0
    # bi-GRU encoder: 2 directions x 3 gates x h x (emb + h) per token
    f += 2 * 6.0 * (3 * hidden * (emb_dim + hidden)) * bs * src_len
    # encoder attention projection [2h -> h] per source token
    f += 6.0 * (2 * hidden * hidden) * bs * src_len
    # decoder per target step: query proj [h->h], score dot [s x h],
    # context einsum [s x 2h], GRU x-proj [(emb+2h) -> 3h], h-proj
    f += 6.0 * (hidden * hidden) * bs * trg_len
    f += 6.0 * (src_len * hidden) * bs * trg_len
    f += 6.0 * (src_len * 2 * hidden) * bs * trg_len
    f += 6.0 * (3 * hidden * (emb_dim + 2 * hidden)) * bs * trg_len
    f += 6.0 * (3 * hidden * hidden) * bs * trg_len
    # output projection [h -> V]
    f += 6.0 * (hidden * trg_vocab) * bs * trg_len
    return f


def deepfm_train_flops(bs: int, num_fields: int, emb_size: int, num_dense: int,
                       hidden_dims: Sequence[int]) -> float:
    """MLP tower + linear heads; embedding gathers/FM interactions are
    bandwidth-bound and excluded (undercount)."""
    dims = [num_fields * emb_size + num_dense, *hidden_dims, 1]
    f = mlp_train_flops(bs, dims)
    f += 6.0 * num_dense * bs  # dense linear head
    return f
