"""Analytic FLOP accounting (core/flops.py).

The MFU denominators must be trustworthy: conv counts are pinned to the
well-known ResNet-50/VGG-16 totals, transformer counts to the 6N+12Lsd
convention.
"""

import sys
import os

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from paddle_tpu.core import flops


def test_resnet50_fwd_flops_matches_known_count():
    # torchvision ResNet-50: 4.09 GMACs @ 224 → 8.18 GFLOPs (2 per MAC)
    f = flops.resnet_fwd_flops(50, 224)
    assert abs(f - 8.18e9) / 8.18e9 < 0.02


def test_vgg16_fwd_flops_matches_known_count():
    # VGG-16: 15.5 GMACs @ 224 → ~31 GFLOPs
    f = flops.vgg_fwd_flops(16, 224)
    assert abs(f - 31.0e9) / 31.0e9 < 0.02


def test_resnet_depths_monotonic():
    assert flops.resnet_fwd_flops(101) > flops.resnet_fwd_flops(50)
    assert flops.resnet_fwd_flops(152) > flops.resnet_fwd_flops(101)


def test_alexnet_googlenet_fwd_flops_match_known_counts():
    # AlexNet: ~714 MMACs @ 224 → ~1.43 GFLOPs
    f = flops.alexnet_fwd_flops(224)
    assert abs(f - 1.43e9) / 1.43e9 < 0.05
    # GoogLeNet v1: ~1.6 GMACs @ 224 → ~3.1 GFLOPs
    g = flops.googlenet_fwd_flops(224)
    assert abs(g - 3.1e9) / 3.1e9 < 0.05


def test_se_resnext_fwd_flops_matches_known_count():
    # SE-ResNeXt-50 32x4d: ~4.25 GMACs @ 224 → ~8.5 GFLOPs
    f = flops.se_resnext_fwd_flops(50, 224)
    assert abs(f - 8.5e9) / 8.5e9 < 0.05
    assert flops.se_resnext_fwd_flops(101) > f


def test_transformer_flops_scaling():
    from paddle_tpu.models.transformer import base_config

    cfg6 = base_config(num_encoder_layers=6, num_decoder_layers=6)
    cfg12 = base_config(num_encoder_layers=12, num_decoder_layers=12)
    f6 = flops.transformer_train_flops(8, 256, cfg6)
    f12 = flops.transformer_train_flops(8, 256, cfg12)
    # layer-count doubling less than doubles total (vocab projection fixed)
    assert 1.5 < f12 / f6 < 2.0
    # tokens scale linearly
    assert flops.transformer_train_flops(16, 256, cfg6) == pytest.approx(2 * f6)


def test_bert_flops_dominated_by_encoder():
    from paddle_tpu.models.bert import base_config

    cfg = base_config()
    f = flops.bert_train_flops(32, 128, 20, cfg)
    # 6N per token alone: N_matmul = L(4d^2+2d*di)
    n_matmul = cfg.num_layers * (4 * cfg.d_model ** 2 + 2 * cfg.d_model * cfg.d_inner)
    assert f > 6.0 * n_matmul * 32 * 128


def test_causal_attention_halved():
    assert flops._attn_train_flops(100, 64, 32, 2, causal=True) == \
        pytest.approx(flops._attn_train_flops(100, 64, 32, 2, causal=False) / 2)
