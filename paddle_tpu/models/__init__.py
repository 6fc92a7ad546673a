"""Model zoo mirroring the reference's book/benchmark configs
(MNIST MLP, ResNet-50, Transformer-base, DeepFM,
BERT-base; plus VGG/AlexNet/GoogLeNet/LSTM from benchmark/fluid/models/
and the recommender_system / label_semantic_roles book chapters), plus
the post-reference TPU-first families: GPT (decoder-only LM with
sp/pp training paths and KV-cache generation) and the GShard-style MoE
transformer."""

from . import (bert, convnets, deepfm, fit_a_line, gpt, lstm, mnist,
               moe_transformer, recommender, resnet, seq2seq, srl,
               transformer, vgg, word2vec)

__all__ = ["bert", "convnets", "deepfm", "fit_a_line", "gpt", "lstm", "mnist",
           "moe_transformer", "recommender", "resnet", "seq2seq", "srl",
           "transformer", "vgg", "word2vec"]
