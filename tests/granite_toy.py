"""The toy Granite-4.0-H the three ``test_granite_*.py`` files share
(``benchmarks/tests/data/tiny-granite-hybrid.json``: 4 layers, one period of
the toy pattern, Mamba-2 at 0, 1, 3 and attention at 2; four heads of 32 in
one lane group; 4 of 8 experts held, 3 a token; prefill pieces of 80 tokens,
five chunks of 16), its seeded weights and the two ends of every comparison:
the decoder's own log-probabilities and the plain reference's."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

import paddle_tpu as pt
from benchmarks.families import granite_hybrid as family
from benchmarks.reference import granite_hybrid as reference
from paddle_tpu.layers import gqa, mamba2
from paddle_tpu.models import granite_hybrid

with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                       "tiny-granite-hybrid.json")) as f:
    TINY = json.load(f)
SHAPE = reference.shape_of(TINY)
KINDS = [kind for _, kind in reference.layers_of(TINY)]
VOCAB = TINY["vocab_size"]
MDIMS = mamba2.Mamba2Dims(64, 4, 32, 16, 4, 16, 1e-5, 0.22)
ADIMS = gqa.GQADims(64, 4, 2, 16, 0, 0.0, 1e-5)


def rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def seeded(config, prompt_len, new, seed=3):
    """``(weights, the program's parameters on the device)``."""
    weights = family.decoder_params(config, seed, prompt_len, new)
    return weights, jax.tree.map(jnp.asarray, weights.host_params())


def prompts(rows, length, seed=0):
    return family.prompts(VOCAB, rows, length, seed, 1)[0]


def scored(config, params, prompt, next_ids):
    """The decoder's log-probabilities under ``next_ids``, ``[rows, n + 1,
    vocab]``: its own prefill, carry and steps (``decoding.make_scorer``)."""
    prog = pt.build(granite_hybrid.make_scorer(family.program_config(config)))
    return np.asarray(prog.apply(params, {}, training=False, prompt_ids=prompt,
                                 next_ids=next_ids)[0]["logp"])


def reference_logp(config, params, ids, first):
    sh = reference.shape_of(config)
    lg = reference.logits(family.reference_params(params, config),
                          jnp.asarray(ids), sh, KINDS, first=first)
    return np.asarray(jax.nn.log_softmax(lg, axis=-1))
