"""Plain reference for the decoder `paddle_tpu/models/gpt.py` defines.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no scan over
stacked layers, no cache. It is the published GPT-2 block with the
departures the configuration files list (sinusoidal positions, untied
head, ReLU in the FFN, no dropout). Layers run in a Python loop over one
jitted block, and gradients come from one jitted ``jax.vjp`` of that same
block applied layer by layer, so the whole reference compiles two small
programs whatever the depth.

Parameters arrive as a dict: ``emb [V, d]``, ``head [d, V]``,
``ln_f/scale``, ``ln_f/bias`` and ``layers``, a dict of arrays stacked
``[L, ...]`` under the block's own names (``qkv/w`` is ``[L, d, 3, d]``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG = -1e30


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def positions(seq: int, d_model: int):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    i = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / d_model)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def layer_norm(x, scale, bias, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def block(x, p, num_heads: int):
    """One pre-LN block: causal self-attention, then the ReLU FFN."""
    b, s, d = x.shape
    hd = d // num_heads
    h = layer_norm(x, p["ln1/scale"], p["ln1/bias"])
    qkv = jnp.einsum("bsd,dke->bske", h, p["qkv/w"]) + p["qkv/b"]
    q, k, v = (qkv[:, :, i].reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)
               for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, NEG)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    x = x + o.transpose(0, 2, 1, 3).reshape(b, s, d) @ p["out/w"] + p["out/b"]
    h = layer_norm(x, p["ln2/scale"], p["ln2/bias"])
    h = jax.nn.relu(h @ p["ffn_in/w"] + p["ffn_in/b"])
    return x + h @ p["ffn_out/w"] + p["ffn_out/b"]


def embed(emb, ids):
    return emb[ids] + positions(ids.shape[1], emb.shape[1])[None]


def head_logits(x, scale, bias, head):
    return layer_norm(x, scale, bias) @ head


def head_loss(x, scale, bias, head, labels, pad_id: int = 0):
    """Mean next-token cross-entropy over labels that are not ``pad_id``."""
    logp = jax.nn.log_softmax(head_logits(x, scale, bias, head), axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    keep = (labels != pad_id).astype(jnp.float32)
    return (ce * keep).sum() / jnp.maximum(keep.sum(), 1.0)


def _highest(fn):
    @functools.wraps(fn)
    def run(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return run


@functools.partial(jax.jit, static_argnums=2)
@_highest
def _block_at(x, layers, num_heads, i):
    return block(x, jax.tree.map(lambda a: a[i], layers), num_heads)


@functools.partial(jax.jit, static_argnums=2)
@_highest
def _block_vjp_at(x, layers, num_heads, i, g):
    """(gradient w.r.t. the block's input, gradients of layer i's params)."""
    p = jax.tree.map(lambda a: a[i], layers)
    _, vjp = jax.vjp(lambda x_, p_: block(x_, p_, num_heads), x, p)
    return vjp(g)


_embed = jax.jit(_highest(embed))
_head_logits = jax.jit(_highest(head_logits))
_head_grads = jax.jit(_highest(jax.value_and_grad(head_loss, argnums=(0, 3))))


@jax.jit
@_highest
def _embed_grad(emb, ids, g):
    return jax.vjp(lambda e: embed(e, ids), emb)[1](g)[0]


def hidden_states(params, ids, num_heads: int, keep: bool = False):
    """Hidden state after the last block; with ``keep`` also every
    block's input, which the backward pass needs."""
    params = _f32(params)
    x = _embed(params["emb"], ids)
    n_layers = params["layers"]["qkv/w"].shape[0]
    inputs = []
    for i in range(n_layers):
        if keep:
            inputs.append(x)
        x = _block_at(x, params["layers"], num_heads, i)
    return (x, inputs) if keep else x


def logits(params, ids, num_heads: int, first: int):
    """Logits at positions ``first`` onwards, ``[b, s - first, V]``."""
    params = _f32(params)
    x = hidden_states(params, ids, num_heads)
    return _head_logits(x[:, first:], params["ln_f/scale"],
                        params["ln_f/bias"], params["head"])


def loss_and_grads(params, ids, labels, num_heads: int, layer_grads):
    """Loss, and gradients of ``emb``, ``head`` and, for every
    ``(name, i)`` in ``layer_grads``, of layer ``i``'s ``name``."""
    params = _f32(params)
    x, inputs = hidden_states(params, ids, num_heads, keep=True)
    loss, (g, g_head) = _head_grads(x, params["ln_f/scale"],
                                    params["ln_f/bias"], params["head"], labels)
    grads = {"head": g_head}
    for i in reversed(range(len(inputs))):
        g, g_layer = _block_vjp_at(inputs[i], params["layers"], num_heads, i, g)
        for name, at in layer_grads:
            if at == i:
                grads[f"{name}[{i}]"] = g_layer[name]
    grads["emb"] = _embed_grad(params["emb"], ids, g)
    return loss, grads
