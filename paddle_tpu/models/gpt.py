"""GPT — decoder-only causal language model.

No reference counterpart (the 2018 reference predates decoder-only LMs;
its closest config is the transformer benchmark,
benchmark/fluid/models/machine_translation.py) — this is the modern
long-context flagship the TPU build adds on top of the capability set,
and the model family that exercises sequence/context parallelism as a
TRAINING PATH:

- blocks are the stacked causal self-attention blocks (layers/stacked.py),
  so pipeline parallelism (DistStrategy.pp_microbatches) works unchanged;
- with DistStrategy.sequence_parallel on an ``sp`` mesh, the input ids /
  labels / positions are permuted ONCE into the zigzag order and the
  whole stack runs in that layout — attention is zigzag ring attention
  (parallel/ring_attention.py) with shard-local entry/exit, positions
  travel with their tokens, and the mean loss is permutation-invariant,
  so nothing is ever permuted back.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import initializer as init
from .. import layers as L
from ..core.errors import enforce
from ..framework import LayerHelper, cast_compute, name_scope, sp_config
from ..layers import attention as A
from ..layers import decoding
from ..layers import stacked as S
from .lm_head import lm_head_loss


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 32000
    max_len: int = 1024
    d_model: int = 768
    d_inner: int = 3072
    num_heads: int = 12
    num_layers: int = 12
    use_flash: bool = True
    fused_ce: bool = True
    ce_chunk: int = 4096         # rows of a chunk of the fused head
    remat: bool = False
    # residual/softmax/ffn dropout inside the stacked blocks (per-layer
    # rng via framework.rng_fold; rate > 0 disables the flash kernel the
    # same way the unrolled attention layer does)
    dropout: float = 0.0
    dtype: str = "float32"
    # KV cache storage for the incremental generator, one lane-dense
    # [rows, T, heads*head_dim] slab for k and one for v a layer (the
    # minor dimension is the model width, so the TPU's (8, 128) tiles
    # hold no padding whatever the head_dim): "compute" keeps the
    # compute dtype; "int8" stores symmetric int8 with one f32 scale a
    # head and position, [rows, T, heads] (layers/stacked.quantize_kv) —
    # half the bf16 cache bytes on the HBM-bound decode read, scales
    # factored out of both attention matmuls so nothing is dequantized
    # into memory
    kv_cache_dtype: str = "compute"


def base_config(**kw) -> GPTConfig:
    return GPTConfig(**kw)


def make_model(cfg: GPTConfig):
    """Program fn: (ids [b, s], labels [b, s]) -> {"loss", "token_count"}.
    Next-token CE over non-pad labels (pad id 0)."""

    def gpt(ids, labels):
        dtype = jnp.dtype(cfg.dtype)
        s = ids.shape[1]
        enforce(s <= cfg.max_len, f"seq {s} exceeds max_len {cfg.max_len}")
        sp = sp_config()
        if sp is not None and sp.get("impl", "ring") == "ring":
            from ..parallel.ring_attention import zigzag_order
            n = sp["mesh"].shape[sp["axis"]]
            enforce(s % (2 * n) == 0,
                    f"sequence parallelism needs seq {s} divisible by 2·sp={2 * n}")
            order = zigzag_order(s, n)
            ids = jnp.take(ids, order, axis=1)
            labels = jnp.take(labels, order, axis=1)
            positions = order
            # this model keeps activations in zigzag order end-to-end, so
            # the ring may skip its per-call entry/exit gathers; models
            # that do NOT permute get the safe "natural" default
            sp["layout"] = "zigzag"
        else:
            if sp is not None:  # ulysses: natural order, no permutation
                n = sp["mesh"].shape[sp["axis"]]
                enforce(s % n == 0,
                        f"ulysses sequence parallelism needs seq {s} "
                        f"divisible by sp={n}")
            positions = jnp.arange(s)

        with name_scope("tok"):
            x = L.embedding(ids, size=[cfg.vocab_size, cfg.d_model],
                            dtype=cfg.dtype)
            pe = A.positional_encoding(cfg.max_len, cfg.d_model, dtype)
            x = x + pe[positions][None]

        with name_scope("gpt"):
            stack = S.encoder_stack_params(cfg.num_layers, cfg.d_model,
                                           cfg.d_inner)
            x = S.apply_stacked(x, stack, S.make_encoder_block,
                                num_heads=cfg.num_heads,
                                use_flash=cfg.use_flash, causal=True,
                                remat=cfg.remat,
                                dropout_rate=cfg.dropout)
            x = L.layer_norm(x, begin_norm_axis=2)

        loss, token_count = lm_head_loss(x, labels, cfg.vocab_size, dtype,
                                         cfg.fused_ce, cfg.ce_chunk)
        return {"loss": loss, "token_count": token_count}

    return gpt


def _decoder(cfg: GPTConfig, prompt_ids, max_new_tokens: int,
             beam_size: int = 1):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, with the exact names the
    train program uses, so trained params load directly), the prefill of
    ``prompt_ids``, its rows repeated ``beam_size`` times, and the cached
    step that follows it; no audit."""
    dtype = jnp.dtype(cfg.dtype)
    b, p = prompt_ids.shape
    total = p + max_new_tokens
    decoding.check_length(p, max_new_tokens, cfg.max_len, "max_len")
    pe = A.positional_encoding(cfg.max_len, cfg.d_model, dtype)

    # the decode loop closes over the arrays (no LayerHelper calls inside
    # scan — nothing to re-resolve)
    with name_scope("tok"):
        w_emb = LayerHelper("embedding").create_parameter(
            "w", (cfg.vocab_size, cfg.d_model), dtype,
            initializer=init.Xavier())
    with name_scope("gpt"):
        stack = S.encoder_stack_params(cfg.num_layers, cfg.d_model,
                                       cfg.d_inner)
        ln = LayerHelper("layer_norm")
        ln_scale = ln.create_parameter("scale", (cfg.d_model,), jnp.float32,
                                       initializer=init.Constant(1.0))
        ln_bias = ln.create_parameter("bias", (cfg.d_model,), jnp.float32,
                                      initializer=init.Constant(0.0))
    w_head = LayerHelper("lm_head").create_parameter(
        "w", (cfg.d_model, cfg.vocab_size), dtype,
        initializer=init.Xavier())

    def head(x_last):  # [rows, d] -> log-probs [rows, vocab]
        with jax.named_scope("head"):
            h = S._ln(x_last[:, None, :], ln_scale, ln_bias)[:, 0]
            return jax.nn.log_softmax(
                jnp.matmul(h, w_head).astype(jnp.float32), axis=-1)

    # ---- prefill: run the prompt causally, capture per-layer k/v
    # (cast_compute keeps the scan carry dtype consistent with the
    # blocks' compute dtype regardless of cfg.dtype)
    def pre(a, lp):
        return S.prefill_block(a, lp, cfg.num_heads, cfg.use_flash)

    with jax.named_scope("prefill"):
        with jax.named_scope("tok"):
            x = cast_compute(w_emb[prompt_ids] + pe[:p][None])
        x, (ks, vs) = jax.lax.scan(pre, x, stack)
        first_logp = head(x[:, -1])  # first generated token comes from here

    K = beam_size
    rows = b * K
    L = cfg.num_layers

    def grow(a):  # [b, p, ...] -> [rows, total, ...]
        a = jnp.repeat(a, K, axis=0) if K > 1 else a
        pad = jnp.zeros((rows, total - p) + a.shape[2:], a.dtype)
        return jnp.concatenate([a, pad], axis=1)

    # caches are PER-LAYER lists of lane-dense [rows, total, h*hd]
    # arrays (layers/stacked.py: heads side by side in the minor
    # dimension, so the TPU's (8, 128) tiling pads nothing) —
    # beam_search reorders state leaves whose leading dim is
    # batch*beam, so the layer axis must NOT lead (the transformer
    # decoder's contract, layers/beam_search.py _gather_beams)
    enforce(cfg.kv_cache_dtype in ("compute", "int8"),
            f"kv_cache_dtype={cfg.kv_cache_dtype!r} (compute|int8)")
    int8_kv = cfg.kv_cache_dtype == "int8"
    with jax.named_scope("cache_init"):
        if int8_kv:
            # quantize the prefix BEFORE growing: padded tail positions
            # get int8 zeros with zero scales (dequantize to exact 0)
            kq, ksc = zip(*(S.quantize_kv(ks[i], cfg.num_heads)
                            for i in range(L)))
            vq, vsc = zip(*(S.quantize_kv(vs[i], cfg.num_heads)
                            for i in range(L)))
            caches = {"kq": [grow(a) for a in kq],
                      "ks": [grow(a) for a in ksc],
                      "vq": [grow(a) for a in vq],
                      "vs": [grow(a) for a in vsc]}
        else:
            caches = {"k": [grow(ks[i]) for i in range(L)],
                      "v": [grow(vs[i]) for i in range(L)]}
    slab = jax.tree.leaves(caches)[0]
    decoding.record_plans("kv", rows, total, cfg.num_heads, L,
        str(slab.dtype), slab.shape[2], {"cache": caches},
        head_dim=cfg.d_model // cfg.num_heads, first_step="conditional")
    state0 = decoding.start(
        caches, p, jnp.repeat(first_logp, K, axis=0) if K > 1 else first_logp)
    with jax.named_scope("stack_slice"):
        layer_params = [jax.tree.map(lambda a, i=i: a[i], stack)
                        for i in range(L)]
    block = S.decode_block_q8 if int8_kv else S.decode_block
    cache_keys = tuple(caches)      # the order a block takes and returns them

    # the prefill already produced the first step's distribution;
    # afterwards embed the chosen token and run the cached stack
    def layers(tokens, carried, index):
        with jax.named_scope("tok"):
            xt = cast_compute(w_emb[tokens][:, None, :]
                              + pe[index][None, None])
        new = {k: [] for k in cache_keys}
        for i, lp in enumerate(layer_params):
            xt, *layer = block(xt, lp, *(carried[k][i] for k in cache_keys),
                               index, cfg.num_heads)
            for k, c in zip(cache_keys, layer):
                new[k].append(c)
        return xt, new

    return (state0, decoding.step_in_conditional(layers, head),
            decoding.no_audit)


def make_generator(cfg: GPTConfig, max_new_tokens: int, beam_size: int = 1,
                   bos_id: int = 1, eos_id: int = 2,
                   length_penalty_alpha: float = 0.0):
    """Incremental generation program with a KV cache over the stacked
    params (beam_search_op capability for the decoder-only family; the
    transformer zoo's make_decoder sibling). Parameter names match
    make_model's train program, so trained params load directly.

    Returns a program fn: (prompt_ids [b, p]) -> {"ids": [b, max_new]}
    (greedy) or {"ids": [b, beam, max_new], "scores": [b, beam]} (beam).
    """
    return decoding.make_generator(_decoder, cfg, max_new_tokens, bos_id,
                                   eos_id, beam_size, length_penalty_alpha)
