"""Kimi-K2 (``model_type: kimi_k2``, the DeepseekV3 decoder) — the serving
path of one rank of an expert-parallel stage.

The published model: latent attention (``layers/latent.py``) in every
layer; ``first_k_dense_replace`` leading layers with a dense gated FFN, then
layers of ``n_routed_experts`` routed experts, ``num_experts_per_tok`` a
token by sigmoid scores with a selection bias (``parallel/moe.py``
:func:`~paddle_tpu.parallel.moe.sigmoid_topk_route`), plus shared experts;
RMSNorm, an untied head. At 384 experts of 44M parameters a layer no chip
holds a layer, so the config says which share this program holds:
``experts_held`` contiguous experts from ``first_expert`` in every expert
layer (the router still scores all ``n_routed_experts`` and takes the
published ``top_k``; the layer computes the part of the result that its own
experts give, :func:`~paddle_tpu.parallel.moe.moe_held`, and adds the shared
expert), ``vocab_size`` rows of the vocabulary (ids are drawn from and the
argmax is over the slice), and ``num_hidden_layers`` of the depth (the rest
lie on further chips as pipeline stages). Nothing stands in for the absent
chips or their exchange.

This module serves only: :func:`make_generator`, through the contract of
``layers/decoding.py`` (``prompt_ids [b, p] -> {"ids": [b, new]}``, the
first step's plain form). There is no ``make_model``: no cut of this model
trains on one chip, and the flash backward does not take unequal widths yet
(ROADMAP R1). Every matrix is created in ``cfg.dtype`` and held in it (no
float32 master copy: the weights are the chip's memory); norm scales and the
selection bias are float32.

Prefill runs the expanded attention through the flash kernel and seeds the
cache; a cached step runs the absorbed form over it. The cache is one
latent slab a layer, not k and v: ``c [rows, T, kv_lora]`` and the rotary
keys ``r [rows, rope, T]``, both lane-dense (``layers/latent.py``), in
per-layer lists as the GPT generator holds its k and v. The expert layers
are one stack of ``[L - dense, ...]`` parameters: under ``lax.scan`` in the
prefill, written out in the step (a scanned step copied the whole cache at
every layer to change its layout: the compile for a described v5e, PERF.md
section 6). The routed experts' banks are never sliced: every layer reads
its experts in place (``moe_held``'s ``bank_offset``). The attention's
``q_b`` and ``kv_b`` are regrouped once a request, before the scans and the
loop (``layers/latent.regrouped``: a head's 128 apart from its rotary 64),
and the step takes each layer's two halves of ``q_b`` as arrays of their
own: a view of the published columns, or a slice of a stack, cost a copy of
the weight in every layer of every step.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..core.errors import enforce
from ..framework import LayerHelper, name_scope
from ..layers import blocks as B
from ..layers import decoding
from ..layers import latent as M
from ..layers.stacked import StackedInit
from ..parallel import moe


@dataclasses.dataclass
class KimiK2Config:
    """Published key names where the meaning is the published one; the
    held share beside them."""
    vocab_size: int = 163840            # rows of the vocabulary held here
    hidden_size: int = 7168
    num_hidden_layers: int = 61         # layers held here, dense ones first
    first_k_dense_replace: int = 1
    intermediate_size: int = 18432      # the dense layers' FFN width
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384         # the router's width (all experts)
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0                       # rope_scaling.factor
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 262144
    # the share of the layer held here
    experts_held: int = 384
    first_expert: int = 0
    dtype: str = "bfloat16"

    @property
    def mla(self) -> M.MLADims:
        return M.MLADims(self.hidden_size, self.num_attention_heads,
                         self.q_lora_rank, self.kv_lora_rank,
                         self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim, self.rms_norm_eps)

    @property
    def yarn(self) -> M.Yarn:
        return M.Yarn(self.rope_theta, self.rope_factor,
                      self.rope_original_max_position, self.rope_beta_fast,
                      self.rope_beta_slow, self.rope_mscale,
                      self.rope_mscale_all_dim)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


# The selection bias as initialised here: small against the scores' spread,
# large enough to decide some selections (``e_score_correction_bias`` is
# moved by the load balancer, not by the loss, and published at zero).
SELECT_BIAS_STD = 0.01


def base_config(**kw) -> KimiK2Config:
    return KimiK2Config(**kw)


def _expert_stack(cfg: KimiK2Config, dtype):
    """The expert layers' parameters, one ``[layers, ...]`` stack: the
    attention, the shared expert, the router with its selection bias, and
    the held experts' banks ``[layers, experts_held, ...]``."""
    L, d, f = cfg.expert_layers, cfg.hidden_size, cfg.moe_intermediate_size
    E, held = cfg.n_routed_experts, cfg.experts_held
    p = M.mla_params(cfg.mla, dtype, L)
    shared = B.gated_ffn_params(d, f * cfg.n_shared_experts, dtype, L,
                                name="shared")
    p.update({"shared/" + k: v for k, v in shared.items()})
    helper = LayerHelper("experts", name="experts")

    def normal(fan_in):
        return StackedInit(init.Normal(0.0, fan_in ** -0.5))

    p["router/w"] = helper.create_parameter(
        "router/w", (L, d, E), jnp.float32, initializer=normal(d))
    p["router/select_bias"] = helper.create_parameter(
        "router/select_bias", (L, E), jnp.float32,
        initializer=init.Normal(0.0, SELECT_BIAS_STD))
    for name, shape, fan_in in (("gate/w", (d, f), d), ("up/w", (d, f), d),
                                ("down/w", (f, d), f)):
        p["experts/" + name] = helper.create_parameter(
            name, (L, held) + shape, dtype,
            initializer=StackedInit(StackedInit(init.Normal(0.0, fan_in ** -0.5))))
    return p


_BANKS = ("experts/gate/w", "experts/up/w", "experts/down/w")


def _expert_ffn(cfg: KimiK2Config, x, p, banks, layer):
    """``x + shared(h) + the held experts' part``, ``h`` the normed input;
    ``p`` one layer's slice of the stack, ``banks`` the whole stack's
    experts flattened to ``[layers * held, ...]`` and ``layer`` this one's
    index in it."""
    b, s, d = x.shape
    h = B.rms_norm(x, p["shared/ffn_norm/g"], cfg.rms_norm_eps)
    flat = h.reshape(b * s, d)
    experts, weights = moe.sigmoid_topk_route(
        flat, p["router/w"], p["router/select_bias"],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    routed = moe.moe_held(
        flat, experts, weights, *banks, first_expert=cfg.first_expert,
        experts_held=cfg.experts_held, experts_total=cfg.n_routed_experts,
        bank_offset=layer * cfg.experts_held)
    with jax.named_scope("shared"):
        shared = B.gated_ffn(h, p["shared/gate/w"], p["shared/up/w"],
                             p["shared/down/w"])
    return (x.astype(jnp.float32) + shared.astype(jnp.float32)
            + routed.reshape(b, s, d)).astype(x.dtype)


def _decoder(cfg: KimiK2Config, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, by name), the prefill of
    ``prompt_ids`` and the cached step that follows it; no audit."""
    enforce(0 < cfg.first_k_dense_replace < cfg.num_hidden_layers,
            "kimi_k2: dense layers lead and expert layers follow")
    enforce(0 <= cfg.first_expert
            and cfg.first_expert + cfg.experts_held <= cfg.n_routed_experts,
            f"kimi_k2: experts {cfg.first_expert}.."
            f"{cfg.first_expert + cfg.experts_held} of {cfg.n_routed_experts}")
    dims, yarn, dtype = cfg.mla, cfg.yarn, jnp.dtype(cfg.dtype)
    rows, p_len = prompt_ids.shape
    decoding.check_length(p_len, max_new_tokens, cfg.max_position_embeddings)
    d, n_dense, n_exp = (cfg.hidden_size, cfg.first_k_dense_replace,
                         cfg.expert_layers)

    # every parameter once, by name; the loops close over the arrays
    w_emb = decoding.token_embedding(cfg.vocab_size, d, dtype)
    with name_scope("dense"):
        dense = M.mla_params(dims, dtype, n_dense)
        dense.update(B.gated_ffn_params(d, cfg.intermediate_size, dtype,
                                        n_dense))
    with name_scope("moe"):
        stack = _expert_stack(cfg, dtype)
    final_g, w_head = decoding.untied_head(cfg.vocab_size, d, dtype)

    banks = tuple(stack[k].reshape((-1,) + stack[k].shape[2:])
                  for k in _BANKS)
    sliced = {k: v for k, v in stack.items() if k not in _BANKS}
    layer_ids = jnp.arange(n_exp)
    # the attention's projections as both forms read them, once a request:
    # a copy here, outside the scans and the loop, and none in them
    with jax.named_scope("regroup"):
        dense, sliced = M.regrouped(dense, dims), M.regrouped(sliced, dims)
        # a step's q_b a layer, cut out of the stacks here as well: the
        # written-out step then reads each half as it is held
        step_q_b = [{k: t[k][i] for k in ("q_b/w_nope", "q_b/w_rope")}
                    for t, n in ((dense, n_dense), (sliced, n_exp))
                    for i in range(n)]

    def head(x_last):   # [rows, d] -> log-probs over the held rows
        with jax.named_scope("head"):
            return decoding.log_probs(
                B.rms_norm(x_last, final_g, cfg.rms_norm_eps), w_head)

    # ---- prefill: the prompt through the expanded form
    def pre_dense(x, lp):
        x, cache = M.mla_prefill(x, lp, dims, yarn)
        return B.ffn_block(x, lp, cfg.rms_norm_eps), cache

    def pre_expert(x, xs):
        lp, layer = xs
        x, cache = M.mla_prefill(x, lp, dims, yarn)
        return _expert_ffn(cfg, x, lp, banks, layer), cache

    with jax.named_scope("prefill"):
        with jax.named_scope("tok"):
            x = w_emb[prompt_ids]
        x, (c0, r0) = jax.lax.scan(pre_dense, x, dense)
        x, (c1, r1) = jax.lax.scan(pre_expert, x, (sliced, layer_ids))
        first_logp = head(x[:, -1])

        def grow(a, axis):      # [b, p, ...] -> [b, total, ...] on ``axis``
            pad = [(0, 0)] * a.ndim
            pad[axis] = (0, max_new_tokens)
            return jnp.pad(a, pad)

        # per-layer lists of lane-dense slabs, as the GPT generator's k and v
        n_layers = cfg.num_hidden_layers
        c = [grow(a, 1) for a in list(c0) + list(c1)]   # [rows, total, kv_lora]
        r = [grow(a, 2) for a in list(r0) + list(r1)]   # [rows, rope, total]
    # ``lane_width``: the minor dimension of a latent slab as stored (a
    # rotary slab's is the context length); ``q_b_regrouped_bytes``: what the
    # request's one regrouping of ``q_b`` wrote
    decoding.record_plans(
        "latent", rows, c[0].shape[1], cfg.num_attention_heads, n_layers,
        str(c[0].dtype), c[0].shape[2], {"cache": c + r},
        rope_lane_width=r[0].shape[-1], first_step="conditional",
        q_b_regrouped_bytes=decoding.nbytes(
            [t[k] for t in (dense, sliced)
             for k in ("q_b/w_nope", "q_b/w_rope")]))

    # ---- one cached step: the absorbed form, the layers written out, each
    # with its own slabs (written at one row, read in place) and its slice
    # of the stack taken where it is used
    def layers(tokens, carried, index):
        with jax.named_scope("tok"):
            x = w_emb[tokens][:, None, :]
        c, r = list(carried["c"]), list(carried["r"])
        for i in range(n_layers):
            j = i - n_dense
            with jax.named_scope("stack_slice"):
                lp = jax.tree.map(lambda a: a[i if j < 0 else j],
                                  dense if j < 0 else sliced)
                lp.update(step_q_b[i])
            x, c[i], r[i] = M.mla_decode(x, lp, c[i], r[i], index, dims, yarn)
            x = (B.ffn_block(x, lp, cfg.rms_norm_eps) if j < 0
                 else _expert_ffn(cfg, x, lp, banks, j))
        return x, {"c": c, "r": r}

    return (decoding.start({"c": c, "r": r}, p_len, first_logp),
            decoding.step_in_conditional(layers, head), decoding.no_audit)


# ``make_generator(cfg, max_new_tokens, bos_id=1, eos_id=2)``: greedy
# incremental generation over the latent cache, a program fn ``(prompt_ids
# [b, p]) -> {"ids": [b, max_new_tokens]}``
make_generator = functools.partial(decoding.make_generator, _decoder)


__all__ = ["KimiK2Config", "base_config", "make_generator"]
