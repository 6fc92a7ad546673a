"""The ``kimi_k2`` family: from a configuration file to the generator under
test, its seeded weights, its operation and byte counts, and its check
against the plain reference (``benchmarks/reference/kimi_k2.py``).

The configuration file keeps the published keys of ``config.json`` and says
what of the model is held here: ``num_hidden_layers``, ``n_routed_experts``
(the experts held) and ``vocab_size`` (the rows held) are cut, the
``published`` group has their published values and the ``deployment`` group
the stage they are a rank of. No training path (``models/kimi_k2.py``).

The weights are the family's, not the program's initialisers: every tensor
of every layer is one seeded draw on the device (``Weights.slab``), so the
export can make 8.35 GB a layer at a time and hand them over on the host,
and the check can make one layer again, in float32, without ever holding a
second copy of the model beside the server's.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List

import numpy as np

from benchmarks.reference import kimi_k2 as reference

# The check: over 32 served rows x 64 tokens, the gap between the reference's
# largest logit and its logit of the served token. Three limits, each from
# readings at the published widths on the chip at these very values (ten
# sound runs of 2,048 tokens on ten seeds, and ``tools/k25_sensitivity.py``
# on three of them; PERF.md section 6, PR 31; the reference's logits have
# standard deviation 1.0, the largest stands 4.0 above the mean and 0.24
# above the second):
# (1) AGREE_FLOOR: the share of tokens that are the reference's own argmax.
# bfloat16 against the float32 reference read 95.95 to 97.66% (mean 96.9%,
# deviation 0.53%: the rest are near-ties that rounding decides); every
# fault reads lower on every seed: the selection bias dropped 89.3 / 87.2 /
# 87.1%, the keys rotated one position on 75.7 / 77.2 / 75.2%, the routed
# experts left out 69.3 / 69.2 / 67.0%, the reference in an 8-bit float
# (the precision below the one the configuration states) 69.0 / 67.6 /
# 68.6%. The floor lies 3.6 deviations under the sound mean and 5.7 points
# over the nearest fault.
# (2) MEAN_GAP_LIMIT: the mean gap read 0.0005 to 0.0024 with nothing wrong
# (a few tokens carry it: where a token's 8th and 9th score lie within
# bfloat16's reach, program and reference seat different experts, and one
# held expert more or less in a late layer moves a logit by tenths); the
# faults read 0.015 to 0.070, the dropped bias lowest. This limit catches a
# fault that moves every logit, (1) one that moves few.
# (3) LOGIT_MARGIN guards against a garbled id only, and none of the faults
# above: one token lost up to 0.92 with nothing wrong and 0.69 to 1.28
# under the faults, so no margin parts them; another id at the same
# position (the check's ``other_id_gap_p01``) loses more than 1.30 to 1.74
# at 99 positions of 100 and 4.0 on average.
AGREE_FLOOR = 0.95
MEAN_GAP_LIMIT = 0.008
LOGIT_MARGIN = 1.5

SERVE_CHECK_ROWS = 32
CHECK_ROWS_AT_ONCE = 8

# Initial scales (the file's ``assumed`` group says the same): every matrix
# N(0, 1 / fan_in), the embedding N(0, 1), norm scales 1, and the selection
# bias N(0, 0.01^2) from the seed, as ISSUE 31 fixes it: small against the
# scores' spread and large enough to decide some selections (dropping it
# fails the check). Nothing balances the experts' loads: random routers are
# skewed (a direction the hidden states share favours some experts by tens
# of percent), so the 12 experts held here draw some percent more or fewer
# pairs from one seed's weights to the next's, a request's time follows, and
# that is the cell's spread across seeds (PERF.md section 6, PR 31).
SELECT_BIAS_STD = 0.01


# ---------------------------------------------------------------------------
# configuration


def program_config(config: Dict[str, Any]):
    """The ``models/kimi_k2.py`` config for a configuration file."""
    from paddle_tpu.models import kimi_k2

    sc, dep = config["rope_scaling"], config["deployment"]
    return kimi_k2.base_config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["published"]["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        moe_intermediate_size=config["moe_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_factor=sc["factor"],
        rope_original_max_position=sc["original_max_position_embeddings"],
        rope_beta_fast=sc["beta_fast"], rope_beta_slow=sc["beta_slow"],
        rope_mscale=sc["mscale"], rope_mscale_all_dim=sc["mscale_all_dim"],
        max_position_embeddings=config["max_position_embeddings"],
        experts_held=config["n_routed_experts"],
        first_expert=dep["expert_rank"] * config["n_routed_experts"],
        dtype=config["run"]["dtype"])


def _program(config: Dict[str, Any], new_tokens: int):
    import paddle_tpu as pt
    from paddle_tpu.models import kimi_k2

    return pt.build(kimi_k2.make_generator(program_config(config),
                                           max_new_tokens=new_tokens))


# ---------------------------------------------------------------------------
# inputs


def prompts(vocab: int, rows: int, length: int, seed: int, n: int,
            stream: int = 7) -> List[np.ndarray]:
    """``n`` prompts ``[rows, length]`` of ids drawn evenly from the held
    rows of the vocabulary (pad 0 and the generator's bos 1 and eos 2 never
    drawn): ISSUE 31 draws ids from the slice. Not GPT's noisy cycle over
    256 ids: a router sees the ids, the generated ones are any of the held
    rows, and a prompt of 256 ids would give the prefill's experts a load
    the steps' never see. A pure function of its arguments."""
    rng = np.random.RandomState((seed + 1 + stream) % (2 ** 32))
    return [rng.randint(3, vocab, (rows, length)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, from the configuration alone


def _counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by part, of one layer."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    ql, kvl = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    f = config["moe_intermediate_size"]
    return {"mla": d * ql + ql * H * (nope + rope) + d * (kvl + rope)
            + kvl * H * (nope + v) + H * v * d,
            "shared": 3 * d * f * config["n_shared_experts"],
            "router": d * config["published"]["n_routed_experts"],
            "expert": 3 * d * f,
            "dense_ffn": 3 * d * config["intermediate_size"],
            "head": d * config["vocab_size"]}


def _layers(config: Dict[str, Any]):
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def experts_touched(config: Dict[str, Any], tokens: int) -> float:
    """Of the experts held in a layer, how many ``tokens`` tokens reach on
    average when each takes ``num_experts_per_tok`` of the published count
    at random: ``held * (1 - (1 - k / E) ^ tokens)``."""
    k, total = config["num_experts_per_tok"], config["published"]["n_routed_experts"]
    return config["n_routed_experts"] * (1.0 - (1.0 - k / total) ** tokens)


def _weight_bytes(config: Dict[str, Any], experts_read: float) -> float:
    """Bytes of every held matrix a pass reads, ``experts_read`` of the
    held experts a layer: bfloat16 but for the float32 router; the head,
    not the embedding (read by row)."""
    c = _counts(config)
    dense, expert = _layers(config)
    return 2.0 * ((dense + expert) * c["mla"] + dense * c["dense_ffn"]
                  + expert * (c["shared"] + c["expert"] * experts_read)
                  + c["head"]) + 4.0 * expert * c["router"]


def decode_step_bytes(config: Dict[str, Any], rows: int, position: int) -> float:
    """Bytes one cached step at ``position`` has to read: bfloat16 weights
    of attention, shared expert, dense layer and head, the float32 router,
    the latent cache up to the position (576 numbers a token and layer), and
    of the held experts a layer the expected number the step's rows touch."""
    cache = 2.0 * config["num_hidden_layers"] * rows * (position + 1) * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"])
    return _weight_bytes(config, experts_touched(config, rows)) + cache


def prefill_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations the prefill needs: two a multiply-add of every matrix a
    token passes (the held experts at the expected 8 * held / E a token),
    causal attention at (prompt + 1) / 2 keys a query over 192 + 128, and
    the head for each row's last token."""
    c = _counts(config)
    dense, expert = _layers(config)
    per_token = 2.0 * (
        (dense + expert) * c["mla"] + dense * c["dense_ffn"]
        + expert * (c["shared"] + c["router"] + c["expert"]
                    * config["num_experts_per_tok"] * config["n_routed_experts"]
                    / config["published"]["n_routed_experts"]))
    return (rows * prompt * per_token
            + (dense + expert) * mla_flash_flops(config, rows, prompt)
            + 2.0 * rows * c["head"])


def mla_flash_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations one layer's causal attention needs: a query against
    (prompt + 1) / 2 keys on average, 192 wide for the score and 128 for
    the value, two a multiply-add."""
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    return (2.0 * rows * config["num_attention_heads"] * prompt
            * (prompt + 1) / 2 * width)


def decode_min_bytes(config: Dict[str, Any], rows: int, prompt: int,
                     new_tokens: int) -> float:
    """Bytes one request has to move: every held weight once for the
    prefill (a prompt this long reaches every held expert), and each of the
    ``new_tokens - 1`` cached steps its :func:`decode_step_bytes`."""
    return (_weight_bytes(config, config["n_routed_experts"])
            + sum(decode_step_bytes(config, rows, prompt + j)
                  for j in range(max(new_tokens - 1, 0))))


# ---------------------------------------------------------------------------
# seeded weights


@functools.lru_cache(maxsize=None)
def _draw():
    """The jitted ``(key, std, dtype) -> DRAW`` numbers N(0, std^2) in
    ``dtype``, on the device."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key, std, dtype: (
        std * jax.random.normal(key, (DRAW,), jnp.float32)).astype(dtype),
        static_argnums=2)


# Elements of one draw. Every tensor is made of draws of this one shape (the
# last cut short), so that two compiled programs (bfloat16 and float32) make
# all 4.17 billion numbers in about a thousand calls: a draw compiled for
# each tensor's own shape cost 61 s of a cold set-up (24 compiles; my chip
# run, PR 31).
DRAW = 1 << 22


class Weights:
    """The generator's weights as seeded draws, one tensor of one layer at
    a time. ``shapes`` is the program's own parameter table (names, shapes
    and dtypes from ``jax.eval_shape`` of its init): the family decides the
    values, the program where they go."""

    def __init__(self, config: Dict[str, Any], seed: int, prompt_len: int,
                 new_tokens: int):
        import jax

        self.config, self.seed, self.prompt_len = config, seed, prompt_len
        prog = _program(config, new_tokens)
        one_row = np.zeros((1, prompt_len), np.int32)
        self.shapes = jax.eval_shape(
            lambda key: prog.init(key, prompt_ids=one_row)[0],
            jax.random.PRNGKey(0))

    @staticmethod
    def stacked(name: str) -> bool:
        return name.startswith(("dense/", "moe/"))

    def _std(self, name: str, shape) -> float:
        if name.endswith("select_bias"):
            return SELECT_BIAS_STD
        if name.startswith("tok/"):
            return 1.0
        if name.endswith(("kv_b_k/w", "kv_b_v/w")):    # [H, nope, c] / [H, c, v]
            return self.config["kv_lora_rank"] ** -0.5
        return shape[-2] ** -0.5                       # [..., in, out]

    def _draws(self, name: str, layer: int):
        """``(shape, [device arrays of DRAW numbers])`` of one tensor."""
        import jax
        import jax.numpy as jnp

        full = self.shapes[name]
        shape = tuple(full.shape[1:] if self.stacked(name) else full.shape)
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(self.seed), zlib.crc32(name.encode()) & 0x7fffffff),
            layer)
        std = jnp.float32(self._std(name, shape))
        return shape, [_draw()(jax.random.fold_in(key, i), std,
                               np.dtype(full.dtype))
                       for i in range(-(-int(np.prod(shape)) // DRAW))]

    def slab(self, name: str, layer: int = 0, on_host: bool = False):
        """One tensor (of one layer, for a stacked name) in the program's
        dtype for it, put together on the device or, ``on_host``, in numpy
        (the same numbers). Norm scales are ones."""
        import jax.numpy as jnp

        xp = np if on_host else jnp
        if name.endswith("/g"):
            full = self.shapes[name]
            return xp.ones(full.shape[1:] if self.stacked(name) else full.shape,
                           full.dtype)
        shape, parts = self._draws(name, layer)
        if on_host:
            parts = [np.asarray(p) for p in parts]
        flat = parts[0] if len(parts) == 1 else xp.concatenate(parts)
        return flat[:int(np.prod(shape))].reshape(shape)

    def host_params(self) -> Dict[str, np.ndarray]:
        """Every parameter under the program's names, on the host, made a
        layer at a time: the device never holds more than one slab."""
        out = {}
        for name, full in self.shapes.items():
            if self.stacked(name):
                out[name] = np.stack([self.slab(name, l, on_host=True)
                                      for l in range(full.shape[0])])
            else:
                out[name] = self.slab(name, on_host=True)
        return out

    # -- the same values under the reference's names, float32, on the device

    def _scope(self, layer: int):
        dense, _ = _layers(self.config)
        return ("dense/", layer) if layer < dense else ("moe/", layer - dense)

    def reference_attention(self, layer: int) -> Dict[str, Any]:
        import jax.numpy as jnp

        scope, l = self._scope(layer)
        return reference_attention(
            lambda n: self.slab(scope + n, l).astype(jnp.float32))

    def reference_ffn(self, layer: int) -> Dict[str, Any]:
        import jax.numpy as jnp

        scope, l = self._scope(layer)
        return reference_ffn(
            lambda n: self.slab(scope + n, l).astype(jnp.float32),
            dense=scope == "dense/")

    def reference_ends(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        return reference_ends(lambda n: self.slab(n).astype(jnp.float32))


def _each_row(fn, rows, lp, sh) -> None:
    """``rows[i] = fn(rows[i], lp, sh)``, one execution in flight: queued
    all at once, every execution's temporaries are set aside at once."""
    import jax

    for i, x in enumerate(rows):
        rows[i] = jax.block_until_ready(fn(x, lp, sh))


def reference_attention(get) -> Dict[str, Any]:
    """One layer's attention parameters under the reference's names;
    ``get(name)`` gives the program's float32 tensor of that layer, by its
    name inside the layer's scope. The published ``kv_b_proj`` is put
    together again from the two halves the program holds."""
    import jax.numpy as jnp

    g = lambda n: get("mla/" + n)
    k = jnp.transpose(g("kv_b_k/w"), (2, 0, 1))        # [c, H, nope]
    v = jnp.transpose(g("kv_b_v/w"), (1, 0, 2))        # [c, H, v]
    kv_b = jnp.concatenate([k, v], axis=-1)            # per head [k_nope | v]
    return {"attn_norm": g("attn_norm/g"), "q_a": g("q_a/w"),
            "q_norm": g("q_norm/g"), "q_b": g("q_b/w"), "kv_a": g("kv_a/w"),
            "kv_norm": g("kv_norm/g"),
            "kv_b": kv_b.reshape(kv_b.shape[0], -1), "o": g("o/w")}


def reference_ffn(get, dense: bool) -> Dict[str, Any]:
    if dense:
        return {"ffn_norm": get("ffn/ffn_norm/g"), "gate": get("ffn/gate/w"),
                "up": get("ffn/up/w"), "down": get("ffn/down/w")}
    return {"ffn_norm": get("shared/ffn_norm/g"),
            "router": get("experts/router/w"),
            "select_bias": get("experts/router/select_bias"),
            "shared_gate": get("shared/gate/w"), "shared_up": get("shared/up/w"),
            "shared_down": get("shared/down/w"),
            "experts_gate": get("experts/gate/w"),
            "experts_up": get("experts/up/w"),
            "experts_down": get("experts/down/w")}


def reference_ends(get) -> Dict[str, Any]:
    return {"emb": get("tok/embedding_0/w"), "final_norm": get("final_norm_0/g"),
            "head": get("lm_head_0/w")}


def reference_params(params: Dict[str, Any], config: Dict[str, Any]):
    """A whole parameter dict of the program under the reference's names,
    float32 (the tests' small sizes; the chip check streams, see
    :func:`reference_logits`)."""
    import jax.numpy as jnp

    dense, expert = _layers(config)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layers = []
    for scope, n in (("dense/", dense), ("moe/", expert)):
        for l in range(n):
            get = lambda name, scope=scope, l=l: f32(params[scope + name][l])
            layers.append({**reference_attention(get),
                           **reference_ffn(get, scope == "dense/")})
    return {**reference_ends(lambda n: f32(params[n])), "layers": layers}


# ---------------------------------------------------------------------------
# the system under test: serving


def decoder_params(config: Dict[str, Any], seed: int, prompt_len: int,
                   new_tokens: int) -> Weights:
    """Not the weights but their seeded maker: the server holds the only
    copy on the device, and :func:`served_check` makes one float32 layer
    at a time from this."""
    return Weights(config, seed, prompt_len, new_tokens)


def export_decoder(config: Dict[str, Any], seed: int, dirname: str,
                   prompt_len: int, new_tokens: int, buckets) -> None:
    """``fleet.decode.export_decoder`` of the seeded weights, handed over
    on the host, with the given batch buckets."""
    from paddle_tpu.fleet import decode
    from paddle_tpu.models import kimi_k2

    buckets = sorted(int(b) for b in buckets)
    weights = Weights(config, seed, prompt_len, new_tokens)
    decode.export_decoder(dirname, program_config(config), new_tokens,
                          np.zeros((buckets[-1], prompt_len), np.int32),
                          params=weights.host_params(),
                          batch_buckets=buckets, model=kimi_k2)


def reference_logits(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
                     first: int, edit=None) -> np.ndarray:
    """The reference's logits ``[rows, s - first, vocab]`` for ``ids``, a
    layer's attention and then its FFN at a time, a row at a time, each
    part's float32 weights freed before the next is made. ``edit(shape,
    part, layer, params)`` may change what the reference is given (the
    sensitivity runs)."""
    import jax
    import jax.numpy as jnp

    sh = reference.shape_of(config)
    edit = edit or (lambda sh_, part, layer, lp: (sh_, lp))
    attn = jax.jit(reference.attention_part, static_argnums=2)
    ffn = jax.jit(reference.ffn_part, static_argnums=2)
    ends = weights.reference_ends()
    rows = [reference.embed(ends["emb"], jnp.asarray(r)[None]) for r in ids]
    del ends
    with jax.default_matmul_precision("highest"):
        for layer in range(config["num_hidden_layers"]):
            for part, make, fn in (("attention", weights.reference_attention, attn),
                                   ("ffn", weights.reference_ffn, ffn)):
                sh_l, lp = edit(sh, part, layer, make(layer))
                _each_row(fn, rows, lp, sh_l)
                del lp
        ends = weights.reference_ends()
        out = [reference.head_logits(x[:, first:], ends["final_norm"],
                                     ends["head"], sh) for x in rows]
    return np.concatenate([np.asarray(o) for o in out], axis=0)


def served_check(config: Dict[str, Any], params: Weights,
                 prompt_ids: np.ndarray, served: np.ndarray, eos_id: int = 2,
                 edit=None) -> Dict[str, Any]:
    """One full reference forward over prompt + served ids; at every
    generated position (up to a row's first end-of-sequence id, after which
    the generator forces it) the served token's reference logit must be
    within ``LOGIT_MARGIN`` of the largest, the mean of those gaps within
    ``MEAN_GAP_LIMIT``, and at least ``AGREE_FLOOR`` of the tokens the
    reference's own argmax."""
    prompt_ids, served = np.asarray(prompt_ids), np.asarray(served)
    p = prompt_ids.shape[1]
    ids = np.concatenate([prompt_ids, served[:, :-1]], axis=1).astype(np.int32)
    # a request's rows at a time: 8 rows of float32 activations and one
    # part's weights are what fits beside the server's copy of the model
    logits = np.concatenate([
        reference_logits(config, params, ids[i:i + CHECK_ROWS_AT_ONCE], p - 1,
                         edit)
        for i in range(0, len(ids), CHECK_ROWS_AT_ONCE)])
    got = np.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    gap = logits.max(-1) - got
    ended = np.cumsum(served == eos_id, axis=1) - (served == eos_id) > 0
    gap = np.where(ended, 0.0, gap)
    top2 = np.sort(logits, axis=-1)[..., -2:]
    agree = float(((gap == 0) | ended).mean())
    # what a plainly wrong token would read: the gap of another id at each
    # position (the served id plus one), its first percentile
    other = np.take_along_axis(logits, ((served + 1) % logits.shape[-1])[..., None],
                               axis=-1)[..., 0]
    return {"ok": bool(np.isfinite(gap).all() and agree >= AGREE_FLOOR
                       and gap.mean() <= MEAN_GAP_LIMIT
                       and gap.max() <= LOGIT_MARGIN),
            "rows": int(served.shape[0]), "worst_logit_gap": float(gap.max()),
            "mean_logit_gap": float(gap.mean()),
            "argmax_agree": agree,
            "other_id_gap_p01": float(np.percentile(logits.max(-1) - other, 1)),
            "distinct_ids": int(len(np.unique(served))),
            "logit_std": float(logits.std()),
            "top_above_mean": float((logits.max(-1) - logits.mean(-1)).mean()),
            "top_two_apart": float((top2[..., 1] - top2[..., 0]).mean())}
