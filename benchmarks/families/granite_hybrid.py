"""The ``granite_hybrid`` family: from a configuration file to the generator
under test, its seeded weights, its operation and byte counts, and its check
against the plain reference (``benchmarks/reference/granite_hybrid.py``).

The configuration file keeps the published keys of ``config.json`` and says
what of the model is held here: ``num_hidden_layers`` (with
``layer_indices``, the published indices of the layers held),
``num_local_experts`` (the experts held) and ``vocab_size`` (the rows held)
are cut, the ``published`` group has their published values and the
``deployment`` group the stage they are a rank of. No training path
(``models/granite_hybrid.py``).

The weights are the family's, not the program's initialisers, as in
``families/trinity.py`` (whose :class:`~benchmarks.families.trinity.Weights`
this one extends): every tensor of every layer is seeded draws on the device,
so the export can make 9.5 GB a tensor at a time and hand them over on the
host, and the check can make one layer's half, or one expert, again in
float32 without ever holding a second copy of the model beside the server's.

The counts are those of the algorithm, whatever the program does to carry
them out: a state read once and written once a step, the experts a step's
rows touch, the keys up to the position.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict

import numpy as np

from benchmarks.families import brumby, kimi_k2, trinity
from benchmarks.reference import granite_hybrid as reference

# The check: over 16 served rows x 256 tokens, the gap between the
# reference's largest logit and its logit of the served token, in units of
# the reference's own logit deviation (0.060 under these weights; the largest
# stands 4.27 above the mean and 0.25 above the second). Three limits on the
# tokens, as the other serve families have them, and a fourth on a carried
# state itself, each between what sound seeds read and what faults read at
# the published widths on the chip, **all on the build that stands** (my chip
# runs, PR 49: calls 212 and 274, nine sound runs of 16 rows on nine seeds,
# and ``tools/granite_sensitivity.py --forms`` in call 274, a fault on two
# rows of 256 tokens; PERF.md section 6 prints every reading). The same
# weights and prompts through the other walks of the expert pairs (the
# scatter-add walk in blocks of 512; gathers cut 2,048 tokens a walk in
# blocks of 4,096) read 80.7% / 0.0203 and 78.7% / 0.0202 on four rows, as
# sound as the walk the cell takes. (Two earlier, uncommitted builds of this
# PR read 48 to 53% and 0.17 to 0.21 with nothing known wrong, calls 203 and
# 204, and the limits first stood between this build's sound readings and
# that build's faults; what made those builds noisier was not how the pairs
# were cut, PERF.md section 6, and is in no tree.)
# (1) AGREE_FLOOR, the share of tokens that are the reference's own argmax.
# Sound 78.0 to 80.9% (mean 79.3%, deviation 0.9%); the state zeroed between
# pieces 58.8%, a query head on the neighbouring key head 27.7%, the scale
# 1 / sqrt(128) 19.7%, the softmax over all 72 logits 18.4%, the reference
# in an 8-bit float (the precision below the stated one) 18.2%,
# ``residual_multiplier`` left out 8.8%, the norm before the gate 6.8%,
# ``D x`` dropped 0.6%. The floor is ten points under the lowest sound
# reading and nine over the nearest fault.
# (2) MEAN_GAP_LIMIT: the mean gap read 0.0193 to 0.0250 with nothing wrong;
# the faults 0.0834 (the state zeroed) to 3.84, the 8-bit reference 0.661.
# The limit is the geometric middle of 0.025 and 0.083, a factor of 1.8
# from either.
# (3) LOGIT_MARGIN guards against a garbled id only: a row of unrelated ids
# loses 4.27 on average and more than 6 somewhere in 256 tokens; one token
# lost up to 0.94 with nothing wrong and 0.79 to 6.9 under the faults, so
# no margin parts them.
# A state rounded to bfloat16 at every hand-over read 79.1% / 0.0213 / 0.47
# on the tokens where sound read 80.5% / 0.0185 / 0.45 on the same two rows:
# no limit on tokens sees it, so a state is read itself:
# (4) CARRIED_ERROR_LIMIT: one more request through the timed server after
#     the window returns, beside its ids, what the first lane group of heads
#     of the first Mamba-2 layer's recurrence was given at every position
#     and their state as the request left it (``models/granite_hybrid.py``:
#     the ``audit_*`` outputs); the largest relative error over the rows
#     against ``reference.carried_state`` of the same inputs, the definition
#     in float64. As served 3.3e-6 to 7.7e-5 over fifteen runs (8 pieces and
#     255 steps) / a state rounded to bfloat16 at every hand-over, the
#     nearest precision below the float32 the configuration states, 3.2e-3
#     and 4.5e-3: the limit is the geometric middle of 7.7e-5 and 3.2e-3, a
#     factor of 6.4 from either.
AGREE_FLOOR = 0.68
MEAN_GAP_LIMIT = 0.045
LOGIT_MARGIN = 6.0
CARRIED_ERROR_LIMIT = 5e-4

SERVE_CHECK_ROWS = 16
# rows of the check a block of the reference's FFN half (an FFN is a token's
# own): 4 x 2,303 tokens x 4,096 float32 are 151 MB, thrice (the input, the
# normed input, the sum) for each of four blocks beside the served weights
CHECK_ROWS_AT_ONCE = 4
# queries a block of the reference's attention: 512 x 32 heads x 2,303 keys
# of float32 scores are 151 MB
CHECK_QUERY_BLOCK = 512
# rows of the embedding a block of the check's logits
CHECK_HEAD_BLOCK = 12544


# ---------------------------------------------------------------------------
# configuration


def program_config(config: Dict[str, Any]):
    """The ``models/granite_hybrid.py`` config for a configuration file."""
    from paddle_tpu.models import granite_hybrid

    indices, dep = config["layer_indices"], config["deployment"]
    assert list(indices) == list(range(indices[0], indices[0] + len(indices)))
    assert len(indices) == config["num_hidden_layers"]
    assert config["mamba_n_groups"] == 1 and config["mamba_expand"] * config[
        "hidden_size"] == config["mamba_n_heads"] * config["mamba_d_head"]
    return granite_hybrid.base_config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["assumed"]["head_dim"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        num_local_experts=config["published"]["num_local_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        first_layer=indices[0], experts_held=config["num_local_experts"],
        first_expert=dep["expert_rank"] * config["num_local_experts"],
        prefill_chunk=config["run"]["chunk"], dtype=config["run"]["dtype"],
        # (a toy states a shorter block, so that its pieces walk several)
        **{k: config["run"][k] for k in ("pair_block",) if k in config["run"]})


def _program(config: Dict[str, Any], new_tokens: int):
    import paddle_tpu as pt
    from paddle_tpu.models import granite_hybrid

    return pt.build(granite_hybrid.make_generator(program_config(config),
                                                  max_new_tokens=new_tokens))


# ``prompts(vocab, rows, length, seed, n)``: ids drawn evenly from the held
# rows of the vocabulary (pad 0 and the generator's bos 1 and eos 2 never
# drawn), the ``kimi_k2`` family's and for its reason: a router sees the ids
prompts = kimi_k2.prompts


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, from the configuration alone


def _shape(config: Dict[str, Any]) -> reference.Shape:
    return reference.shape_of(config)


def _kinds(config: Dict[str, Any]):
    return [kind for _, kind in reference.layers_of(config)]


def _counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameters of the matrices by part, of one layer."""
    sh, d = _shape(config), config["hidden_size"]
    qw, kvw = sh.heads * sh.head_dim, sh.kv_heads * sh.head_dim
    return {"mamba": d * (2 * sh.d_inner + 2 * sh.d_state + sh.m_heads)
                     + sh.d_inner * d,
            "attention": 2 * d * qw + 2 * d * kvw,
            "shared": 3 * d * config["shared_intermediate_size"],
            "router": d * sh.routed,
            "expert": 3 * d * config["intermediate_size"],
            "head": d * config["vocab_size"]}


def experts_touched(config: Dict[str, Any], tokens: int) -> float:
    """Of the experts held in a layer, how many ``tokens`` tokens reach on
    average when each takes ``num_experts_per_tok`` of the published count
    at random: ``held * (1 - (1 - k / E) ^ tokens)``."""
    sh = _shape(config)
    return sh.held * (1.0 - (1.0 - sh.top_k / sh.routed) ** tokens)


def state_bytes(config: Dict[str, Any], rows: int) -> float:
    """Bytes of one Mamba-2 layer's float32 state and bfloat16 convolution
    tail, ``rows`` rows."""
    sh = _shape(config)
    return rows * (4.0 * sh.d_inner * sh.d_state
                   + 2.0 * (sh.d_conv - 1) * (sh.d_inner + 2 * sh.d_state))


def decode_step_bytes(config: Dict[str, Any], rows: int, position: int) -> float:
    """Bytes one step at ``position`` has to move: the bfloat16 matrices of
    mixers and shared experts once for the batch, the float32 routers, of the
    held experts a layer the expected number the step's rows touch, the tied
    head once (the token's row of it as the embedding is nothing beside
    that), every Mamba-2 layer's state and tail read and written once, the
    attention layer's keys and values up to the position."""
    sh, c, kinds = _shape(config), _counts(config), _kinds(config)
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    weights = 2.0 * (n_mamba * c["mamba"] + n_attn * c["attention"]
                     + len(kinds) * (c["shared"] + c["expert"]
                                     * experts_touched(config, rows))
                     + c["head"]) + 4.0 * len(kinds) * c["router"]
    keys = 2.0 * 2 * rows * (position + 1) * sh.kv_heads * sh.head_dim
    return (weights + n_mamba * 2.0 * state_bytes(config, rows)
            + n_attn * keys)


def ssd_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """The products one Mamba-2 layer's chunked recurrence needs over a
    prompt, two operations a multiply-add: a chunk of ``Q`` tokens has ``Q (Q
    + 1) / 2`` (query, key) pairs; ``C . B`` over ``d_state`` once a pair,
    and a head's pair times ``head_dim``, its read of the carried state and
    its update of it (``Q x d_state x head_dim`` each)."""
    sh, q = _shape(config), config["mamba_chunk_size"]
    total = 0.0
    for start in range(0, prompt, q):
        n = min(q, prompt - start)
        pairs = n * (n + 1) / 2.0
        total += 2.0 * (pairs * sh.d_state + sh.m_heads * (
            pairs * sh.m_head_dim + 2.0 * n * sh.d_state * sh.m_head_dim))
    return rows * total


def attention_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """One attention layer over a prompt: every key up to the query's own, a
    ``head_dim``-wide score and value for every query head."""
    sh = _shape(config)
    return 2.0 * rows * sh.heads * (prompt * (prompt + 1) / 2.0) * 2 * sh.head_dim


def prefill_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations the prefill needs: two a multiply-add of every matrix a
    token passes (the held experts at the expected ``k * held / E`` a token),
    each Mamba-2 layer's recurrence, the attention layers' pairs, and the head
    for each row's last token."""
    sh, c, kinds = _shape(config), _counts(config), _kinds(config)
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    per_token = 2.0 * (n_mamba * c["mamba"] + n_attn * c["attention"]
                       + len(kinds) * (c["shared"] + c["router"] + c["expert"]
                                       * sh.top_k * sh.held / sh.routed))
    return (rows * prompt * per_token
            + n_mamba * ssd_flops(config, rows, prompt)
            + n_attn * attention_flops(config, rows, prompt)
            + 2.0 * rows * c["head"])


def kernel_counts(config: Dict[str, Any], rows: int, prompt: int, kernel: str):
    """``(operations, bytes, calls)`` all of one request's calls of
    ``kernel`` need (``readers/kernel_roofline.py``), or None for a kernel
    this family does not count. ``ssd_fwd``: a call a Mamba-2 layer and piece;
    the needed products (:func:`ssd_flops`); bfloat16 ``u`` and ``y`` of every
    position, ``B`` and ``C``, the float32 running sums in their two
    layouts, and a call's states read and written once. ``flash_fwd``: a
    call an attention layer and piece; q and o of every position and the keys
    and values a piece's queries reach, each key/value head once."""
    sh, kinds = _shape(config), _kinds(config)
    chunk = min(config["run"]["chunk"], prompt)
    pieces = -(-prompt // chunk)
    if kernel == "ssd_fwd":
        n = kinds.count("mamba")
        moved = (rows * prompt * (2.0 * 2 * sh.d_inner + 2.0 * 2 * sh.d_state
                                  + 2 * 4.0 * sh.m_heads)
                 + pieces * 2.0 * 4 * rows * sh.d_inner * sh.d_state)
        return n * ssd_flops(config, rows, prompt), n * moved, n * pieces
    if kernel == "flash_fwd":
        n = kinds.count("attention")
        moved = sum(2.0 * rows * (2 * min(chunk, prompt - p0) * sh.heads
                                  * sh.head_dim + 2 * min(p0 + chunk, prompt)
                                  * sh.kv_heads * sh.head_dim)
                    for p0 in range(0, prompt, chunk))
        return n * attention_flops(config, rows, prompt), n * moved, n * pieces
    return None


# ---------------------------------------------------------------------------
# seeded weights


EMBEDDING, FINAL = "tok/embedding_0/w", "final_norm_0/g"

# What is scaled so that a mechanism moves a logit (``assumed.weights`` in
# the configuration file says the same in words). Every half sees a normed
# input, so what it adds to the stream is set by its own matrices: the gains
# below make each of the twenty halves add about 1.0 an element after the
# ``residual_multiplier`` (readings in PERF.md section 6, PR 49, from
# ``tools/granite_sensitivity.py --scales``), beside an embedding of 12 x
# 0.015 = 0.18 an element.
# The head is the embedding: a token's own row meets itself in the last
# hidden state, and its logit stands ``sqrt(d) * e / rms(h)`` deviations
# above the rest (``e`` the embedding's size in the stream, ``h`` the last
# hidden state); at rows N(0, 1) that is tens of deviations and every greedy
# token repeats the last. At 0.015 it is 2 to 3, beside a largest logit 4.1
# above the mean: it tilts and does not decide. Smaller still, and leaving
# ``residual_multiplier`` out would move nothing (every half scaled alike
# against an embedding that is nothing).
EMBED_STD = 0.015
# B's and C's columns of ``W_in``: at fan-in scale B and C are 0.35 an
# element after the convolution and the state adds a tenth of what the skip
# ``D x`` adds; nothing carried across a piece would reach a logit.
BC_GAIN = 4.0
# ... and the convolution's bias of B's and C's channels lowered by one
# deviation of their input (4 x 0.58). B and C pass a SiLU: at a bias about
# zero their mean is half their size, ``C_t . B_s`` is a positive constant
# for every pair of tokens (108 +- 35), the state a running mean of ``dt x``
# and 27% of a mixer's output a vector every token shares; the routers turn
# that into expert loads that differ from layer to layer and seed to seed
# (the pairs held here 45 to 55% of a layer's, 48.7 and 50.8% of a request's
# on two seeds), which moves the cell's time by the seed as
# ``families/trinity.py`` found of flat attention. Lowered, B's shared part
# falls from 49 to 23% of its size, a mixer's output's from 27 to 16%, a
# router's input's from 24 to 15%, and the pairs held here are 47.7 to 53.5%
# of a layer's and 49.8, 50.3 and 51.1% of a request's on three seeds
# (builder, PR 49: the reference on the CPU at the published widths, one row
# of 256 and 512 tokens; PERF.md section 6).
BC_BIAS_SHIFT = -2.3
# ``W_q`` and ``W_k``: the scale is 1 / 128 and not 1 / sqrt(128), so at
# fan-in scale a score deviates by 0.09, the softmax is flat and the layer
# adds the mean of the values (``families/trinity.py``, ``Q_NORM_GAIN``, says
# what that does to the routers); at 6 a score deviates by 3.2.
QK_GAIN = 6.0
MAMBA_OUT_GAIN = 4.5
ATTN_OUT_GAIN = 9.0
SHARED_DOWN_GAIN = 6.5
# the held experts' part (about half the selected ten, weights summing to
# about a half) at two fifths of the shared expert's size. At the shared
# expert's own size (a gain of 24) the served tokens agreed with the
# reference's argmax 51% of the time and lost 0.175 deviations on average
# (my chip run, PR 49, call 203): the tenth and eleventh largest of 72 router
# logits lie 0.06 apart, the bfloat16 stream moves a logit by 0.01, so one
# token in five changes an expert in a layer, and each such change moved the
# stream by 5%.
EXPERT_DOWN_GAIN = 10.0
DT_MIN, DT_MAX = 0.001, 0.1
A_MIN, A_MAX = 1.0, 16.0


class Weights(trinity.Weights):
    """The generator's weights as seeded draws, a tensor at a time
    (``trinity.Weights`` makes the draws): every matrix N(0, 1 / fan_in) but
    for the gains above, the embedding N(0, 0.015^2), norm gains 1; and the
    recurrence as Mamba-2 is published to start: ``A`` uniform in [1, 16]
    (``A_log`` its logarithm), ``dt_bias`` such that its softplus is
    log-uniform in [0.001, 0.1], ``D = 1``, the convolution's taps and bias
    U(-1/2, 1/2) (B's and C's channels' bias lowered by ``BC_BIAS_SHIFT``)."""

    def __init__(self, config: Dict[str, Any], seed: int, prompt_len: int,
                 new_tokens: int):
        import jax

        self.config, self.seed = config, seed
        prog = _program(config, new_tokens)
        one_row = np.zeros((1, prompt_len), np.int32)
        self.shapes = jax.eval_shape(
            lambda key: prog.init(key, prompt_ids=one_row)[0],
            jax.random.PRNGKey(0))

    def _std(self, name: str, shape) -> float:
        if name.startswith("tok/"):
            return EMBED_STD
        fan_in = shape[-2] ** -0.5                      # [..., in, out]
        gains = {"mixer/out/w": MAMBA_OUT_GAIN, "mixer/o/w": ATTN_OUT_GAIN,
                 "mixer/q/w": QK_GAIN, "mixer/k/w": QK_GAIN,
                 "shared/down/w": SHARED_DOWN_GAIN,
                 "experts/down/w": EXPERT_DOWN_GAIN}
        return gains.get(name.split("/", 1)[1], 1.0) * fan_in

    def _uniform(self, name: str, on_host: bool):
        """U(0, 1) of the tensor's shape, float32, from the seed and the
        name."""
        import jax

        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 zlib.crc32(name.encode()) & 0x7fffffff)
        u = jax.random.uniform(key, self.shapes[name].shape)
        return np.asarray(u) if on_host else u

    def slab(self, name: str, on_host: bool = False, part=None):
        import jax.numpy as jnp

        xp = np if on_host else jnp
        full = self.shapes[name]
        if name.endswith("mixer/d"):
            return xp.ones(full.shape, full.dtype)
        if name.endswith("conv/w"):
            return self._uniform(name, on_host) - 0.5
        if name.endswith("conv/b"):
            first_bc = _shape(self.config).d_inner
            return (self._uniform(name, on_host) - 0.5 + xp.where(
                xp.arange(full.shape[0]) >= first_bc, BC_BIAS_SHIFT, 0.0
            ).astype(xp.float32))
        # a head's numbers: made on the host either way, so that the served
        # copy and the check's are the same bits (the two logarithms are not)
        if name.endswith("/a_log"):
            return xp.asarray(np.log(A_MIN + (A_MAX - A_MIN)
                                     * self._uniform(name, True)))
        if name.endswith("dt/b"):
            dt = np.exp(self._uniform(name, True)
                        * (math.log(DT_MAX) - math.log(DT_MIN))
                        + math.log(DT_MIN))
            return xp.asarray(dt + np.log(-np.expm1(-dt)))      # softplus^-1
        if name.endswith("mixer/in/w"):
            # B's and C's columns at BC_GAIN, the rest at fan-in scale
            sh = _shape(self.config)
            w = super().slab(name, on_host)
            lo, hi = 2 * sh.d_inner, 2 * sh.d_inner + 2 * sh.d_state
            cols = xp.arange(full.shape[1])
            scale = xp.where((cols >= lo) & (cols < hi), BC_GAIN, 1.0)
            return (w.astype(xp.float32) * scale.astype(xp.float32)
                    ).astype(full.dtype)
        return super().slab(name, on_host, part)

    # -- the same values under the reference's names, float32, on the device

    def reference_mixer(self, index: int, kind: str) -> Dict[str, Any]:
        return reference_mixer(self._get(index), kind)

    def reference_ffn(self, index: int) -> Dict[str, Any]:
        """The FFN half's parameters but the experts' banks."""
        return reference_ffn(self._get(index), banks=False)

    def reference_ends(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        return reference_ends(lambda n: self.slab(n).astype(jnp.float32))


def reference_mixer(get, kind: str) -> Dict[str, Any]:
    """One layer's mixer under the reference's names; ``get(name)`` gives
    the program's float32 tensor of that layer by its name in the layer's
    scope."""
    g = lambda n: get("mixer/" + n)
    if kind == reference.MAMBA:
        return {"norm": g("norm/g"), "in_proj": g("in/w"), "conv_w": g("conv/w"),
                "conv_b": g("conv/b"), "dt_bias": g("dt/b"), "a_log": g("a_log"),
                "d_skip": g("d"), "gate_norm": g("gate_norm/g"),
                "out_proj": g("out/w")}
    return {"norm": g("attn_norm/g"), "q": g("q/w"), "k": g("k/w"),
            "v": g("v/w"), "o": g("o/w")}


def reference_ffn(get, banks: bool = True) -> Dict[str, Any]:
    lp = {"ffn_norm": get("shared/ffn_norm/g"),
          "router": get("experts/router/w"),
          "shared_gate": get("shared/gate/w"), "shared_up": get("shared/up/w"),
          "shared_down": get("shared/down/w")}
    if banks:
        lp.update(experts_gate=get("experts/gate/w"),
                  experts_up=get("experts/up/w"),
                  experts_down=get("experts/down/w"))
    return lp


def reference_ends(get) -> Dict[str, Any]:
    return {"emb": get(EMBEDDING), "final_norm": get(FINAL)}


def reference_params(params: Dict[str, Any], config: Dict[str, Any]):
    """A whole parameter dict of the program under the reference's names,
    float32 (the tests' small sizes; the chip check streams, see
    :func:`reference_hidden`)."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layers = []
    for i, kind in reference.layers_of(config):
        get = lambda name, i=i: f32(params[f"layer_{i}/{name}"])
        layers.append({**reference_mixer(get, kind), **reference_ffn(get)})
    return {**reference_ends(lambda n: f32(params[n])), "layers": layers}


# ---------------------------------------------------------------------------
# the system under test: serving


def decoder_params(config: Dict[str, Any], seed: int, prompt_len: int,
                   new_tokens: int) -> Weights:
    """Not the weights but their seeded maker: the server holds the only
    copy on the device, and :func:`served_check` makes one float32 part at
    a time from this."""
    return Weights(config, seed, prompt_len, new_tokens)


def export_decoder(config: Dict[str, Any], seed: int, dirname: str,
                   prompt_len: int, new_tokens: int, buckets) -> None:
    """``fleet.decode.export_decoder`` of the seeded weights, handed over
    on the host, with the given batch buckets."""
    from paddle_tpu.fleet import decode
    from paddle_tpu.models import granite_hybrid

    buckets = sorted(int(b) for b in buckets)
    weights = Weights(config, seed, prompt_len, new_tokens)
    decode.export_decoder(dirname, program_config(config), new_tokens,
                          np.zeros((buckets[-1], prompt_len), np.int32),
                          params=weights.host_params(),
                          batch_buckets=buckets, model=granite_hybrid)


@functools.lru_cache(maxsize=None)
def _jitted():
    """The reference's parts as the check applies them, compiled once a
    shape: ``(mixer_part a row at a time, routed_setup, add_expert,
    ffn_close)``; the expert's index is traced (``families/trinity.py`` says
    why)."""
    import jax

    return (jax.jit(lambda x, lp, sh, kind: jax.lax.map(
                lambda row: reference.mixer_part(row[None], lp, sh, kind)[0], x),
                static_argnums=(2, 3)),
            # (a lambda each: jit keeps its traces by the function it wraps,
            # and ``tools/granite_sensitivity.py`` clears this cache to have a
            # name it wrapped in the reference traced again)
            jax.jit(lambda x, lp, sh: reference.routed_setup(x, lp, sh),
                    static_argnums=2),
            jax.jit(lambda *a: reference.add_expert(*a)),
            jax.jit(lambda x, f, sh: reference.ffn_close(x, f, sh),
                    static_argnums=2))


def reference_hidden(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
                     first: int, edit=None):
    """The reference's last hidden state ``[rows, s - first, d]`` (on the
    device, before the final norm) for the sequences ``ids [rows, s]``: a
    layer's mixer and then its FFN half at a time, the mixer a sequence at a
    time, the FFN half ``CHECK_ROWS_AT_ONCE`` rows at a time and **an expert
    at a time**, each part's float32 weights made again from the seed once
    for all the rows and freed before the next is made. ``edit(shape, part,
    layer, kind, params) -> (shape, params)`` may change what the reference
    is given (the sensitivity runs); ``layer`` counts the held layers from
    0, ``part`` is ``"mixer"``, ``"ffn"``, ``"expert"`` or ``"ends"``."""
    import jax
    import jax.numpy as jnp

    sh = reference.shape_of(config, query_block=CHECK_QUERY_BLOCK)
    edit = edit or (lambda sh_, part, layer, kind, lp: (sh_, lp))
    mixer, setup, add, close = _jitted()
    with jax.default_matmul_precision("highest"):
        _, ends = edit(sh, "ends", 0, None, weights.reference_ends())
        x = reference.embed(ends["emb"], jnp.asarray(ids), sh)
        del ends
        spans = [(a, min(a + CHECK_ROWS_AT_ONCE, len(ids)))
                 for a in range(0, len(ids), CHECK_ROWS_AT_ONCE)]
        for layer, (index, kind) in enumerate(reference.layers_of(config)):
            sh_l, lp = edit(sh, "mixer", layer, kind,
                            weights.reference_mixer(index, kind))
            x = jax.block_until_ready(mixer(x, lp, sh_l, kind))
            del lp
            sh_l, lp = edit(sh, "ffn", layer, kind, weights.reference_ffn(index))
            blocks = [(x[a:b],) + setup(x[a:b], lp, sh_l) for a, b in spans]
            for j in range(sh_l.held):
                _, expert = edit(sh, "expert", layer, kind, dict(zip(
                    ("gate", "up", "down"), weights.reference_expert(index, j))))
                blocks = [(xb, m, idx, w, add(
                    f, m, idx, w, sh_l.rank * sh_l.held + j, expert["gate"],
                    expert["up"], expert["down"])) for xb, m, idx, w, f in blocks]
                del expert
            x = jax.block_until_ready(jnp.concatenate(
                [close(xb, f, sh_l) for xb, _, _, _, f in blocks], axis=0))
            del lp, blocks
    return x[:, first:]


def reference_logits(config: Dict[str, Any], weights: Weights, hidden, edit=None):
    """``hidden [rows, n, d] ->`` the reference's logits, a row ``[n,
    vocab]`` at a time (a generator), the embedding's rows in blocks of
    ``CHECK_HEAD_BLOCK``."""
    import jax
    import jax.numpy as jnp

    sh = _shape(config)
    _, ends = (edit or (lambda sh_, part, layer, kind, lp: (sh_, lp)))(
        sh, "ends", 0, None, weights.reference_ends())
    block = jax.jit(lambda h, part: reference.head_logits(
        h, ends["final_norm"], part, sh))
    for row in hidden:
        yield jnp.concatenate(
            [block(row, ends["emb"][c:c + CHECK_HEAD_BLOCK])
             for c in range(0, ends["emb"].shape[0], CHECK_HEAD_BLOCK)], axis=1)


def carried_check(audit: Dict[str, np.ndarray], a_log, head_dim: int
                  ) -> Dict[str, Any]:
    """A request's ``audit_*`` outputs (``models/granite_hybrid.py``) against
    the definition: a row at a time, the relative error (Frobenius) of
    ``audit_state`` against ``reference.carried_state`` of what the
    recurrence was given; the largest must be finite and within
    ``CARRIED_ERROR_LIMIT``. ``a_log [heads]``: the audited layer's."""
    a = -np.exp(np.asarray(a_log, np.float64))
    err = []
    for got, dt, x, b in zip(audit["audit_state"], audit["audit_dt"],
                             audit["audit_x"], audit["audit_b"]):
        want = reference.carried_state(dt, x, b, a, head_dim)
        err.append(np.linalg.norm(np.asarray(got, np.float64) - want)
                   / np.linalg.norm(want))
    err = np.asarray(err)
    return {"ok": bool(np.isfinite(err).all()
                       and err.max() <= CARRIED_ERROR_LIMIT),
            "carried_error": float(err.max()),
            "positions": int(audit["audit_dt"].shape[1])}


def audited_a_log(config: Dict[str, Any], weights: Weights):
    """``A_log`` of the layer a request audits."""
    from paddle_tpu.models import granite_hybrid

    index = [i for i, kind in reference.layers_of(config)
             if kind == reference.MAMBA][granite_hybrid.AUDIT_LAYER]
    return np.asarray(weights.slab(f"layer_{index}/mixer/a_log", on_host=True))


# ``served_audit(prompt_ids)``: one more request through the process's one
# ready server, every output fetched (the ``brumby`` family's)
served_audit = brumby.served_audit


def served_check(config: Dict[str, Any], params: Weights,
                 prompt_ids: np.ndarray, served: np.ndarray,
                 eos_id: int = 2, edit=None, audit=None) -> Dict[str, Any]:
    """One full reference forward over prompt + served ids; at every
    generated position (up to a row's first end-of-sequence id, after which
    the generator forces it) the served token's reference logit, in
    deviations of the reference's logits, must be within ``LOGIT_MARGIN`` of
    the largest, the mean of those gaps within ``MEAN_GAP_LIMIT``, and at
    least ``AGREE_FLOOR`` of the tokens the reference's own argmax. Then a
    state itself: ``audit``, a request's outputs for these prompts (when
    not given, :func:`served_audit` asks the timed server for them), must
    pass :func:`carried_check`."""
    import jax

    prompt_ids, served = np.asarray(prompt_ids), np.asarray(served)
    if audit is None:
        audit = served_audit(prompt_ids)
    carried = carried_check(audit, audited_a_log(config, params),
                            config["mamba_d_head"])
    carried["ids_as_served"] = float((audit["ids"] == served).mean())
    p = prompt_ids.shape[1]
    ids = np.concatenate([prompt_ids, served[:, :-1]], axis=1).astype(np.int32)
    hidden = reference_hidden(config, params, ids, p - 1, edit)
    stats = jax.jit(brumby._row_stats)
    got = [jax.device_get(stats(logits, row.astype(np.int32)))
           for logits, row in zip(reference_logits(config, params, hidden, edit),
                                  served)]
    s = {k: np.stack([g[k] for g in got]).astype(np.float64) for k in got[0]}
    std = float(np.sqrt(s["square"].mean() - s["mean"].mean() ** 2))
    gap = (s["top"] - s["got"]) / std
    ended = np.cumsum(served == eos_id, axis=1) - (served == eos_id) > 0
    gap = np.where(ended, 0.0, gap)
    agree = float(((gap == 0) | ended).mean())
    return {"ok": bool(np.isfinite(gap).all() and agree >= AGREE_FLOOR
                       and gap.mean() <= MEAN_GAP_LIMIT
                       and gap.max() <= LOGIT_MARGIN and carried["ok"]),
            "rows": int(served.shape[0]), "worst_logit_gap": float(gap.max()),
            "mean_logit_gap": float(gap.mean()),
            "argmax_agree": agree, "carried": carried,
            "other_id_gap_p01": float(np.percentile(
                (s["top"] - s["other"]) / std, 1)),
            "distinct_ids": int(len(np.unique(served))),
            "logit_std": std,
            "top_above_mean": float((s["top"] - s["mean"]).mean() / std),
            "top_two_apart": float((s["top"] - s["second"]).mean() / std)}
