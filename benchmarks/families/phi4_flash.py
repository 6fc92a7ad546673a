"""The ``phi4_flash`` family: from a configuration file to the generator under
test, its seeded weights, its operation and byte counts, and its check
against the plain reference (``benchmarks/reference/phi4_flash.py``).

The configuration file keeps every published key of ``config.json`` (the
model is held whole: ``reduced`` is empty) and, under ``assumed.mamba``, the
four Mamba sizes the catalogued file dropped. No training path
(``models/phi4_flash.py``).

The weights are made as the ``kimi_k2`` family makes its own
(:class:`benchmarks.families.kimi_k2.Weights`: every tensor of every layer
one seeded draw on the device), so the export hands 7.7 GB over a layer at a
time and the check makes one layer's half again, in float32, beside the
server's copy of the model. What is not a fan-in-scaled normal draw is in
:class:`Weights`.

The counts are those of the algorithm, whatever the program does to carry
them out: the prefill's cross-decoder at one position a row, the window's
keys a query needs and no tile more, a state read and written once a step.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict

import numpy as np

from benchmarks.families import brumby, kimi_k2
from benchmarks.reference import phi4_flash as reference

# The check: over 16 served rows x 256 tokens, the gap between the
# reference's largest logit and its logit of the served token, in units of
# the reference's own logit deviation (2.547 under these weights; its top
# two lie 0.21-0.22 apart on average). Three limits on the tokens, as the
# other serve families have them, and a fourth on a carried state itself,
# each between its two readings at the published widths on the chip with
# the weights below (my chip runs, PR 41; PERF.md section 6 prints every
# reading; ``tools/phi4_flash_sensitivity.py`` makes the faulty ones). As
# served over nine runs / the reference with every matrix in an 8-bit
# float, the nearest precision below the weights' bfloat16 / the least
# faulty of the five edits of the tool that change the function (the
# shared keys and values cut to the last 512; the others read worse):
# (1) AGREE_FLOOR, the share of tokens that are the reference's own argmax:
#     0.9143-0.9348 / 0.3916 / 0.6108;
# (2) MEAN_GAP_LIMIT, the mean gap: 1.53e-3 - 2.74e-3 / 0.234 / 0.0763;
# (3) LOGIT_MARGIN, the largest gap: 0.0899-0.1634 / 1.77 / 0.704.
# (32 layers of bfloat16 matrices against a float32 reference: the served
# tokens agree less than an 8-layer stage's do, and the limits lie where
# that leaves room: (1) a tenth under the lowest served reading and over
# every faulty one, (2) and (3) near the geometric middles, 0.025 and 0.54,
# and under the least faulty edit's.)
# A few hundred greedy tokens show little of how a state was carried (a
# state rounded to bfloat16 at every hand-over reads 0.9209 / 2.46e-3 /
# 0.123: inside all three), so a state is read itself:
# (4) CARRIED_ERROR_LIMIT: one more request through the timed server after
#     the window returns, beside its ids, what 128 channels of the first
#     Mamba layer's recurrence were given at every position and their state
#     as the request left it (``models/phi4_flash.py``: the ``audit_*``
#     outputs); the largest relative error over the rows against
#     ``reference.carried_state`` of the same inputs, the definition in
#     float64. As served 1.30e-4 - 3.49e-4 (4 pieces and 255 steps; the
#     chip's ``exp`` reads 1.15e-6 low and a slow channel multiplies some
#     hundred of them up) / a state rounded to bfloat16 at every hand-over,
#     the nearest precision below the float32 the configuration states,
#     1.40e-2 (a state dropped between pieces 2.63e-2): the limit is the
#     geometric middle of the largest served reading and that, a factor of
#     6.3 from either.
AGREE_FLOOR = 0.80
MEAN_GAP_LIMIT = 0.02
LOGIT_MARGIN = 0.40
CARRIED_ERROR_LIMIT = 2.2e-3

SERVE_CHECK_ROWS = 16
# rows of the embedding a block of the check's logits (the head is the
# embedding: a float32 block of 16,384 rows is 168 MB, the whole 2.0 GB)
CHECK_HEAD_BLOCK = 16384

# ---------------------------------------------------------------------------
# configuration


def program_config(config: Dict[str, Any]):
    """The ``models/phi4_flash.py`` config for a configuration file."""
    from paddle_tpu.models import phi4_flash

    m = config["assumed"]["mamba"]
    return phi4_flash.base_config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        sliding_window=config["sliding_window"],
        layer_norm_eps=config["layer_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        mamba_d_state=m["d_state"], mamba_d_conv=m["d_conv"],
        mamba_expand=m["expand"],
        mamba_dt_rank=0 if m["dt_rank"] == "auto" else m["dt_rank"],
        prefill_chunk=config["run"]["chunk"], dtype=config["run"]["dtype"])


def _program(config: Dict[str, Any], new_tokens: int):
    import paddle_tpu as pt
    from paddle_tpu.models import phi4_flash

    return pt.build(phi4_flash.make_generator(program_config(config),
                                              max_new_tokens=new_tokens))


# ids drawn evenly from rows 3 .. vocab - 1 (pad 0, bos 1 and eos 2 never
# drawn): the kimi_k2 family's rule
prompts = kimi_k2.prompts


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, from the configuration alone


def _shape(config: Dict[str, Any]) -> reference.Shape:
    return reference.shape_of(config)


def kinds(config: Dict[str, Any]):
    sh = _shape(config)
    return [reference.kind_of(l, sh) for l in range(sh.layers)]


def _matrices(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameters of the matrices of one layer's mixer, by kind, of an FFN
    and of the head (the embedding)."""
    sh = _shape(config)
    d, di, n, r = sh.hidden, sh.d_inner, sh.d_state, sh.dt_rank
    wide, kvw = sh.heads * sh.head_dim, sh.kv_heads * sh.head_dim
    attention = d * (wide + 2 * kvw) + wide * d
    return {"mamba": d * 2 * di + di * (r + 2 * n) + r * di + di * d,
            "window": attention, "full": attention, "cross": 2 * d * wide,
            "gmu": 2 * d * di, "kv": d * 2 * kvw,
            "ffn": 3 * d * config["intermediate_size"],
            "head": d * config["vocab_size"]}


def window_pairs(config: Dict[str, Any], prompt: int) -> float:
    """Query-key pairs a window layer needs over a prompt, a head."""
    w = config["sliding_window"]
    return float(sum(min(t + 1, w) for t in range(prompt)))


SCAN_OPS = 7    # a state element and token: an exp, three multiplies, two adds,
                # and the product with C


def scan_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    sh = _shape(config)
    return float(SCAN_OPS) * rows * prompt * sh.d_inner * sh.d_state


def window_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """One window layer's attention over a prompt: a head's scores 64 wide
    and its values 128 wide."""
    sh = _shape(config)
    return (2.0 * rows * sh.heads * 3 * sh.head_dim
            * window_pairs(config, prompt))


def prefill_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations the prefill needs: two a multiply-add of every matrix of
    the layers below the full-attention layer and of that layer's keys and
    values, at every position; each Mamba layer's scan and each window
    layer's attention; and at one position a row everything above: the
    full-attention layer's query, output and FFN, the cross-decoder, eight
    reads of the prompt's keys and values, the head."""
    sh, m, ks = _shape(config), _matrices(config), kinds(config)
    full = ks.index("full")
    below = sum(m[k] + m["ffn"] for k in ks[:full]) + m["kv"]
    above = (sum(m[k] + m["ffn"] for k in ks[full:]) - m["kv"] + m["head"])
    readers = 1 + ks.count("cross")
    return (2.0 * rows * prompt * below
            + ks.count("mamba") * scan_flops(config, rows, prompt)
            + ks.count("window") * window_flops(config, rows, prompt)
            + 2.0 * rows * above
            + readers * 2.0 * rows * sh.heads * 3 * sh.head_dim * prompt)


def state_bytes(config: Dict[str, Any], rows: int) -> float:
    """Bytes of one Mamba layer's float32 state and bfloat16 convolution
    tail, ``rows`` rows."""
    sh = _shape(config)
    return rows * sh.d_inner * (4.0 * sh.d_state + 2.0 * (sh.d_conv - 1))


def decode_step_bytes(config: Dict[str, Any], rows: int, position: int) -> float:
    """Bytes one step at ``position`` has to move: the bfloat16 matrices
    once for the batch (the head is the embedding, read once as the head;
    the token's row of it is nothing beside that); the full-attention
    layer's keys and values up to the position once for each of its readers
    (itself and every cross layer: each has a query of its own); every
    window layer's keys and values, the window or the position; every Mamba
    layer's state and tail, read and written."""
    sh, m, ks = _shape(config), _matrices(config), kinds(config)
    weights = 2.0 * (sum(m[k] + m["ffn"] for k in ks) + m["head"])
    kv_row = 2.0 * 2 * sh.kv_heads * sh.head_dim        # a key and a value
    seen = position + 1
    return (weights
            + (1 + ks.count("cross")) * rows * seen * kv_row
            + ks.count("window") * rows * min(seen, sh.window) * kv_row
            + ks.count("mamba") * 2.0 * state_bytes(config, rows))


def kernel_counts(config: Dict[str, Any], rows: int, prompt: int,
                  kernel: str):
    """``(operations, bytes, calls)`` all calls of ``kernel`` in one
    request's prefill need, for ``readers/kernel_roofline.py``; None for a
    kernel the family does not count there. ``mamba_fwd``: the scan's vector
    operations (``peaks.json`` has no peak for them, so the bytes bound it:
    float32 ``Delta``, ``u`` and ``y``, ``B`` and ``C``, and a call's states
    read and written). ``flash_fwd``: the window layers' calls, the pairs a
    window needs and no tile more, q and o and the piece's keys and values
    as the cache holds them."""
    sh, ks = _shape(config), kinds(config)
    pieces = -(-prompt // min(config["run"]["chunk"], prompt))
    if kernel == "mamba_fwd":
        n = ks.count("mamba")
        moved = (4.0 * rows * prompt * (3 * sh.d_inner + 2 * sh.d_state)
                 + pieces * 2.0 * 4 * rows * sh.d_inner * sh.d_state)
        return n * scan_flops(config, rows, prompt), n * moved, n * pieces
    if kernel == "flash_fwd":
        n = ks.count("window")
        moved = 2.0 * rows * prompt * (
            sh.heads * 3 * sh.head_dim + 2 * sh.kv_heads * sh.head_dim)
        return n * window_flops(config, rows, prompt), n * moved, n * pieces
    return None


# ---------------------------------------------------------------------------
# seeded weights


EMBEDDING, FINAL = "tok/embedding_0/w", "final_norm_0/"

# What is scaled so that a mechanism moves a logit (assumed.weights in the
# configuration file says the same in words). A fan-in-scaled x_proj gives B
# and C of size 0.3 and a state that adds a thirtieth of what the skip D x
# adds: nothing carried across a piece would reach a logit. x_proj's B and C
# columns are drawn X_GAIN / sqrt(fan_in) (its dt_rank columns stay at fan-in
# scale, so that Delta keeps its published spread); the three output matrices
# are drawn with the gains that make their mixers add to the residual stream
# what the FFN adds (0.6 an element); an attention's W_o is divided by (1 -
# lambda_init(l)), the factor the layer multiplies its normed output by.
# The head is the embedding: a token's own row meets itself in the last
# hidden state and its logit stands d * std^2 above the rest, which deviate by
# sqrt(d * var x) * std. At N(0, 1) rows that is ten deviations at 2,560 wide
# and every greedy token repeats the last; rows are drawn N(0, 0.05^2) (as
# tied embeddings are published to start, small), half a deviation.
EMBED_STD = 0.05
X_GAIN = 4.0
MAMBA_OUT_GAIN = 2.2
GMU_OUT_GAIN = 2.2
ATTN_OUT = 0.6
LAMBDA_STD = 0.1
BIAS_STD = 0.1
DT_MIN, DT_MAX = 0.001, 0.1


def _layer_table(config: Dict[str, Any], kind: str):
    """``name -> (shape, is a matrix in the held dtype)`` of one layer."""
    sh = _shape(config)
    d, di, n, r, f = (sh.hidden, sh.d_inner, sh.d_state, sh.dt_rank,
                      config["intermediate_size"])
    hd, wide, kvw = sh.head_dim, sh.heads * sh.head_dim, sh.kv_heads * sh.head_dim
    diff = {"mixer/lambda": ((4, hd), False), "mixer/sub_norm/g": ((2 * hd,), False),
            "mixer/o/w": ((wide, d), True), "mixer/o/b": ((d,), False)}
    mixer = {
        "mamba": {"mixer/in/w": ((d, 2 * di), True),
                  "mixer/conv/w": ((sh.d_conv, di), False),
                  "mixer/conv/b": ((di,), False),
                  "mixer/x/w": ((di, r + 2 * n), True),
                  "mixer/dt/w": ((r, di), True), "mixer/dt/b": ((di,), False),
                  "mixer/a_log": ((n, di), False), "mixer/d": ((di,), False),
                  "mixer/out/w": ((di, d), True)},
        "attention": {"mixer/qkv/w": ((d, wide + 2 * kvw), True),
                      "mixer/qkv/b": ((wide + 2 * kvw,), False), **diff},
        "cross": {"mixer/q/w": ((d, wide), True), "mixer/q/b": ((wide,), False),
                  **diff},
        "gmu": {"mixer/in/w": ((d, di), True), "mixer/out/w": ((di, d), True)},
    }[kind if kind in ("mamba", "cross", "gmu") else "attention"]
    return {"mixer/norm/g": ((d,), False), "mixer/norm/b": ((d,), False),
            **mixer, "ffn/ffn_norm/g": ((d,), False),
            "ffn/ffn_norm/b": ((d,), False), "ffn/gate/w": ((d, f), True),
            "ffn/up/w": ((d, f), True), "ffn/down/w": ((f, d), True)}


def parameter_table(config: Dict[str, Any]):
    """The program's parameters (``name -> ShapeDtypeStruct``, sorted as
    ``prog.init`` gives them) by arithmetic from the configuration (the
    ``brumby`` family says why not by a trace). ``tests/test_phi4_flash.py``
    holds this table to the program's own."""
    import jax
    import jax.numpy as jnp

    d = config["hidden_size"]
    held, f32 = jnp.dtype(config["run"]["dtype"]), jnp.dtype(jnp.float32)
    table = {EMBEDDING: ((config["vocab_size"], d), held),
             FINAL + "g": ((d,), f32), FINAL + "b": ((d,), f32)}
    for layer, kind in enumerate(kinds(config)):
        for name, (shape, matrix) in _layer_table(config, kind).items():
            table[f"layer_{layer}/{name}"] = (shape, held if matrix else f32)
    return {name: jax.ShapeDtypeStruct(*table[name]) for name in sorted(table)}


class Weights(kimi_k2.Weights):
    """The generator's weights as seeded draws, a tensor of a layer at a
    time: every matrix N(0, 1 / fan_in) but for the gains above, the
    embedding N(0, 1), norm scales 1, every bias N(0, 0.1^2), the lambdas
    N(0, 0.1^2); and the recurrence as Mamba is published to start:
    ``A_log = log(1 .. d_state)`` a channel, ``dt_bias`` such that its
    softplus is log-uniform in [0.001, 0.1], ``D = 1``, the convolution's
    taps and bias U(-1/2, 1/2)."""

    def __init__(self, config: Dict[str, Any], seed: int, prompt_len: int,
                 new_tokens: int):
        self.config, self.seed, self.prompt_len = config, seed, prompt_len
        self.shapes = parameter_table(config)

    @staticmethod
    def stacked(name: str) -> bool:
        return False

    def _std(self, name: str, shape) -> float:
        if name.startswith("tok/"):
            return EMBED_STD
        if name.endswith("/b"):
            return BIAS_STD
        if name.endswith("lambda"):
            return LAMBDA_STD
        fan_in = shape[-2] ** -0.5                      # [in, out]
        layer = int(name.split("/")[0].split("_")[1])   # "layer_<l>/..."
        if name.endswith("mixer/o/w"):
            return ATTN_OUT / (1.0 - float(reference.lambda_init(layer))) * fan_in
        if name.endswith("mixer/x/w"):
            return X_GAIN * fan_in
        if name.endswith("mixer/out/w"):
            mamba = kinds(self.config)[layer] == "mamba"
            return (MAMBA_OUT_GAIN if mamba else GMU_OUT_GAIN) * fan_in
        return fan_in

    def _uniform(self, name: str, on_host: bool):
        """U(0, 1) of the tensor's shape, float32, from the seed and the
        name."""
        import jax

        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 zlib.crc32(name.encode()) & 0x7fffffff)
        u = jax.random.uniform(key, self.shapes[name].shape)
        return np.asarray(u) if on_host else u

    def slab(self, name: str, layer: int = 0, on_host: bool = False):
        import jax.numpy as jnp

        xp = np if on_host else jnp
        full = self.shapes[name]
        if name.endswith("/a_log"):
            return xp.broadcast_to(xp.log(xp.arange(
                1, full.shape[0] + 1, dtype=xp.float32))[:, None], full.shape)
        if name.endswith("mixer/d"):
            return xp.ones(full.shape, full.dtype)
        if name.endswith(("conv/w", "conv/b")):
            return self._uniform(name, on_host) - 0.5
        if name.endswith("dt/b"):
            dt = xp.exp(self._uniform(name, on_host)
                        * (math.log(DT_MAX) - math.log(DT_MIN))
                        + math.log(DT_MIN))
            return dt + xp.log(-xp.expm1(-dt))          # softplus^-1
        if name.endswith("x/w"):
            # the dt_rank columns at fan-in scale, B's and C's at X_GAIN
            w = super().slab(name, layer, on_host)
            r = _shape(self.config).dt_rank
            scale = xp.concatenate([
                xp.full((r,), 1.0 / X_GAIN, xp.float32),
                xp.ones((full.shape[1] - r,), xp.float32)])
            return (w.astype(xp.float32) * scale).astype(full.dtype)
        return super().slab(name, layer, on_host)

    def _get(self, layer: int):
        import jax.numpy as jnp

        return lambda n: self.slab(f"layer_{layer}/{n}").astype(jnp.float32)

    def reference_mixer(self, layer: int) -> Dict[str, Any]:
        return reference_mixer(self._get(layer), kinds(self.config)[layer])

    def reference_ffn(self, layer: int) -> Dict[str, Any]:
        return reference_ffn(self._get(layer))


def reference_mixer(get, kind: str) -> Dict[str, Any]:
    """One layer's mixer under the reference's names; ``get(name)`` gives
    the program's float32 tensor of that layer by its name in the layer's
    scope. The program holds ``a_log`` as ``[d_state, d_inner]``, the
    reference as published, ``[d_inner, d_state]``."""
    out = {"norm_g": get("mixer/norm/g"), "norm_b": get("mixer/norm/b")}
    if kind == "mamba":
        out.update(in_proj=get("mixer/in/w"), conv_w=get("mixer/conv/w"),
                   conv_b=get("mixer/conv/b"), x_proj=get("mixer/x/w"),
                   dt_proj=get("mixer/dt/w"), dt_bias=get("mixer/dt/b"),
                   a_log=get("mixer/a_log").T, d_skip=get("mixer/d"),
                   out_proj=get("mixer/out/w"))
    elif kind == "gmu":
        out.update(in_proj=get("mixer/in/w"), out_proj=get("mixer/out/w"))
    else:
        first = "q" if kind == "cross" else "qkv"
        out.update({first: get(f"mixer/{first}/w"),
                    first + "_b": get(f"mixer/{first}/b"),
                    "lambdas": get("mixer/lambda"),
                    "sub_norm": get("mixer/sub_norm/g"), "o": get("mixer/o/w"),
                    "o_b": get("mixer/o/b")})
    return out


def reference_ffn(get) -> Dict[str, Any]:
    """The program holds the published ``gate_up_proj`` as its halves."""
    import jax.numpy as jnp

    return {"ffn_norm_g": get("ffn/ffn_norm/g"),
            "ffn_norm_b": get("ffn/ffn_norm/b"),
            "gate_up": jnp.concatenate([get("ffn/gate/w"), get("ffn/up/w")],
                                       axis=1),
            "down": get("ffn/down/w")}


def reference_params(params: Dict[str, Any], config: Dict[str, Any]):
    """A whole parameter dict of the program under the reference's names,
    float32 (the tests' small sizes; the chip check streams, see
    :func:`reference_hidden`)."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layers = []
    for layer, kind in enumerate(kinds(config)):
        get = lambda n, scope=f"layer_{layer}/": f32(params[scope + n])
        layers.append({**reference_mixer(get, kind), **reference_ffn(get)})
    return {"emb": f32(params[EMBEDDING]), "final_g": f32(params[FINAL + "g"]),
            "final_b": f32(params[FINAL + "b"]), "layers": layers}


# ---------------------------------------------------------------------------
# the system under test: serving


def decoder_params(config: Dict[str, Any], seed: int, prompt_len: int,
                   new_tokens: int) -> Weights:
    """Not the weights but their seeded maker: the server holds the only
    copy on the device."""
    return Weights(config, seed, prompt_len, new_tokens)


def export_decoder(config: Dict[str, Any], seed: int, dirname: str,
                   prompt_len: int, new_tokens: int, buckets) -> None:
    """``fleet.decode.export_decoder`` of the seeded weights, handed over
    on the host, with the given batch buckets."""
    from paddle_tpu.fleet import decode
    from paddle_tpu.models import phi4_flash

    buckets = sorted(int(b) for b in buckets)
    weights = Weights(config, seed, prompt_len, new_tokens)
    decode.export_decoder(dirname, program_config(config), new_tokens,
                          np.zeros((buckets[-1], prompt_len), np.int32),
                          params=weights.host_params(),
                          batch_buckets=buckets, model=phi4_flash)


def reference_hidden(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
                     first: int, edit=None):
    """The reference's last hidden state ``[rows, s - first, d]`` (on the
    device, before the final norm) for the sequences ``ids [rows, s]``: a
    layer's mixer and then its FFN at a time, each half's float32 weights
    made from the seed once for all the rows and freed before the next is
    made; a mixer takes a sequence at a time, the FFN all tokens in blocks.
    The cross-decoder runs over every position. ``edit(params, layer) ->
    params`` may change what a half is given (the sensitivity run's
    reference in a lower precision)."""
    import jax
    import jax.numpy as jnp

    sh = _shape(config)
    edit = edit or (lambda lp, layer: lp)
    rows, s = ids.shape
    ffn = jax.jit(lambda x, lp: reference.ffn_part(
        x.reshape(rows * s, -1), lp, sh).reshape(x.shape))
    x = weights.slab(EMBEDDING)[jnp.asarray(ids)].astype(jnp.float32)
    memory = kv = None
    # one compiled mixer a kind (five, not 32): the layer's index is traced
    mixers = {kind: jax.jit(lambda x, memory, kv, lp, layer, kind=kind:
                            jax.lax.map(lambda a: reference.mixer_part(
                                a[0], lp, sh, layer, a[1], a[2], kind=kind),
                                (x, memory, kv)))
              for kind in set(kinds(config))}
    for layer, kind in enumerate(kinds(config)):
        lp = edit(weights.reference_mixer(layer), layer)
        x, memory, kv = jax.block_until_ready(mixers[kind](
            x, memory, kv, lp, jnp.asarray(layer, jnp.float32)))
        lp = edit(weights.reference_ffn(layer), layer)
        x = jax.block_until_ready(ffn(x, lp))
        del lp
    return x[:, first:]


def reference_logits(config: Dict[str, Any], weights: Weights, hidden, edit=None):
    """``hidden [rows, n, d] ->`` the reference's logits, a row ``[n,
    vocab]`` at a time (a generator), the embedding's rows in blocks of
    ``CHECK_HEAD_BLOCK``."""
    import jax
    import jax.numpy as jnp

    sh = _shape(config)
    emb = (edit or (lambda lp, layer: lp))(
        {"emb": weights.slab(EMBEDDING)}, sh.layers)["emb"]
    final = [weights.slab(FINAL + n).astype(jnp.float32) for n in "gb"]
    block = jax.jit(lambda h, part: reference.head_logits(
        h, *final, part.astype(jnp.float32), sh))
    for row in hidden:
        yield jnp.concatenate(
            [block(row, emb[c:c + CHECK_HEAD_BLOCK])
             for c in range(0, emb.shape[0], CHECK_HEAD_BLOCK)], axis=1)


def carried_check(audit: Dict[str, np.ndarray], a_log) -> Dict[str, Any]:
    """A request's ``audit_*`` outputs (``models/phi4_flash.py``) against the
    definition: a row at a time, the relative error (Frobenius) of
    ``audit_state`` against ``reference.carried_state`` of what the
    recurrence was given; the largest must be finite and within
    ``CARRIED_ERROR_LIMIT``. ``a_log [d_state, channels]``: the audited
    layer's, for the audited channels."""
    a = -np.exp(np.asarray(a_log, np.float64))
    err = []
    for got, delta, u, b in zip(audit["audit_state"], audit["audit_delta"],
                                audit["audit_u"], audit["audit_b"]):
        want = reference.carried_state(delta, u, b, a)
        err.append(np.linalg.norm(np.asarray(got, np.float64) - want)
                   / np.linalg.norm(want))
    err = np.asarray(err)
    return {"ok": bool(np.isfinite(err).all()
                       and err.max() <= CARRIED_ERROR_LIMIT),
            "carried_error": float(err.max()),
            "positions": int(audit["audit_delta"].shape[1])}


def audited_a_log(config: Dict[str, Any], weights: Weights):
    """``a_log`` of the layer and channels a request audits."""
    from paddle_tpu.models import phi4_flash

    layer = [l for l, k in enumerate(kinds(config)) if k == "mamba"][
        phi4_flash.AUDIT_LAYER]
    return np.asarray(weights.slab(f"layer_{layer}/mixer/a_log", on_host=True)
                      )[:, :phi4_flash.AUDIT_CHANNELS]


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
    not given, ``brumby.served_audit`` asks the timed server for them), must
    pass :func:`carried_check`."""
    import jax

    prompt_ids, served = np.asarray(prompt_ids), np.asarray(served)
    if audit is None:
        audit = brumby.served_audit(prompt_ids)
    carried = carried_check(audit, audited_a_log(config, params))
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
