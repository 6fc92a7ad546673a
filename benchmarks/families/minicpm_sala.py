"""The ``minicpm_sala`` family: from a configuration file to the generator
under test, its seeded weights, its operation and byte counts, and its
check against the plain reference (``benchmarks/reference/minicpm_sala.py``).

The configuration file keeps the published keys of ``config.json``
(``mixer_types`` whole) and says which of the layers are held here:
``num_hidden_layers`` is cut, ``layer_indices`` lists the published indices
of the layers held, ``published`` has the published depth (the muP
scalings and the lightning decays refer to it) and ``deployment`` the
pipeline stage this is. No training path (``models/minicpm_sala.py``).

The weights are made as the ``kimi_k2`` family makes its own
(:class:`benchmarks.families.kimi_k2.Weights`: every tensor of every layer
one seeded draw on the device), so the export hands 10 GB over a layer at
a time and the check makes one layer's half again, in float32, beside the
server's copy of the model.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmarks.families import kimi_k2
from benchmarks.reference import minicpm_sala as reference

SPARSE, LIGHTNING = reference.SPARSE, reference.LIGHTNING

# The check: over 4 served rows x 128 tokens, the gap between the
# reference's largest logit and its logit of the served token, in units of
# the reference's own logit deviation (muP divides the final norm's output
# by hidden_size / dim_model_base = 16, so a logit's deviation is 1/16 where
# Kimi-K2.5's is 1; the largest logit stands 4.3 deviations above the mean
# and 0.22 above the second). Three limits, as that family has them, each
# from readings at the published widths on the chip with the weights below
# (seven sound runs of 512 tokens on seven seeds, and
# ``tools/sala_sensitivity.py`` on three more, 128 tokens a reading;
# PERF.md section 6, PR 33):
# (1) AGREE_FLOOR: the share of tokens that are the reference's own argmax.
# bfloat16 against the float32 reference read 94.5 to 97.7% (the rest are
# near-ties that rounding decides, a differently seated 64th block among
# them); every fault reads lower on every seed: the scores summed before
# the scorer's softmax 82.0 / 82.0 / 84.4%, the reference in an 8-bit float
# (the precision below the one the configuration states) 80.5 / 81.3 /
# 82.0%, the chosen blocks left out 65.6 / 83.6 / 74.2%, rotary applied in
# the sparse layers 66.4 / 63.3 / 68.0%, another layer's decay 59.4 / 57.0 /
# 60.9%, rotary left off lightning's q and k 29.7 / 34.4 / 29.7%.
# (2) MEAN_GAP_LIMIT: the mean gap read 0.0004 to 0.0015 with nothing wrong;
# the faults read 0.0106 to 0.35, the summed scores and the 8-bit reference
# lowest (0.0106 and 0.0111). The limit lies 4.6 times over the largest
# sound reading and 1.5 times under the smallest fault.
# (3) LOGIT_MARGIN guards against a garbled id only: one token lost up to
# 0.078 with nothing wrong and 0.14 to 1.7 under the faults; another id at
# the same position (the check's ``other_id_gap_p01``) loses more than 1.67
# to 2.5 at 99 positions of 100.
AGREE_FLOOR = 0.88
MEAN_GAP_LIMIT = 0.007
LOGIT_MARGIN = 1.0

SERVE_CHECK_ROWS = 4
# queries a block of the reference's mixers on the chip: 128 queries x 32
# heads x 32,895 keys of float32 scores are 540 MB; a mixer half then takes
# some 4 GB (compiled for a described v5e) beside the server's 10 GB
CHECK_QUERY_BLOCK = 128


# ---------------------------------------------------------------------------
# configuration


def layers_of(config: Dict[str, Any]):
    """``(kinds, published indices)`` of the layers held."""
    indices = tuple(config["layer_indices"])
    return tuple(config["mixer_types"][i] for i in indices), indices


def program_config(config: Dict[str, Any]):
    """The ``models/minicpm_sala.py`` config for a configuration file."""
    from paddle_tpu.models import minicpm_sala

    kinds, indices = layers_of(config)
    sc = config["assumed"]["sparse_config"]
    return minicpm_sala.base_config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"], mixer_types=kinds,
        layer_indices=indices,
        published_layers=config["published"]["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        lightning_nh=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        scale_emb=config["scale_emb"], scale_depth=config["scale_depth"],
        dim_model_base=config["dim_model_base"],
        max_position_embeddings=config["max_position_embeddings"],
        sparse_kernel_size=sc["kernel_size"],
        sparse_kernel_stride=sc["kernel_stride"],
        sparse_block_size=sc["block_size"], sparse_init_blocks=sc["init_blocks"],
        sparse_window_size=sc["window_size"], sparse_topk=sc["topk"],
        sparse_dense_len=sc["dense_len"],
        prefill_chunk=config["run"]["prefill_chunk"],
        dtype=config["run"]["dtype"])


def _program(config: Dict[str, Any], new_tokens: int):
    import paddle_tpu as pt
    from paddle_tpu.models import minicpm_sala

    return pt.build(minicpm_sala.make_generator(program_config(config),
                                                max_new_tokens=new_tokens))


# ids drawn evenly from rows 3 .. vocab - 1 (pad 0, bos 1 and eos 2 never
# drawn): the kimi_k2 family's rule
prompts = kimi_k2.prompts


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, from the configuration alone


def _counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by part."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    lw = config["lightning_nh"] * config["lightning_head_dim"]
    return {SPARSE: 3 * d * q + 2 * d * kv, LIGHTNING: 5 * d * lw,
            "ffn": 3 * d * config["intermediate_size"],
            "head": d * config["vocab_size"]}


def _matrix_params(config: Dict[str, Any]) -> float:
    """Parameters of every matrix a token passes, the head apart."""
    c = _counts(config)
    return sum(c[k] + c["ffn"] for k in layers_of(config)[0])


def _sparse(config):
    return config["assumed"]["sparse_config"]


def selected_keys(config: Dict[str, Any], position: int, context: int) -> int:
    """Keys the query at ``position`` reads in a call of ``context``
    tokens: all up to itself within ``dense_len``, else those of at most
    ``topk`` blocks (its own cut at itself)."""
    sc = _sparse(config)
    blk = sc["block_size"]
    if context <= sc["dense_len"] or position // blk + 1 <= sc["topk"]:
        return position + 1
    return (sc["topk"] - 1) * blk + position % blk + 1


def _kernels_seen(config, position: int) -> int:
    sc = _sparse(config)
    return max((position + 1 - sc["kernel_size"]) // sc["kernel_stride"] + 1, 0)


def sparse_attention_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations one sparse layer's attention needs over a prompt: for
    each query the keys ``<= i`` of its selected blocks, ``2 x (128 + 128)``
    a key and head (score and value)."""
    hd = config["head_dim"]
    keys = sum(selected_keys(config, i, prompt) for i in range(prompt))
    return 2.0 * rows * config["num_attention_heads"] * keys * 2 * hd


def scorer_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations one sparse layer's scorer needs: every head of a query
    against the compressed keys that exist for it (none within
    ``dense_len``)."""
    if prompt <= _sparse(config)["dense_len"]:
        return 0.0
    kernels = sum(_kernels_seen(config, i) for i in range(prompt))
    return 2.0 * rows * config["num_attention_heads"] * kernels * config["head_dim"]


# rows of a chunk of the recurrence (``ops/lightning_attention.CHUNK``): the
# algorithm's cost depends on it, a quadratic form inside and a state across
LIGHTNING_CHUNK = 256


def lightning_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations one lightning layer needs over a prompt, a token and
    head: the causal half of a chunk's scores and values (``2 d (C + 1)``)
    and the two products with the state (``4 d^2``)."""
    d = config["lightning_head_dim"]
    return (float(rows) * prompt * config["lightning_nh"]
            * (2.0 * d * (LIGHTNING_CHUNK + 1) + 4.0 * d * d))


def prefill_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations the prefill needs: two a multiply-add of every matrix a
    token passes, each mixer's own, and the head for a row's last token."""
    kinds, _ = layers_of(config)
    return (2.0 * rows * prompt * _matrix_params(config)
            + kinds.count(SPARSE) * (sparse_attention_flops(config, rows, prompt)
                                     + scorer_flops(config, rows, prompt))
            + kinds.count(LIGHTNING) * lightning_flops(config, rows, prompt)
            + 2.0 * rows * _counts(config)["head"])


def _weight_bytes(config: Dict[str, Any]) -> float:
    """bfloat16 bytes of every matrix a pass reads; the head, not the
    embedding (read by row)."""
    return 2.0 * (_matrix_params(config) + _counts(config)["head"])


def decode_step_bytes(config: Dict[str, Any], rows: int, position: int) -> float:
    """Bytes one step at ``position`` has to move: the weights; a sparse
    layer's selected keys and values and the compressed keys its scorer
    reads; a lightning layer's float32 state, read and written."""
    kinds, _ = layers_of(config)
    width = 2.0 * config["num_key_value_heads"] * config["head_dim"]   # bf16 row
    sparse = rows * width * (2 * selected_keys(config, position, position + 1)
                             + _kernels_seen(config, position))
    state = 2.0 * 4 * rows * config["lightning_nh"] * config["lightning_head_dim"] ** 2
    return (_weight_bytes(config) + kinds.count(SPARSE) * sparse
            + kinds.count(LIGHTNING) * state)


def kernel_counts(config: Dict[str, Any], rows: int, prompt: int,
                  kernel: str):
    """``(operations, bytes, calls)`` all calls of ``kernel`` in one
    request need (the prefill's: a step calls neither), for
    ``readers/kernel_roofline.py``; None for a kernel the family does not
    count."""
    kinds, _ = layers_of(config)
    chunk = min(config["run"]["prefill_chunk"], prompt)
    chunks = prompt // chunk
    if kernel == "sparse_fwd":
        if prompt <= _sparse(config)["dense_len"]:
            return None
        n, hd, sc = kinds.count(SPARSE), config["head_dim"], _sparse(config)
        qo = 2.0 * 2 * rows * prompt * config["num_attention_heads"] * hd
        # a call reads the keys and values up to its last query, and a
        # query's block indices with their count
        kv = sum(2.0 * 2 * rows * (c + 1) * chunk * config["num_key_value_heads"] * hd
                 for c in range(chunks))
        sel = 4.0 * rows * prompt * config["num_key_value_heads"] * (
            sc["topk"] - sc["init_blocks"]
            - sc["window_size"] // sc["block_size"] + 1)
        return (n * sparse_attention_flops(config, rows, prompt),
                n * (qo + kv + sel), n * chunks)
    if kernel == "lightning_fwd":
        n = kinds.count(LIGHTNING)
        width = config["lightning_nh"] * config["lightning_head_dim"]
        moved = (4.0 * 2 * rows * prompt * width
                 + chunks * 2.0 * 4 * rows * width * config["lightning_head_dim"])
        return n * lightning_flops(config, rows, prompt), n * moved, n * chunks
    return None


# ---------------------------------------------------------------------------
# seeded weights


# Two scales are set so that every mechanism moves a logit (a first build
# drew the embedding N(0, 1) and left every norm scale at 1: the residual
# stream was then scale_emb = 12 times the size of everything the layers
# add, softmax attention over 4,096 random keys was near-uniform and its
# output 1/40 of a value's size, and of six faults put into the reference
# four read like the sound run, an 8-bit reference among them: PERF.md
# section 6, PR 33). The embedding is drawn N(0, 1 / scale_emb^2), so that
# scale_emb brings it to the size of one layer's input, as muP means it to;
# a sparse layer's q_norm scale is SPARSE_Q_GAIN, so that its scores have
# that deviation and its attention is peaked as a trained model's is (8
# keys' worth of weight among 4,096, not 1,500).
SPARSE_Q_GAIN = 4.0


class Weights(kimi_k2.Weights):
    """The generator's weights as seeded draws, a tensor of a layer at a
    time (the ``kimi_k2`` family's maker over this program's parameter
    table, where every layer has its own names, ``layer_<published
    index>/...``): every matrix N(0, 1 / fan_in), the embedding N(0, 1 /
    scale_emb^2), norm scales 1 but a sparse layer's ``q_norm``."""

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
        return False

    def _std(self, name: str, shape) -> float:
        if name.startswith("tok/"):
            return 1.0 / self.config["scale_emb"]
        # qkv is stored [out, in], every other matrix [in, out]
        return shape[-1 if name.endswith("qkv/w") else -2] ** -0.5

    def slab(self, name: str, layer: int = 0, on_host: bool = False):
        out = super().slab(name, layer, on_host)
        if name.endswith("mixer/q_norm/g") and self.config["mixer_types"][
                int(name.split("/")[0].split("_")[1])] == SPARSE:
            out = out * SPARSE_Q_GAIN
        return out

    def _get(self, layer: int):
        import jax.numpy as jnp

        scope = f"layer_{layers_of(self.config)[1][layer]}/"
        return lambda n: self.slab(scope + n).astype(jnp.float32)

    def reference_mixer(self, layer: int) -> Dict[str, Any]:
        return reference_mixer(self._get(layer), self.config,
                               layers_of(self.config)[0][layer])

    def reference_ffn(self, layer: int) -> Dict[str, Any]:
        return reference_ffn(self._get(layer))

    def reference_ends(self) -> Dict[str, Any]:
        import jax.numpy as jnp

        return reference_ends(lambda n: self.slab(n).astype(jnp.float32))


def reference_mixer(get, config: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """One layer's mixer under the reference's names; ``get(name)`` gives
    the program's float32 tensor of that layer by its name in the layer's
    scope. The program holds q, k and v as one matrix ``[out, in]``, q's
    rows first; the reference takes three ``[in, out]``."""
    names = ["attn_norm/g", "q_norm/g", "k_norm/g", "gate/w", "o/w"] + (
        ["o_norm/g"] if kind == LIGHTNING else [])
    out = {n.split("/")[0]: get("mixer/" + n) for n in names}
    if kind == LIGHTNING:
        q = k = config["lightning_nh"] * config["lightning_head_dim"]
    else:
        q = config["num_attention_heads"] * config["head_dim"]
        k = config["num_key_value_heads"] * config["head_dim"]
    qkv = get("mixer/qkv/w")
    out.update(q=qkv[:q].T, k=qkv[q:q + k].T, v=qkv[q + k:].T)
    return out


def reference_ffn(get) -> Dict[str, Any]:
    return {"ffn_norm": get("ffn/ffn_norm/g"), "ffn_gate": get("ffn/gate/w"),
            "ffn_up": get("ffn/up/w"), "ffn_down": get("ffn/down/w")}


def reference_ends(get) -> Dict[str, Any]:
    return {"emb": get("tok/embedding_0/w"), "final_norm": get("final_norm_0/g"),
            "head": get("lm_head_0/w")}


def reference_params(params: Dict[str, Any], config: Dict[str, Any]):
    """A whole parameter dict of the program under the reference's names,
    float32 (the tests' small sizes; the chip check streams, see
    :func:`reference_logits`)."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layers = []
    for kind, index in zip(*layers_of(config)):
        get = lambda n, scope=f"layer_{index}/": f32(params[scope + n])
        layers.append({**reference_mixer(get, config, kind), **reference_ffn(get)})
    return {**reference_ends(lambda n: f32(params[n])), "layers": layers}


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
    from paddle_tpu.models import minicpm_sala

    buckets = sorted(int(b) for b in buckets)
    weights = Weights(config, seed, prompt_len, new_tokens)
    decode.export_decoder(dirname, program_config(config), new_tokens,
                          np.zeros((buckets[-1], prompt_len), np.int32),
                          params=weights.host_params(),
                          batch_buckets=buckets, model=minicpm_sala)


def reference_logits(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
                     prompt_len: int, first: int, edit=None,
                     query_block: int = CHECK_QUERY_BLOCK) -> np.ndarray:
    """The reference's logits ``[s - first, vocab]`` for one sequence
    ``ids [s]`` whose first ``prompt_len`` tokens were a prompt: a layer's
    mixer and then its FFN at a time, each half's float32 weights freed
    before the next is made. ``edit(shape, part, layer, kind, index,
    params)`` may change what the reference is given (the sensitivity
    runs): it returns ``(shape, kind, index, params)``."""
    import jax
    import jax.numpy as jnp

    sh = reference.shape_of(config, query_block=query_block)
    kinds, indices = layers_of(config)
    edit = edit or (lambda sh_, part, layer, kind, index, lp: (sh_, kind, index, lp))
    mixer = jax.jit(reference.mixer_part, static_argnums=(2, 3, 4, 5))
    ffn = jax.jit(reference.ffn_part, static_argnums=2)
    x = reference.embed(weights.reference_ends()["emb"], jnp.asarray(ids), sh)
    for layer, (kind, index) in enumerate(zip(kinds, indices)):
        sh_l, kind_l, index_l, lp = edit(sh, "mixer", layer, kind, index,
                                         weights.reference_mixer(layer))
        x = jax.block_until_ready(mixer(x, lp, sh_l, kind_l, index_l, prompt_len))
        del lp
        sh_l, _, _, lp = edit(sh, "ffn", layer, kind, index,
                              weights.reference_ffn(layer))
        x = jax.block_until_ready(ffn(x, lp, sh_l))
        del lp
    ends = weights.reference_ends()
    return np.asarray(reference.head_logits(x[first:], ends["final_norm"],
                                            ends["head"], sh))


def served_check(config: Dict[str, Any], params: Weights,
                 prompt_ids: np.ndarray, served: np.ndarray, eos_id: int = 2,
                 edit=None) -> Dict[str, Any]:
    """One full reference forward over prompt + served ids, a row at a
    time; at every generated position (up to a row's first end-of-sequence
    id, after which the generator forces it) the served token's reference
    logit, in deviations of the reference's logits, must be within
    ``LOGIT_MARGIN`` of the largest, the mean of those gaps within
    ``MEAN_GAP_LIMIT``, and at least ``AGREE_FLOOR`` of the tokens the
    reference's own argmax."""
    prompt_ids, served = np.asarray(prompt_ids), np.asarray(served)
    p = prompt_ids.shape[1]
    ids = np.concatenate([prompt_ids, served[:, :-1]], axis=1).astype(np.int32)
    logits = np.stack([reference_logits(config, params, row, p, p - 1, edit)
                       for row in ids])
    std = float(logits.std())
    got = np.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    gap = (logits.max(-1) - got) / std
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
            "other_id_gap_p01": float(np.percentile(
                (logits.max(-1) - other) / std, 1)),
            "distinct_ids": int(len(np.unique(served))),
            "logit_std": std,
            "top_above_mean": float((logits.max(-1) - logits.mean(-1)).mean() / std),
            "top_two_apart": float((top2[..., 1] - top2[..., 0]).mean() / std)}
