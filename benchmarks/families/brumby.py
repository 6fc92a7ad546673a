"""The ``brumby`` family: from a configuration file to the generator under
test, its seeded weights, its operation and byte counts, and its check
against the plain reference (``benchmarks/reference/brumby.py``).

The configuration file keeps the published keys of ``config.json`` and says
which of the layers are held here: ``num_hidden_layers`` is cut,
``layer_indices`` lists the published indices of the layers held,
``published`` has the published depth and ``deployment`` the pipeline stage
this is. No training path (``models/brumby.py``).

The weights are made as the ``kimi_k2`` family makes its own
(:class:`benchmarks.families.kimi_k2.Weights`: every tensor of every layer
one seeded draw on the device), so the export hands 8.4 GB over a layer at a
time and the check makes one layer's half again, in float32, beside the
server's copy of the model.

The counts are those of the algorithm at the symmetric feature map (8,256
products of a 128-wide head), whatever layout the program holds its state
in (``ops/power_retention.py`` holds 8,704 and a padded key sum): a roofline
reads the same work whatever implements it.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmarks.families import kimi_k2
from benchmarks.reference import brumby as reference

# The check: over 16 served rows x 256 tokens, the gap between the
# reference's largest logit and its logit of the served token, in units of
# the reference's own logit deviation (1.000 under these weights; its top
# two lie 0.19-0.23 apart on average). Three limits on the tokens, as the
# other serve families have them, and a fourth on the carried sums
# themselves, each between its two readings at the published widths on the
# chip with the weights below (my chip runs, PR 39; PERF.md section 6 prints
# every reading; ``tools/brumby_sensitivity.py`` makes the faulty ones). As
# served over twelve runs / the reference with every matrix in an 8-bit float,
# the nearest precision below the weights' bfloat16 / the least faulty of a
# dropped key sum, a gate of 1 and a state zeroed between chunks:
# (1) AGREE_FLOOR, the share of tokens that are the reference's own argmax:
#     0.9814-0.9888 / 0.7236 / 0.036;
# (2) MEAN_GAP_LIMIT, the mean gap: 4.6e-5 - 1.08e-4 / 0.0402 / 1.90;
# (3) LOGIT_MARGIN, the largest gap: 0.0134-0.0253 / 0.559 / 5.8.
# A few hundred greedy tokens show little of how a state was carried (the
# first session's control, a conversion to bfloat16 and back at every
# hand-over, passed all three: the compiler had dropped it as excess
# precision, PERF.md section 6), so the state is read itself:
# (4) CARRIED_ERROR_LIMIT: one more request through the timed server after
#     the window returns, beside its ids, what one key head's recurrence was
#     given at every position and that head's sums for the products of
#     dimension 0 (``models/brumby.py``: the ``audit_*`` outputs); the
#     largest relative error over the 16 rows of the state's part and of the
#     key sum's part against ``reference.carried_sums`` of the same inputs,
#     the definition in float64. As served 1.39e-4 - 1.95e-4 over eight
#     seeds (the chip's ``exp`` reads 1.15e-6 low, and a state is 128 gates
#     multiplied up: 1.47e-4) / a state rounded to bfloat16 at every
#     hand-over, the nearest precision below the float32 the configuration
#     states, 4.1e-2 (a state zeroed between chunks 2.1e-2, a dropped key
#     sum 1.0, a gate of 1 65): the limit is the geometric middle of the
#     first two, a factor of 14 from either.
AGREE_FLOOR = 0.93
MEAN_GAP_LIMIT = 0.002
LOGIT_MARGIN = 0.12
CARRIED_ERROR_LIMIT = 2.8e-3

SERVE_CHECK_ROWS = 16
# columns of the head a block of the check's logits (a float32 block of
# 16,384 columns is 335 MB; the whole float32 head would be 3.1 GB)
CHECK_HEAD_BLOCK = 16384

# ---------------------------------------------------------------------------
# configuration


def program_config(config: Dict[str, Any]):
    """The ``models/brumby.py`` config for a configuration file."""
    from paddle_tpu.models import brumby

    return brumby.base_config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        layer_indices=tuple(config["layer_indices"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=config["run"]["dtype"])


def _program(config: Dict[str, Any], new_tokens: int):
    import paddle_tpu as pt
    from paddle_tpu.models import brumby

    return pt.build(brumby.make_generator(program_config(config),
                                          max_new_tokens=new_tokens))


# ids drawn evenly from rows 3 .. vocab - 1 (pad 0, bos 1 and eos 2 never
# drawn): the kimi_k2 family's rule
prompts = kimi_k2.prompts


# ---------------------------------------------------------------------------
# arithmetic: what the algorithm needs, from the configuration alone


def symmetric_rows(config: Dict[str, Any]) -> int:
    d = config["head_dim"]
    return d * (d + 1) // 2


def _counts(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by part."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return {"mixer": 2 * d * q + 2 * d * kv + d * config["num_key_value_heads"],
            "ffn": 3 * d * config["intermediate_size"],
            "head": d * config["vocab_size"]}


def _matrix_params(config: Dict[str, Any]) -> float:
    """Parameters of every matrix a token passes, the head apart."""
    c = _counts(config)
    return config["num_hidden_layers"] * (c["mixer"] + c["ffn"])


def retention_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations one layer's retention needs over a prompt, a token: a query
    head's product with the state (``2 D d``) and the causal half of a
    chunk's scores and values (``2 d (C + 1)``), a key head's update of the
    state (``2 D d``); ``D`` the symmetric 8,256, ``C`` the recurrence's
    chunk (``run.chunk``: ``ops/power_retention.CHUNK``, which is also the
    prefill's piece)."""
    d, big = config["head_dim"], symmetric_rows(config)
    chunk = config["run"]["chunk"]
    return float(rows) * prompt * (
        config["num_attention_heads"] * (2.0 * big * d
                                         + 2.0 * d * (chunk + 1))
        + config["num_key_value_heads"] * 2.0 * big * d)


def prefill_flops(config: Dict[str, Any], rows: int, prompt: int) -> float:
    """Operations the prefill needs: two a multiply-add of every matrix a
    token passes, each layer's retention, and the head for a row's last
    token."""
    return (2.0 * rows * prompt * _matrix_params(config)
            + config["num_hidden_layers"] * retention_flops(config, rows, prompt)
            + 2.0 * rows * _counts(config)["head"])


def state_bytes(config: Dict[str, Any], rows: int) -> float:
    """float32 bytes of one layer's states and key sums, ``rows`` rows."""
    return 4.0 * rows * config["num_key_value_heads"] * symmetric_rows(config) * (
        config["head_dim"] + 1)


def decode_step_bytes(config: Dict[str, Any], rows: int, position: int) -> float:
    """Bytes one step has to move, whatever the position: the bfloat16
    weights (the head, not the embedding, which is read by row) once for the
    batch, and every row's state and key sum of every layer, read and
    written."""
    weights = 2.0 * (_matrix_params(config) + _counts(config)["head"])
    return weights + config["num_hidden_layers"] * 2.0 * state_bytes(config, rows)


def kernel_counts(config: Dict[str, Any], rows: int, prompt: int,
                  kernel: str):
    """``(operations, bytes, calls)`` all calls of ``kernel`` in one
    request's prefill need, for ``readers/kernel_roofline.py``; None for a
    kernel the family does not count there."""
    if kernel != "retention_fwd":
        return None
    n, hd = config["num_hidden_layers"], config["head_dim"]
    chunk = min(config["run"]["chunk"], prompt)
    calls = -(-prompt // chunk)
    wide = (config["num_attention_heads"] + config["num_key_value_heads"]) * hd
    # q and o, k and v in bfloat16, the gates; a call reads and writes the states
    moved = (2.0 * 2 * rows * prompt * wide
             + 4.0 * rows * prompt * config["num_key_value_heads"]
             + calls * 2.0 * state_bytes(config, rows))
    return n * retention_flops(config, rows, prompt), n * moved, n * calls


def step_kernel_counts(config: Dict[str, Any], rows: int, kernel: str):
    """``(operations, bytes)`` one call of the one-token kernel needs, for
    ``readers/step_kernel_roofline.py``: a layer's states read and written,
    ``2 D (d + 1)`` operations a key head's update and a query head's
    read-out."""
    if kernel != "retention_step":
        return None
    big, d = symmetric_rows(config), config["head_dim"]
    ops = 2.0 * rows * big * (d + 1) * (config["num_attention_heads"]
                                        + config["num_key_value_heads"])
    return ops, 2.0 * state_bytes(config, rows)


# ---------------------------------------------------------------------------
# seeded weights


EMBEDDING, FINAL_NORM, HEAD = "tok/embedding_0/w", "final_norm_0/g", "lm_head_0/w"

# Two scales are set so that the mechanism moves a logit. A zero-mean gate
# puts gamma near 1/2: the state forgets in a handful of tokens and nothing
# carried across a chunk reaches a logit. The gate's bias is GATE_BIAS and
# its matrix is drawn GATE_STD / sqrt(fan_in), so that W_g u + b_g lies
# within about 4.85 +- 1.4 and -log gamma between 1/512 and 1/32, as a
# trained model's does. Degree 2 has no temperature (a scale on q cancels in
# the division), and the weighted mean of some hundred values is a tenth of
# a value's size: W_o is drawn MIXER_OUT_GAIN / sqrt(fan_in), so that the
# mixer adds to the residual stream what the FFN adds.
GATE_BIAS = 4.85
GATE_STD = 0.7
MIXER_OUT_GAIN = 4.0


def parameter_table(config: Dict[str, Any]):
    """The program's parameters (``name -> ShapeDtypeStruct``, sorted as
    ``prog.init`` gives them) by arithmetic from the configuration. The
    ``kimi_k2`` family asks the program (``jax.eval_shape`` of its init);
    here that is a trace of the whole generator, kernels' bodies and all,
    twice a run (export and check): seconds of set-up each and some 2,900
    events in ``core/profiler``'s ring. ``tests/test_brumby.py`` holds this
    table to the program's own."""
    import jax
    import jax.numpy as jnp

    d, hd, f = config["hidden_size"], config["head_dim"], config["intermediate_size"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    held, f32 = jnp.dtype(config["run"]["dtype"]), jnp.dtype(jnp.float32)
    table = {EMBEDDING: ((config["vocab_size"], d), held), FINAL_NORM: ((d,), f32),
             HEAD: ((d, config["vocab_size"]), held)}
    for index in config["layer_indices"]:
        for name, shape, dtype in (
                ("mixer/attn_norm/g", (d,), f32), ("mixer/qkv/w", (q + 2 * kv, d), held),
                ("mixer/q_norm/g", (hd,), f32), ("mixer/k_norm/g", (hd,), f32),
                ("mixer/gate/w", (d, config["num_key_value_heads"]), held),
                ("mixer/gate/b", (config["num_key_value_heads"],), f32),
                ("mixer/o/w", (q, d), held), ("ffn/ffn_norm/g", (d,), f32),
                ("ffn/gate/w", (d, f), held), ("ffn/up/w", (d, f), held),
                ("ffn/down/w", (f, d), held)):
            table[f"layer_{index}/{name}"] = (shape, dtype)
    return {name: jax.ShapeDtypeStruct(*table[name]) for name in sorted(table)}


class Weights(kimi_k2.Weights):
    """The generator's weights as seeded draws, a tensor of a layer at a
    time (the ``kimi_k2`` family's maker over this program's parameter
    table, where every layer has its own names, ``layer_<published
    index>/...``): every matrix N(0, 1 / fan_in) but the gate's and W_o
    (above), the embedding N(0, 1), norm scales 1, the gate's bias
    ``GATE_BIAS``."""

    def __init__(self, config: Dict[str, Any], seed: int, prompt_len: int,
                 new_tokens: int):
        self.config, self.seed, self.prompt_len = config, seed, prompt_len
        self.shapes = parameter_table(config)

    @staticmethod
    def stacked(name: str) -> bool:
        return False

    def _std(self, name: str, shape) -> float:
        if name.startswith("tok/"):
            return 1.0
        if name.endswith("qkv/w"):                  # stored [out, in]
            return shape[-1] ** -0.5
        gain = (GATE_STD if name.endswith("mixer/gate/w") else
                MIXER_OUT_GAIN if name.endswith("mixer/o/w") else 1.0)
        return gain * shape[-2] ** -0.5             # [in, out]

    def slab(self, name: str, layer: int = 0, on_host: bool = False):
        if name.endswith("gate/b"):
            import jax.numpy as jnp

            full = self.shapes[name]
            return (np if on_host else jnp).full(full.shape, GATE_BIAS, full.dtype)
        return super().slab(name, layer, on_host)

    def _get(self, layer: int):
        import jax.numpy as jnp

        scope = f"layer_{self.config['layer_indices'][layer]}/"
        return lambda n: self.slab(scope + n).astype(jnp.float32)

    def reference_mixer(self, layer: int) -> Dict[str, Any]:
        return reference_mixer(self._get(layer), self.config)

    def reference_ffn(self, layer: int) -> Dict[str, Any]:
        return reference_ffn(self._get(layer))


def reference_mixer(get, config: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's mixer under the reference's names; ``get(name)`` gives
    the program's float32 tensor of that layer by its name in the layer's
    scope. The program holds q, k and v as one matrix ``[out, in]``, q's
    rows first; the reference takes three ``[in, out]``."""
    out = {n.split("/")[0]: get("mixer/" + n) for n in (
        "attn_norm/g", "q_norm/g", "k_norm/g", "gate/w", "o/w")}
    out["gate_bias"] = get("mixer/gate/b")
    q = config["num_attention_heads"] * config["head_dim"]
    k = config["num_key_value_heads"] * config["head_dim"]
    qkv = get("mixer/qkv/w")
    out.update(q=qkv[:q].T, k=qkv[q:q + k].T, v=qkv[q + k:].T)
    return out


def reference_ffn(get) -> Dict[str, Any]:
    return {"ffn_norm": get("ffn/ffn_norm/g"), "ffn_gate": get("ffn/gate/w"),
            "ffn_up": get("ffn/up/w"), "ffn_down": get("ffn/down/w")}


def reference_params(params: Dict[str, Any], config: Dict[str, Any]):
    """A whole parameter dict of the program under the reference's names,
    float32 (the tests' small sizes; the chip check streams, see
    :func:`reference_hidden`)."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layers = []
    for index in config["layer_indices"]:
        get = lambda n, scope=f"layer_{index}/": f32(params[scope + n])
        layers.append({**reference_mixer(get, config), **reference_ffn(get)})
    return {"emb": f32(params[EMBEDDING]), "final_norm": f32(params[FINAL_NORM]),
            "head": f32(params[HEAD]), "layers": layers}


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
    from paddle_tpu.models import brumby

    buckets = sorted(int(b) for b in buckets)
    weights = Weights(config, seed, prompt_len, new_tokens)
    decode.export_decoder(dirname, program_config(config), new_tokens,
                          np.zeros((buckets[-1], prompt_len), np.int32),
                          params=weights.host_params(),
                          batch_buckets=buckets, model=brumby)


def reference_hidden(config: Dict[str, Any], weights: Weights, ids: np.ndarray,
                     first: int, edit=None):
    """The reference's last hidden state ``[rows, s - first, d]`` (on the
    device, before the final norm) for the sequences ``ids [rows, s]``: a
    layer's mixer and then its FFN at a time, each half's float32 weights
    made from the seed once for all the rows and freed before the next is
    made; a mixer takes a sequence at a time, the FFN all tokens in blocks.
    ``edit(params) -> params`` may change what a half is given (the
    sensitivity run's reference in a lower precision)."""
    import jax
    import jax.numpy as jnp

    sh = reference.shape_of(config)
    edit = edit or (lambda lp: lp)
    rows, s = ids.shape
    mixer = jax.jit(lambda x, lp: jax.lax.map(
        lambda row: reference.mixer_part(row, lp, sh), x))
    ffn = jax.jit(lambda x, lp: reference.ffn_part(
        x.reshape(rows * s, -1), lp, sh).reshape(x.shape))
    x = weights.slab(EMBEDDING)[jnp.asarray(ids)].astype(jnp.float32)
    for layer in range(config["num_hidden_layers"]):
        for make, fn in ((weights.reference_mixer, mixer),
                         (weights.reference_ffn, ffn)):
            lp = edit(make(layer))
            x = jax.block_until_ready(fn(x, lp))
            del lp
    return x[:, first:]


def reference_logits(config: Dict[str, Any], weights: Weights, hidden, edit=None):
    """``hidden [rows, n, d] ->`` the reference's logits, a row ``[n,
    vocab]`` at a time (a generator: 16 rows' float32 logits over the whole
    vocabulary are 2.5 GB, a row's 0.16 GB), the head's columns in blocks of
    ``CHECK_HEAD_BLOCK`` (the whole float32 head would be 3.1 GB)."""
    import jax
    import jax.numpy as jnp

    sh = reference.shape_of(config)
    head = (edit or (lambda lp: lp))({"head": weights.slab(HEAD)})["head"]
    norm = weights.slab(FINAL_NORM).astype(jnp.float32)
    block = jax.jit(lambda h, cols: reference.head_logits(
        h, norm, cols.astype(jnp.float32), sh))
    for row in hidden:
        yield jnp.concatenate(
            [block(row, head[:, c:c + CHECK_HEAD_BLOCK])
             for c in range(0, head.shape[1], CHECK_HEAD_BLOCK)], axis=1)


def _row_stats(logits, served):
    """One row's ``[new, vocab]`` logits against its served ids ``[new]``:
    what :func:`served_check` needs of them, small."""
    import jax.numpy as jnp

    top, at = jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1)
    pick = lambda ids: jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    second = jnp.max(jnp.where(
        jnp.arange(logits.shape[-1])[None, :] == at[:, None], -jnp.inf, logits),
        axis=-1)
    return {"top": top, "got": pick(served), "second": second,
            "other": pick((served + 1) % logits.shape[-1]),
            "mean": jnp.mean(logits, axis=-1),
            "square": jnp.mean(logits * logits, axis=-1)}


def ready_servers():
    """The ``PredictorServer``s of this process that take requests.
    ``drivers/serving.verdict`` hands a family the served ids and nothing
    else of a result, nor the server (PERF.md section 7), so the check
    looks for it among the process's live objects."""
    import gc

    from paddle_tpu.serving import PredictorServer

    return [o for o in gc.get_objects() if isinstance(o, PredictorServer)
            and o.health()["ready"]]


def served_audit(prompt_ids: np.ndarray) -> Dict[str, np.ndarray]:
    """One more request through the timed server (the process's one ready
    server), whole: the rows ``prompt_ids [rows, p]`` in requests of its
    largest bucket (a last short one padded with its own first row), every
    output fetched."""
    ready = ready_servers()
    if len(ready) != 1:
        raise RuntimeError(f"{len(ready)} ready servers in this process")
    bucket = max(ready[0].report()["batch_buckets"])
    parts = []
    for start in range(0, len(prompt_ids), bucket):
        rows = prompt_ids[start:start + bucket]
        pad = np.repeat(rows[:1], bucket - len(rows), axis=0)
        out = ready[0].submit({"prompt_ids": np.concatenate([rows, pad])}
                              ).result(timeout=600)
        parts.append({k: np.asarray(v)[:len(rows)] for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def carried_check(audit: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """A request's ``audit_*`` outputs (``models/brumby.py``) against the
    definition: a row at a time, the relative error (Frobenius) of the
    state's part and of the key sum's part of ``audit_sums`` against
    ``reference.carried_sums`` of what the recurrence was given; the
    largest of all of them must be finite and within
    ``CARRIED_ERROR_LIMIT``."""
    hd = audit["audit_sums"].shape[-1]
    # a value's dimensions and the key sum; the rows behind them are the
    # layout's own (zeros)
    sums = np.asarray(audit["audit_sums"], np.float64)[:, :hd + 1]
    err = []
    for got, k, v, log_gamma in zip(sums, audit["audit_k"], audit["audit_v"],
                                    audit["audit_log_gamma"]):
        want = reference.carried_sums(k, v, log_gamma)
        err.append([np.linalg.norm(got[part] - want[part])
                    / np.linalg.norm(want[part])
                    for part in (slice(0, hd), slice(hd, hd + 1))])
    err = np.asarray(err)
    return {"ok": bool(np.isfinite(err).all()
                       and err.max() <= CARRIED_ERROR_LIMIT),
            "carried_error": float(err.max()),
            "state_error": float(err[:, 0].max()),
            "key_sum_error": float(err[:, 1].max()),
            "positions": int(audit["audit_k"].shape[1])}


def served_check(config: Dict[str, Any], params: Weights,
                 prompt_ids: np.ndarray, served: np.ndarray,
                 eos_id: int = 2, edit=None, audit=None) -> Dict[str, Any]:
    """One full reference forward over prompt + served ids; at every
    generated position (up to a row's first end-of-sequence id, after which
    the generator forces it) the served token's reference logit, in
    deviations of the reference's logits, must be within ``LOGIT_MARGIN`` of
    the largest, the mean of those gaps within ``MEAN_GAP_LIMIT``, and at
    least ``AGREE_FLOOR`` of the tokens the reference's own argmax. Then the
    state itself: ``audit``, a request's outputs for these prompts (when
    not given, :func:`served_audit` asks the timed server for them), must
    pass :func:`carried_check`; the share of its ids that are the served
    ones is reported (the rows meet in another order than they were served
    in, and nothing promises the same bits for that)."""
    import jax

    prompt_ids, served = np.asarray(prompt_ids), np.asarray(served)
    if audit is None:
        audit = served_audit(prompt_ids)
    carried = carried_check(audit)
    carried["ids_as_served"] = float((audit["ids"] == served).mean())
    p = prompt_ids.shape[1]
    ids = np.concatenate([prompt_ids, served[:, :-1]], axis=1).astype(np.int32)
    hidden = reference_hidden(config, params, ids, p - 1, edit)
    stats = jax.jit(_row_stats)
    got = [jax.device_get(stats(logits, row.astype(np.int32)))
           for logits, row in zip(reference_logits(config, params, hidden, edit),
                                  served)]
    s = {k: np.stack([g[k] for g in got]).astype(np.float64) for k in got[0]}
    std = float(np.sqrt(s["square"].mean() - s["mean"].mean() ** 2))
    gap = (s["top"] - s["got"]) / std
    ended = np.cumsum(served == eos_id, axis=1) - (served == eos_id) > 0
    gap = np.where(ended, 0.0, gap)
    agree = float(((gap == 0) | ended).mean())
    # what a plainly wrong token would read: the gap of another id at each
    # position (the served id plus one), its first percentile
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
