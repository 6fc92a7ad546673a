"""The GPT family: from a configuration file to the program under test,
its inputs, its operation and byte counts, and its check against the plain
reference.

The configuration file keeps the keys of the published ``config.json``
(``n_layer``, ``n_embd``, ...) and adds a ``run`` group: how this repo runs
it. Traffic generators and arithmetic are copies (of ``chip_smoke.py`` and
``paddle_tpu/core/flops.py``), not imports: the program may change, the
yardstick may not. From ``paddle_tpu`` come only the system under test and
its counters.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.reference import gpt as reference

# Tolerances of the reference checks, with their reasons.
#
# The system computes in bfloat16 with float32 accumulation; the reference
# in float32 at "highest". bfloat16 keeps 8 bits, so one rounding is worth
# 2**-9 = 0.2% and a few dozen layers of them a few percent of a gradient's
# norm, much less of a loss (an average over thousands of tokens).
LOSS_REL_TOL = 1e-3      # |loss - ref| / ref; measured 6e-7 to 6e-6 on the
                         # chip (PR 23). An 8-bit float compute path
                         # (2**-4 per rounding) is off by percents.
# ||g - g_ref|| / ||g_ref|| per tensor. Gradients next to the loss (the head,
# the last block's ffn_out) measured 0.006; those that crossed every block
# (the embedding, the first block's qkv) measured 0.061-0.069 at 24 layers,
# on the chip and on the CPU alike: it is the arithmetic, not the device.
# An 8-bit float path, 32 times coarser per rounding, is past 1.0.
GRAD_REL_TOL = {"shallow": 2e-2, "deep": 0.15}
# Serving returns ids only. At each generated position the served token's
# reference logit must be within this of the reference's largest logit.
# Random Xavier weights give logits of standard deviation about 0.2 with
# the largest near 0.9; a wrong cache index or mask draws a token whose
# logit is typically 0.5 or more below the top, bfloat16 rounding moves a
# logit by about 0.01.
LOGIT_MARGIN = 0.1

CHECK_ROWS = 2           # sequences of the train check
SERVE_CHECK_ROWS = 8     # served rows held to the reference
GRAD_PROBE_LR = float(2 ** 24)  # see train_check


# ---------------------------------------------------------------------------
# configuration


def program_config(config: Dict[str, Any]):
    """The ``models/gpt.py`` config for a configuration file."""
    from paddle_tpu.models import gpt

    run = config["run"]
    return gpt.base_config(
        vocab_size=config["vocab_size"], max_len=config["n_positions"],
        d_model=config["n_embd"], d_inner=config["n_inner"],
        num_heads=config["n_head"], num_layers=config["n_layer"],
        use_flash=run["use_flash"], fused_ce=run["fused_ce"],
        remat=run["remat"], dropout=run["dropout"], dtype=run["dtype"])


def set_flags(config: Dict[str, Any], seed: int) -> None:
    from paddle_tpu.core.config import set_flag

    set_flag("default_compute_dtype", config["run"]["default_compute_dtype"])
    set_flag("seed", seed)


def mesh_and_rules(config: Dict[str, Any], devices):
    """``(mesh, rules)`` of the configuration's layout, or ``(None, None)``
    on one chip."""
    import paddle_tpu as pt

    axes = config["layout"].get("mesh")
    if not axes:
        return None, None
    rules = getattr(pt.parallel, config["layout"]["rules"])()
    return pt.make_mesh(dict(axes), devices=list(devices)), rules


# ---------------------------------------------------------------------------
# inputs (copied from chip_smoke.lm_batches)


def lm_batches(vocab: int, batch: int, seq: int, seed: int, n: int,
               stream: int = 0) -> List[Dict[str, np.ndarray]]:
    """``n`` batches from a noisy cycle over 256 token ids (pad id 0 and
    the generator's bos/eos ids never drawn): nine times in ten the next
    token is the next of the cycle, so there is something to learn and a
    prompt decides its continuation. ``stream`` draws other sequences over
    the same cycle. A pure function of its arguments."""
    cycle = np.random.RandomState(seed).permutation(vocab - 3)[:256] + 3
    rng = np.random.RandomState(seed + 1 + stream)
    out = []
    for _ in range(n):
        hop = np.where(rng.rand(batch, seq + 1) < 0.9, 1,
                       rng.randint(0, len(cycle), (batch, seq + 1)))
        ids = cycle[np.cumsum(hop, axis=1) % len(cycle)].astype(np.int32)
        out.append({"ids": ids[:, :-1], "labels": ids[:, 1:]})
    return out


def prompts(vocab: int, rows: int, length: int, seed: int, n: int,
            stream: int = 7) -> List[np.ndarray]:
    return [b["ids"] for b in lm_batches(vocab, rows, length, seed, n, stream)]


# ---------------------------------------------------------------------------
# arithmetic (train FLOPs copied from core/flops.gpt_train_flops)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Operations the forward and backward passes need per token: block
    matmuls, causal attention (halved), the head. Recomputation (remat, the
    flash backward's second look at the scores) is not counted."""
    d, di, layers = config["n_embd"], config["n_inner"], config["n_layer"]
    f = 6.0 * (4 * d * d + 2 * d * di) * layers
    f += 12.0 * layers * seq * d / 2
    f += 6.0 * d * config["vocab_size"]
    return f


def flash_flops_per_step(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the flash-attention algorithm needs for one train step:
    seven ``s x s x head_dim`` matmuls per head (two forward; five backward,
    one of them the recomputed scores, since the algorithm stores none),
    halved because the mask is causal. What the split into a dq and a dkv
    kernel recomputes beyond that is not needed and not counted."""
    return 7.0 * batch * seq * seq * config["n_embd"] * config["n_layer"]


def flash_calls_per_step(config: Dict[str, Any]) -> int:
    """Forward, dq and dkv in every layer; with remat the backward pass runs
    the forward kernel a second time (work done, not work needed)."""
    return (4 if config["run"]["remat"] else 3) * config["n_layer"]


def decode_min_bytes(config: Dict[str, Any], rows: int, prompt: int,
                     new_tokens: int) -> float:
    """Bytes the algorithm has to move through HBM for one batch at the
    configuration's precision (bfloat16): the block and head weights once
    for the prefill and once per cached decode step, and in each decode
    step the keys and values up to the current position. The first token
    comes from the prefill, so ``new_tokens - 1`` decode steps run."""
    d, di, layers = config["n_embd"], config["n_inner"], config["n_layer"]
    weights = 2.0 * (layers * (4 * d * d + 2 * d * di)
                     + d * config["vocab_size"])
    kv_per_pos = 2.0 * 2 * layers * rows * d          # k and v, 2 bytes
    steps = max(new_tokens - 1, 0)
    kv = kv_per_pos * sum(prompt + j for j in range(1, steps + 1))
    return weights * (1 + steps) + kv


# ---------------------------------------------------------------------------
# the system under test: training


def make_trainer(config: Dict[str, Any], seed: int, sample_feed, devices,
                 optimizer=None):
    """``pt.build`` -> ``pt.Trainer`` -> ``startup``, on the layout the
    configuration states."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models import gpt

    set_flags(config, seed)
    if optimizer is None:
        o = config["run"]["optimizer"]
        optimizer = getattr(opt, o["name"])(o["lr"],
                                            weight_decay=o["weight_decay"])
    mesh, rules = mesh_and_rules(config, devices)
    trainer = pt.Trainer(pt.build(gpt.make_model(program_config(config))),
                         optimizer, loss_name="loss", fetch_list=["loss"],
                         mesh=mesh, sharding_rules=rules)
    trainer.startup(sample_feed=sample_feed)
    return trainer


def step_structure(trainer, feed) -> Dict[str, Any]:
    """Compile-time facts of the train step: Pallas kernel calls in it, and
    under a mesh its collectives and how many parameters tp really splits."""
    import jax
    from paddle_tpu import debugger

    out = {"kernel_calls": debugger.step_kernel_calls(trainer, feed)}
    if trainer.mesh is not None:
        report = debugger.collective_report(trainer, feed)
        out["collectives"] = {k: v["count"]
                              for k, v in report["collectives"].items()}
        out["tp_sharded_params"] = sum(
            1 for v in trainer.scope.params.values()
            if "tp" in jax.tree.leaves(tuple(v.sharding.spec))
            and len({str(s.index) for s in v.addressable_shards}) > 1)
    return out


def _find(params: Dict[str, Any], suffix: str) -> str:
    hits = [k for k in params if k.endswith(suffix)]
    if len(hits) != 1:
        raise KeyError(f"{len(hits)} parameters end in {suffix!r}")
    return hits[0]


def reference_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's flat parameter dict under the reference's names."""
    stack = "encoder_stack/"
    return {
        "emb": params[_find(params, "embedding_0/w")],
        "head": params[_find(params, "lm_head_0/w")],
        "ln_f/scale": params[_find(params, "layer_norm_0/scale")],
        "ln_f/bias": params[_find(params, "layer_norm_0/bias")],
        "layers": {k.split(stack, 1)[1]: v for k, v in params.items()
                   if stack in k},
    }


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def train_check(config: Dict[str, Any], seed: int, seq: int, devices) -> Dict[str, Any]:
    """Loss and four gradients of the system against the reference, at the
    full configuration on ``CHECK_ROWS`` seeded sequences.

    The gradients are read through the entry a user has: one step of a
    second ``Trainer`` with plain ``SGD`` at a large power-of-two rate, so
    that ``(before - after) / rate`` is the gradient that step computed, on
    the layout and through the step builder the cell measures. bfloat16
    parameters round the update to 8 bits, which is the precision their
    gradient had."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optimizer as opt

    feed = lm_batches(config["vocab_size"], CHECK_ROWS, seq, seed + 977, 1)[0]
    trainer = make_trainer(config, seed, feed, devices,
                           optimizer=opt.SGD(GRAD_PROBE_LR))
    one = devices[0]
    before = {k: jax.device_put(jnp.copy(v), one)
              for k, v in trainer.scope.params.items()}
    loss = float(trainer.step(feed)["loss"])
    layers = config["n_layer"]
    last = f"ffn_out/w[{layers - 1}]"
    probes = {"emb": (_find(before, "embedding_0/w"), None),
              "head": (_find(before, "lm_head_0/w"), None),
              "qkv/w[0]": (_find(before, "encoder_stack/qkv/w"), 0),
              last: (_find(before, "encoder_stack/ffn_out/w"), layers - 1)}
    tol = {"emb": GRAD_REL_TOL["deep"], "qkv/w[0]": GRAD_REL_TOL["deep"],
           "head": GRAD_REL_TOL["shallow"], last: GRAD_REL_TOL["shallow"]}
    got = {}
    for label, (name, layer) in probes.items():
        a = np.asarray(jax.device_put(trainer.scope.params[name], one),
                       np.float32)
        b = np.asarray(before[name], np.float32)
        delta = (b - a) / GRAD_PROBE_LR
        got[label] = delta if layer is None else delta[layer]
    del trainer
    with jax.default_device(one):
        ref_loss, ref = reference.loss_and_grads(
            reference_params(before), jax.device_put(feed["ids"], one),
            jax.device_put(feed["labels"], one), config["n_head"],
            [("qkv/w", 0), ("ffn_out/w", layers - 1)])
    ref_loss = float(ref_loss)
    errs = {k: _rel(got[k], ref[k]) for k in got}
    loss_gap = abs(loss - ref_loss) / abs(ref_loss)
    return {"ok": bool(np.isfinite(loss) and loss_gap <= LOSS_REL_TOL
                       and all(errs[k] <= tol[k] for k in errs)),
            "loss": loss, "ref_loss": ref_loss, "loss_rel_gap": loss_gap,
            "grad_rel_err": errs}


# ---------------------------------------------------------------------------
# the system under test: serving


def decoder_params(config: Dict[str, Any], seed: int, prompt_len: int,
                   new_tokens: int) -> Dict[str, Any]:
    """The generator's weights, made on the device from the seed in one
    jitted call (the same values every time it is called)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import gpt

    set_flags(config, seed)
    gen = pt.build(gpt.make_generator(program_config(config),
                                      max_new_tokens=new_tokens))
    one_row = np.zeros((1, prompt_len), np.int32)
    return jax.jit(lambda key: gen.init(key, prompt_ids=one_row)[0])(
        jax.random.PRNGKey(seed))


def export_decoder(config: Dict[str, Any], seed: int, dirname: str,
                   prompt_len: int, new_tokens: int, buckets) -> None:
    """``fleet.decode.export_decoder`` of the seeded weights with the given
    batch buckets."""
    from paddle_tpu.fleet import decode

    buckets = sorted(int(b) for b in buckets)
    decode.export_decoder(dirname, program_config(config), new_tokens,
                          np.zeros((buckets[-1], prompt_len), np.int32),
                          params=decoder_params(config, seed, prompt_len,
                                                new_tokens),
                          batch_buckets=buckets)


def served_check(config: Dict[str, Any], params, prompt_ids: np.ndarray,
                 served: np.ndarray, eos_id: int = 2) -> Dict[str, Any]:
    """One full reference forward over prompt + served ids; at every
    generated position (up to a row's first end-of-sequence id, after which
    the generator forces it) the served token's reference logit must be
    within ``LOGIT_MARGIN`` of the largest."""
    import jax

    prompt_ids, served = np.asarray(prompt_ids), np.asarray(served)
    p = prompt_ids.shape[1]
    ids = np.concatenate([prompt_ids, served[:, :-1]], axis=1).astype(np.int32)
    logits = np.asarray(reference.logits(reference_params(params),
                                         jax.numpy.asarray(ids),
                                         config["n_head"], first=p - 1))
    got = np.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    gap = logits.max(-1) - got
    ended = np.cumsum(served == eos_id, axis=1) - (served == eos_id) > 0
    gap = np.where(ended, 0.0, gap)
    return {"ok": bool(np.isfinite(gap).all() and gap.max() <= LOGIT_MARGIN),
            "rows": int(served.shape[0]), "worst_logit_gap": float(gap.max()),
            "argmax_agree": float(((gap == 0) | ended).mean()),
            "distinct_ids": int(len(np.unique(served)))}
