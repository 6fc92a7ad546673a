"""Decode-side serving workload: batched incremental decoding with the
int8 KV cache behind the continuous-batching scheduler.

``models/gpt.make_generator`` (prefill + greedy/beam decode over a KV
cache, optionally stored int8 — ``layers/stacked.quantize_kv``) was an
*example*; this module promotes it to a served workload. The generator
program exports through the ordinary ``save_inference_model`` door
with batch buckets, so single-prompt decode requests coalesce into one
bucket-sized dispatch exactly like classifier traffic — decode is
HBM-bound, so filling a dispatch's rows with real prompts instead of
pad rows converts wasted cache-read bandwidth directly into served
tokens. Rows are independent through prefill and decode (per-row
attention, per-row argmax), so a coalesced request's token ids equal
its sequential pad-alone decode — pinned in ``tests/test_fleet.py``.

The cache lives inside the exported program and never crosses its
boundary: one lane-dense ``[rows, T, heads*head_dim]`` slab for k and
one for v a layer (int8: the same, with scales ``[rows, T, heads]``),
rows leading and the model width minor, so the TPU's (8, 128) tiles
hold no padding and a decode step reads each slab once, as stored
(``layers/stacked.py``; a ``[rows, h, T, 64]`` cache was held and read
at twice its size).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np


def export_decoder(dirname: str, cfg, max_new_tokens: int,
                   example_prompt, params: Optional[Dict[str, Any]] = None,
                   batch_buckets: Sequence[int] = (),
                   seed: int = 0, model=None) -> Tuple[Any, Dict[str, Any]]:
    """Export a generator program (greedy decode over the config's cache)
    as a multi-bucket ``save_inference_model`` artifact. ``model`` is the
    module whose ``make_generator(cfg, max_new_tokens=...)`` builds it:
    ``models.gpt`` when not given (a KV cache, ``cfg.kv_cache_dtype="int8"``
    for the int8 one), ``models.kimi_k2`` for the latent cache; every
    family goes through this one door.

    ``example_prompt``: int32 ``[b, p]`` prompt ids — its batch size
    becomes a bucket; ``batch_buckets`` adds more. ``params`` defaults
    to a fresh init (params trained via ``gpt.make_model`` share names
    and load directly); given on the host, they never touch the device
    here. Returns ``(program, params)``."""
    import jax

    import paddle_tpu as pt
    from .. import io as pio
    if model is None:
        from ..models import gpt as model

    prog = pt.build(model.make_generator(cfg, max_new_tokens=max_new_tokens))
    feed = {"prompt_ids": np.asarray(example_prompt, np.int32)}
    if params is None:
        params, _ = prog.init(jax.random.PRNGKey(seed), **feed)
    pio.save_inference_model(dirname, prog,
                             jax.tree.map(np.asarray, params), {}, feed,
                             batch_buckets=list(batch_buckets) or None)
    return prog, params


def decode_server(dirname: str, max_wait_ms: float = 5.0,
                  workers: int = 1, queue_size: int = 32,
                  **server_kw):
    """A ``PredictorServer`` over an :func:`export_decoder` artifact
    with continuous batching on — the decode serving front. Single
    prompts coalesce into the largest exported bucket within
    ``max_wait_ms``; token-id outputs slice back per caller."""
    from .. import io as pio
    from ..serving import PredictorServer
    from .batching import BatchPolicy

    return PredictorServer(pio.load_inference_model(dirname),
                           workers=workers, queue_size=queue_size,
                           batch_policy=BatchPolicy(max_wait_ms=max_wait_ms),
                           **server_kw)


__all__ = ["decode_server", "export_decoder"]
