"""MiniCPM-SALA (``model_type: minicpm_sala``) — the serving path of a
decoder whose layers are of two kinds (``layers/sala.py``): ``minicpm4``
mixers, block-selected sparse attention over a grouped key/value cache, and
``lightning-attn`` mixers, linear attention that carries a float32 state.
Every layer has a dense gated SiLU FFN; the muP scalings of the config
apply: the embedding times ``scale_emb``, every residual branch times
``scale_depth / sqrt(published depth)``, the final norm's output over
``hidden_size / dim_model_base``.

``mixer_types`` lists the kinds of the layers held here and
``layer_indices`` their published indices (a pipeline stage holds a run of
the published stack; a lightning layer's decay follows its published
index, ``published_layers`` deep); the rest of the depth lies on further
chips. Nothing stands in for it.

This module serves only: :func:`make_generator`, through the contract of
``layers/decoding.py`` (``prompt_ids [b, p] -> {"ids": [b, new]}``, the
first step's write-switch form). There is no ``make_model``: no cut of
this model trains on one chip, and neither kernel has a backward (ROADMAP
R5, R15). Matrices are created and held in ``cfg.dtype``; norm scales are
float32.

The carried state has two kinds of entry, in per-layer lists: for each
sparse layer a key slab, a value slab and a compressed-key slab, lane-dense
(``[rows, T, kv_heads * 128]``, ``[rows, T / 16, kv_heads * 128]``); for
each lightning layer one float32 state ``[rows, heads, 128, 128]``.
``decode.plan`` says so (``cache_kind="kv+state"``).

The prefill walks the prompt in chunks of ``prefill_chunk`` tokens (a
prompt of 32k does not go through a 16,384-wide FFN in one piece) and
carries both kinds of state from chunk to chunk under one ``lax.scan`` with
no conditional in it; a prompt within ``dense_len`` goes through in one
piece, its sparse layers through the flash kernel. The layers are written
out in their published order, in the prefill's chunk and in the step alike,
and each has its own parameters (``layer_<published index>/...``), not a
slice of a stack: the compiler laid one slice of a twelve-layer stack into
VMEM ahead of its use, fused the eleven sibling slices into that copy, and
so copied the whole stack every step (1.4 ms of a 19 ms step a stack:
PERF.md section 6, PR 33).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.errors import enforce
from ..framework import name_scope
from ..layers import blocks as B
from ..layers import decoding
from ..layers import sala as S

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# the published stack: sparse at 0, 9, 16, 17, 22, 29, 30, 31
PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclasses.dataclass
class MiniCPMSALAConfig:
    """Published key names; ``sparse_*`` are the ``sparse_config`` group."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    num_hidden_layers: int = 32             # layers held here
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS     # their kinds, in order
    layer_indices: Optional[Tuple[int, ...]] = None     # their published indices
    published_layers: int = 32              # the depth the scalings refer to
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    intermediate_size: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_topk: int = 64
    sparse_dense_len: int = 8192
    prefill_chunk: int = 4096               # tokens of a prompt a pass
    dtype: str = "bfloat16"

    @property
    def indices(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_hidden_layers))
                if self.layer_indices is None else tuple(self.layer_indices))

    @property
    def sparse(self) -> S.SparseDims:
        return S.SparseDims(
            self.hidden_size, self.num_attention_heads,
            self.num_key_value_heads, self.head_dim, self.rms_norm_eps,
            self.sparse_kernel_size, self.sparse_kernel_stride,
            self.sparse_block_size, self.sparse_init_blocks,
            self.sparse_window_size, self.sparse_topk, self.sparse_dense_len)

    @property
    def lightning(self) -> S.LightningDims:
        return S.LightningDims(self.hidden_size, self.lightning_nh,
                               self.lightning_head_dim, self.rms_norm_eps,
                               self.rope_theta)

    @property
    def branch_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)


def base_config(**kw) -> MiniCPMSALAConfig:
    return MiniCPMSALAConfig(**kw)


def _decoder(cfg: MiniCPMSALAConfig, prompt_ids, max_new_tokens: int):
    """``(state0, step_fn, audit)``, the contract of ``layers/decoding.py``:
    the parameters (created or fetched here, once, by name), the chunked
    prefill of ``prompt_ids`` and the one-token step that follows it; no
    audit."""
    kinds, indices = tuple(cfg.mixer_types), cfg.indices
    enforce(len(kinds) == len(indices) == cfg.num_hidden_layers
            and set(kinds) <= {SPARSE, LIGHTNING},
            f"minicpm_sala: {cfg.num_hidden_layers} layers, kinds {kinds}, "
            f"published indices {indices}")
    sp, li, dtype = cfg.sparse, cfg.lightning, jnp.dtype(cfg.dtype)
    rows, p_len = prompt_ids.shape
    decoding.check_length(p_len, max_new_tokens, cfg.max_position_embeddings)
    d, eps, a = cfg.hidden_size, cfg.rms_norm_eps, cfg.branch_scale
    n_sparse, n_light = kinds.count(SPARSE), kinds.count(LIGHTNING)
    # which of its kind's entries of the carried state a layer has
    slot = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    # every parameter once, by name, a layer under its published index; the
    # loops close over the arrays
    w_emb = decoding.token_embedding(cfg.vocab_size, d, dtype)
    per_layer = []
    for kind, index in zip(kinds, indices):
        with name_scope(f"layer_{index}"):
            mixer = (S.sparse_params(sp, dtype) if kind == SPARSE
                     else S.lightning_params(li, dtype))
            per_layer.append((mixer, B.gated_ffn_params(
                d, cfg.intermediate_size, dtype)))
    final_g, w_head = decoding.untied_head(cfg.vocab_size, d, dtype)
    log_decay = [S.lightning_log_decay(li.heads, l, cfg.published_layers)
                 for l, k in zip(indices, kinds) if k == LIGHTNING]

    def embed(ids):
        with jax.named_scope("tok"):
            return (w_emb[ids].astype(jnp.float32)
                    * cfg.scale_emb).astype(dtype)

    def head(x_last):   # [rows, d] -> log-probs
        with jax.named_scope("head"):
            h = B.rms_norm(x_last, final_g, eps)
            h = (h.astype(jnp.float32)
                 / (cfg.hidden_size / cfg.dim_model_base)).astype(dtype)
            return decoding.log_probs(h, w_head)

    # ---- the carried state: slabs a sparse layer, a state a lightning layer
    blk = sp.block_size
    total = -(-(p_len + max_new_tokens) // blk) * blk    # whole blocks
    selected = n_sparse > 0 and p_len > sp.dense_len
    chunk = min(cfg.prefill_chunk, p_len) if selected else p_len
    enforce(p_len % chunk == 0 and (not selected or chunk % blk == 0),
            f"minicpm_sala: a prompt of {p_len} beyond dense_len "
            f"{sp.dense_len} is walked in whole chunks of {chunk}, whole "
            f"blocks of {blk}")
    enforce(not n_sparse or total <= sp.dense_len or (
        total >= sp.window_size and total // blk >= sp.n_sel),
            f"minicpm_sala: a cache of {total} is shorter than the window")
    width = sp.kv_heads * sp.head_dim
    carried = {
        "k": [jnp.zeros((rows, total, width), dtype)] * n_sparse,
        "v": [jnp.zeros((rows, total, width), dtype)] * n_sparse,
        "ck": [jnp.zeros((rows, total // sp.kernel_stride, width), dtype)]
        * n_sparse,
        "s": [jnp.zeros((rows, li.heads, li.head_dim, li.head_dim),
                        jnp.float32)] * n_light}
    decoding.record_plans(
        "kv+state", rows, total, cfg.num_attention_heads,
        cfg.num_hidden_layers, cfg.dtype, width if n_sparse else 0,
        {"kv": carried["k"] + carried["v"], "index": carried["ck"],
         "state": carried["s"]},
        prefill={"chunk": chunk, "chunks": p_len // chunk},
        sparse_layers=n_sparse, state_layers=n_light, state_dtype="float32",
        first_step="write_switch")

    def through(x, carried, mix_sparse, mix_light):
        """``x`` through the layers held, each with its own entries of the
        carried state."""
        carried = {k: list(v) for k, v in carried.items()}
        for i, kind in enumerate(kinds):
            lp, ffn = per_layer[i]
            j = slot[i]
            if kind == SPARSE:
                x, (carried["k"][j], carried["v"][j], carried["ck"][j]) = (
                    mix_sparse(x, lp, (carried["k"][j], carried["v"][j],
                                       carried["ck"][j])))
            else:
                x, carried["s"][j] = mix_light(x, lp, carried["s"][j],
                                               log_decay[j])
            x = B.ffn_block(x, ffn, eps, scale=a)
        return x, carried

    # ---- prefill: the prompt a chunk at a time (whole chunks: no tail)
    def prefill_piece(carried, p0, length):
        ids = jax.lax.dynamic_slice_in_dim(prompt_ids, p0, length, axis=1)
        x, carried = through(
            embed(ids), carried,
            lambda x, lp, c: S.sparse_prefill(x, lp, sp, c, p0, selected, a),
            lambda x, lp, s, ld: S.lightning_prefill(x, lp, li, s, ld, p0, a))
        return carried, (x[:, -1], ())

    with jax.named_scope("prefill"):
        carried, x_last, _ = decoding.chunked_walk(prefill_piece, carried,
                                                   p_len, chunk)
        first_logp = head(x_last)

    # ---- one step: each layer's one-token form over its own entries, in
    # every iteration (no conditional round the slabs and states it writes
    # in place: 36 copies of them a step went with it, PERF.md section 6,
    # PR 48). The first stands at ``p_len`` as the second does, which
    # writes the slabs there again; a state folds only the second's token
    def layers(tokens, carried, index, first):
        x, carried = through(
            embed(tokens)[:, None, :], carried,
            lambda x, lp, c: S.sparse_decode(x, lp, sp, c, index, p_len, a),
            lambda x, lp, s, ld: S.lightning_decode(x, lp, li, s, ld, index,
                                                    a, write=~first))
        return x, carried, ()

    return (decoding.start(carried, p_len, first_logp),
            decoding.step_with_write_switch(layers, head, p_len),
            decoding.no_audit)


# ``make_generator(cfg, max_new_tokens, bos_id=1, eos_id=2)``: greedy
# incremental generation over the two-kind carried state, a program fn
# ``(prompt_ids [b, p]) -> {"ids": [b, max_new_tokens]}``
make_generator = functools.partial(decoding.make_generator, _decoder)


__all__ = ["LIGHTNING", "MiniCPMSALAConfig", "PUBLISHED_MIXERS", "SPARSE",
           "base_config", "make_generator"]
