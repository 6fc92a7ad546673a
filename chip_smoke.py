#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python3 chip_smoke.py              # one TPU chip: device, kernels, train, serve
    python3 chip_smoke.py --chips 4    # four chips: dp2 x tp2 against one device, only

Drives the main path once through the entry points a user calls, at the
full width and depth of GPT-2 small (12 layers, d_model 768, 12 heads,
d_inner 3072, vocabulary 50257, sequence 1024, bf16, batch 8, flash
attention, fused cross-entropy): ``pt.build`` -> ``pt.Trainer`` ->
``io.save_inference_model`` -> ``io.load_inference_model`` ->
``serving.PredictorServer``. Weights and data are random, made from
``--seed``. One process holds the chip for every phase: the server's
workers are in-process threads, and nothing here starts a child.

Every phase prints what it saw and raises on the first check that
fails, so a failed phase ends the script non-zero before the result
line. Times are wall-clock seconds *as seen in a smoke* — one cold or
cache-warm run, compiles included where labelled — not a benchmark. The
last line of standard output is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It is printed only on a TPU: with no accelerator the device phase
raises first. The persistent compile cache follows the one rule of
``paddle_tpu.core.config.compile_cache_dir``.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)  # paddle_tpu from this checkout, never an installed one

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import debugger, io, optimizer as opt, serving
from paddle_tpu.core.config import enable_compile_cache, set_flag
from paddle_tpu.models import gpt
from paddle_tpu.ops.flash_attention import flash_attention

BATCH, SEQ = 8, 1024
PROMPT, NEW_TOKENS = 128, 32
BF16_TOL = 2e-2  # largest error over largest reference value, bf16 operands

def gpt2_small() -> gpt.GPTConfig:
    return gpt.base_config(vocab_size=50257, max_len=SEQ, d_model=768,
                           d_inner=3072, num_heads=12, num_layers=12,
                           use_flash=True, fused_ce=True, dropout=0.0,
                           dtype="bfloat16")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: FAILED: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


@contextlib.contextmanager
def timed(phase: str, what: str):
    t0 = time.perf_counter()
    yield
    say(phase, f"{what}: {time.perf_counter() - t0:.2f} s (as seen in a smoke)")


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported by this backend" if peak is None else f"{peak:,}"


# ---------------------------------------------------------------------------
# device


def device_phase(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    say("device", json.dumps(device))
    check(d.platform == "tpu",
          f"jax found no TPU (platform {d.platform!r}); this script passes "
          f"on a chip only")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
                              f"jax reports {len(devs)}")
    # git commits only sources under native/; the helpers rebuild from
    # the .cc beside them, so a checkout holds none of the binaries
    built = sorted(n for n in os.listdir(os.path.join(HERE, "paddle_tpu", "native"))
                   if not n.endswith((".py", ".cc", ".h", "__pycache__")))
    say("device", "native build products in this tree: "
        + (", ".join(built) if built else "none")
        + " (this path builds and loads none)")
    return device


# ---------------------------------------------------------------------------
# kernels


def dense_attention(q, k, v, causal: bool, key_bias=None, segment_ids=None):
    """Plain f32 softmax(q k^T / sqrt(d) + masks) v — the reference."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        s = jnp.where(same, s, -1e30)
    if causal:
        sq, sk = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def kernel_case(name: str, shape, causal: bool, mask: str, seed: int,
                bsd: bool = False):
    """Flash forward + backward against the dense f32 composition.
    ``mask``: "none", "key_bias" (padding) or "segment_ids" (packing).
    ``bsd``: the kernels get the same operands as ``[b, s, h*d]``, the
    layout a block's projections leave them in, and read it in place."""
    b, h, s, d = shape
    kq, kk, kv, kg, kb = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v, g = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in (kq, kk, kv, kg))
    bias = seg = None
    if mask == "key_bias":  # the last tenth to half of each row is padding
        keep = s - jax.random.randint(kb, (b, 1), s // 10, s // 2)
        bias = jnp.where(jnp.arange(s)[None, :] < keep, 0.0, -1e30)
    elif mask == "segment_ids":  # documents of uneven length, packed
        starts = jax.random.bernoulli(kb, 8.0 / s, (b, s))
        seg = jnp.cumsum(starts, axis=1).astype(jnp.int32)

    def fwd_bwd(attn):
        def run(q, k, v, g):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v), q, k, v)
            return (out,) + vjp(g.astype(out.dtype))
        return jax.jit(run)

    def heads_together(x):
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def attend(q, k, v):
        if not bsd:
            return flash_attention(q, k, v, causal=causal, key_bias=bias,
                                   segment_ids=seg)
        out = flash_attention(*map(heads_together, (q, k, v)), causal=causal,
                              key_bias=bias, segment_ids=seg, num_heads=h)
        return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    flash = fwd_bwd(attend)
    dense = fwd_bwd(lambda q, k, v: dense_attention(
        q, k, v, causal, key_bias=bias, segment_ids=seg))
    calls = flash.lower(q, k, v, g).as_text().count("tpu_custom_call")
    got = flash(q, k, v, g)
    want = dense(q, k, v, g)
    errs = {}
    for label, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        check(np.isfinite(a).all(), f"{name}: {label} is not finite")
        errs[label] = float(np.abs(a - r).max() / np.abs(r).max())
    say("kernel", f"{name} {shape} causal={causal} mask={mask}: "
        f"tpu_custom_call in the lowered fwd+bwd: {calls}; error over "
        f"largest reference value: "
        + ", ".join(f"{k} {v:.2e}" for k, v in errs.items()))
    check(max(errs.values()) <= BF16_TOL,
          f"{name}: flash differs from the dense f32 reference by "
          f"{max(errs.values()):.2e} > {BF16_TOL}")
    if on_tpu():
        # forward and one backward kernel (two where a sequence streams)
        check(calls >= 2, f"{name}: the lowered program holds {calls} "
                          f"tpu_custom_call, expected fwd + backward")
    return errs


def sparse_case(name: str, seed: int, rows=2, kv_heads=2, group=16, d=128,
                total=8192, queries=256, p0=7936, window_blocks=32,
                n_sel=31) -> None:
    """``sparse_fwd`` at the published head shapes (16 grouped heads of 128
    over one key head, 64-key blocks, a window of 32 and 31 chosen blocks)
    against its ``jnp`` form: the last ``queries`` of a ``total``-key
    cache, random choices among the blocks before the window."""
    from paddle_tpu.ops import sparse_attention as sa

    block = 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (rows, kv_heads, queries * group, d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (rows, total, kv_heads * d), jnp.bfloat16)
            for key in (kk, kv))
    rng = np.random.RandomState(seed)
    sel = np.zeros((rows, kv_heads, queries, n_sel + 1), np.int32)
    for at in np.ndindex(rows, kv_heads, queries):
        free = rng.permutation(np.arange(
            1, max((p0 + at[2]) // block - window_blocks + 1, 1)))[:n_sel]
        sel[at][:len(free)], sel[at][n_sel] = free, len(free)
    kw = dict(group=group, block=block, window_blocks=window_blocks,
              init_blocks=1, scale=d ** -0.5)
    kernel = jax.jit(lambda *a: sa.sparse_attention(*a, **kw))
    args = (q, k, v, jnp.asarray(sel), jnp.int32(p0))
    calls = kernel.lower(*args).as_text().count("tpu_custom_call")
    got = np.asarray(kernel(*args), np.float32)
    want = np.asarray(jax.jit(lambda *a: sa.sparse_attention_jnp(*a, **kw))(*args),
                      np.float32)
    err = float(np.abs(got - want).max() / np.abs(want).max())
    say("kernel", f"{name} q{q.shape} cache{k.shape} p0={p0}: tpu_custom_call "
        f"in the lowered call: {calls}; error over largest reference value "
        f"{err:.2e}")
    check(np.isfinite(got).all() and err <= BF16_TOL,
          f"{name}: sparse_fwd differs from its jnp form by {err:.2e}")
    if on_tpu():
        check(calls == 1, f"{name}: {calls} tpu_custom_call, expected one")


def select_case(name: str, seed: int, rows=2, kv_heads=2, group=16, d=128,
                total=8192, queries=512, p0=7680, window_blocks=32,
                topk=64) -> None:
    """``select_fwd`` at the published head shapes (the 31 highest of the
    blocks before a window of 32, scored by 16 grouped heads of 128 against
    the compressed keys) against the plain scorer, ``select_blocks``: the
    same counts, and the same block in 99.9% of the seats that count (two
    blocks whose scores tie to float32 rounding may swap)."""
    from paddle_tpu.layers import sala
    from paddle_tpu.ops.block_select import block_select

    dims = sala.SparseDims(1, kv_heads * group, kv_heads, d, 1e-6, 32, 16, 64,
                           1, window_blocks * 64, topk, 0)
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    q = 4.0 * jax.random.normal(kq, (rows, queries, dims.heads, d), jnp.float32)
    ck = 0.2 * jax.random.normal(kk, (rows, total // 16, kv_heads * d),
                                 jnp.float32)
    q, ck = q.astype(jnp.bfloat16), ck.astype(jnp.bfloat16)
    kernel = jax.jit(lambda q, ck, p0: block_select(
        q.reshape(rows, queries, -1), ck, p0, group=group, head_dim=d,
        kernel_size=32, stride=16, block=64, init_blocks=1,
        window_blocks=window_blocks, n_sel=dims.n_sel, scale=dims.scale)[0])
    args = (q, ck, jnp.int32(p0))
    calls = kernel.lower(*args).as_text().count("tpu_custom_call")
    got = np.asarray(kernel(*args))
    want = np.asarray(jax.jit(lambda q, ck, p0: sala.select_blocks(
        q, ck, p0 + jnp.arange(queries), dims))(*args))
    live = np.arange(dims.n_sel) < want[..., -1:]
    same = float((got[..., :-1] == want[..., :-1])[live].mean())
    say("kernel", f"{name} q{q.shape} compressed keys{ck.shape} p0={p0}: "
        f"tpu_custom_call in the lowered call: {calls}; seats equal to the "
        f"plain scorer's: {same:.5f} of {int(live.sum())}")
    check((got[..., -1] == want[..., -1]).all() and same >= 0.999,
          f"{name}: select_fwd seats {same:.5f} of the plain scorer's blocks")
    if on_tpu():
        check(calls == 1, f"{name}: {calls} tpu_custom_call, expected one")


def lightning_case(name: str, seed: int, rows=2, heads=32, d=128,
                   seq=1024) -> None:
    """``lightning_fwd`` at the published head shapes (32 heads of 128,
    the decays of published layer 7) from a random float32 state, against
    its ``jnp`` form."""
    from paddle_tpu.layers.sala import lightning_log_decay
    from paddle_tpu.ops import lightning_attention as la

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, (rows, seq, heads * d), jnp.bfloat16)
               for key in keys[:3])
    q = (q.astype(jnp.float32) * d ** -0.5).astype(jnp.bfloat16)
    state = jax.random.normal(keys[3], (rows, heads, d, d), jnp.float32)
    decay = lightning_log_decay(heads, 7, 32)
    kernel = jax.jit(lambda *a: la.lightning_attention(*a, heads))
    calls = kernel.lower(q, k, v, decay, state).as_text().count("tpu_custom_call")
    got, left = kernel(q, k, v, decay, state)
    split = lambda a: a.reshape(rows, seq, heads, d)
    want, want_left = jax.jit(la.lightning_chunk)(split(q), split(k), split(v),
                                                  decay, state)
    errs = {}
    for label, a, r in (("out", got, want.reshape(rows, seq, -1)),
                        ("state", left, want_left)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        check(np.isfinite(a).all(), f"{name}: {label} is not finite")
        errs[label] = float(np.abs(a - r).max() / np.abs(r).max())
    say("kernel", f"{name} {q.shape} heads={heads}: tpu_custom_call in the "
        f"lowered call: {calls}; error over largest reference value: "
        + ", ".join(f"{k} {v:.2e}" for k, v in errs.items()))
    check(max(errs.values()) <= BF16_TOL,
          f"{name}: lightning_fwd differs from its jnp form by "
          f"{max(errs.values()):.2e}")
    if on_tpu():
        check(calls == 1, f"{name}: {calls} tpu_custom_call, expected one")


def retention_case(name: str, seed: int, rows=1, heads=10, kv_heads=2, d=128,
                   seq=576) -> None:
    """``retention_fwd`` and ``retention_read`` at the published head shapes
    (5 query heads of 128 to a key head, 8,704 products held a head) from a
    random float32 state, against their ``jnp`` forms: two whole chunks and
    a tail, then one token from an empty window at a position where the
    second key head folds it and the first keeps it beside its state (the
    state compared once the window is folded in)."""
    from paddle_tpu.ops import power_retention as pr

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (rows, seq, heads * d), jnp.bfloat16)
    q = (q.astype(jnp.float32) * d ** -0.5).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (rows, seq, kv_heads * d), jnp.bfloat16)
            for key in keys[1:3])
    log_gamma = jax.nn.log_sigmoid(
        4.85 + 0.7 * jax.random.normal(keys[3], (rows, seq, kv_heads)))
    # a state as some hundred earlier tokens leave it (positive key sums)
    split = lambda a, n: a.reshape(rows, -1, n, d)
    early = [jax.random.normal(key, (rows, 128, kv_heads * d), jnp.bfloat16)
             for key in keys[4:6]]
    _, state = jax.jit(pr.retention_chunk)(
        split(q[:, :128], heads), split(early[0], kv_heads),
        split(early[1], kv_heads), log_gamma[:, :128],
        pr.empty_state(rows, kv_heads, d))
    kernel = jax.jit(lambda *a: pr.retention(*a, heads, kv_heads))
    window = pr.empty_window(rows, kv_heads, d, jnp.bfloat16)
    at = pr.WINDOW + 1

    def step(q1, k1, v1, gate, state):
        o, left = pr.retention_step(q1, k1, v1, gate, state, heads, kv_heads,
                                    window, at)
        return o, pr.fold_window(
            left, pr.window_append(window, k1, v1, gate, at), at)

    step = jax.jit(step)
    args = (q, k, v, log_gamma, state)
    one = tuple(a[:, 0] for a in args[:4]) + (state,)
    calls = (kernel.lower(*args).as_text().count("tpu_custom_call"),
             step.lower(*one).as_text().count("tpu_custom_call"))
    got, left = kernel(*args)
    want, want_left = jax.jit(pr.retention_chunk)(
        split(q, heads), split(k, kv_heads), split(v, kv_heads), log_gamma, state)
    got1, left1 = step(*one)
    want1, want_left1 = jax.jit(pr.retention_step_plain)(
        split(q, heads)[:, 0], split(k, kv_heads)[:, 0], split(v, kv_heads)[:, 0],
        log_gamma[:, 0], state)
    errs = {}
    for label, a, r in (("out", got, want.reshape(rows, seq, -1)),
                        ("state", left, want_left),
                        ("step out", got1, want1.reshape(rows, -1)),
                        ("step state", left1, want_left1)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        check(np.isfinite(a).all(), f"{name}: {label} is not finite")
        errs[label] = float(np.abs(a - r).max() / np.abs(r).max())
    say("kernel", f"{name} {q.shape} heads={heads} over {kv_heads}: "
        f"tpu_custom_call in the lowered calls: {calls}; error over largest "
        f"reference value: " + ", ".join(f"{k} {v:.2e}" for k, v in errs.items()))
    check(max(errs.values()) <= BF16_TOL,
          f"{name}: the retention kernels differ from their jnp forms by "
          f"{max(errs.values()):.2e}")
    if on_tpu():
        check(calls == (1, 1), f"{name}: {calls} tpu_custom_call, expected one each")


def mamba_case(name: str, seed: int, rows=2, d_inner=5120, d_state=16,
               seq=150) -> None:
    """``mamba_fwd`` at the published widths from a random float32 state
    against the token-by-token ``lax.scan``: two whole blocks and a tail."""
    from paddle_tpu.ops import selective_scan as ss

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    delta = jax.nn.softplus(jax.random.normal(keys[0], (rows, seq, d_inner)) - 4)
    u = delta * jax.random.normal(keys[1], (rows, seq, d_inner))
    b, c = (jax.random.normal(key, (rows, seq, d_state)) for key in keys[2:4])
    a = -jnp.broadcast_to(jnp.arange(1.0, d_state + 1)[:, None],
                          (d_state, d_inner))
    state = jax.random.normal(keys[4], (rows, d_state, d_inner))
    kernel = jax.jit(ss.selective_scan)
    args = (delta, u, b, c, a, state)
    calls = kernel.lower(*args).as_text().count("tpu_custom_call")
    errs = {}
    for label, got, want in zip(("out", "state"), kernel(*args),
                                jax.jit(ss.mamba_scan)(*args)):
        got, want = np.asarray(got), np.asarray(want)
        check(np.isfinite(got).all(), f"{name}: {label} is not finite")
        errs[label] = float(np.abs(got - want).max() / np.abs(want).max())
    say("kernel", f"{name} {delta.shape} x {d_state}: tpu_custom_call in the "
        f"lowered call: {calls}; error over largest reference value: "
        + ", ".join(f"{k} {v:.2e}" for k, v in errs.items()))
    check(max(errs.values()) <= 1e-4,
          f"{name}: mamba_fwd differs from the scan by {max(errs.values()):.2e}")
    if on_tpu():
        check(calls == 1, f"{name}: {calls} tpu_custom_call, expected one")


def window_case(name: str, seed: int, shape=(2, 8, 512, 64), window=512) -> None:
    """The windowed ``flash_fwd`` as a prefill piece calls it (``window``
    keys held before the piece's own, a value twice a score's width, the
    first keys not there yet) against dense masked attention."""
    b, h, s, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], shape, jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h, window + s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h, window + s, 2 * d), jnp.bfloat16)
    bias = jnp.broadcast_to(jnp.where(jnp.arange(window + s) < window // 2,
                                      -1e9, 0.0)[None], (b, window + s))
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, key_bias=bias, window=window))(q, k, v)
    row = jnp.arange(s)[:, None] + window
    col = jnp.arange(window + s)[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    scores = jnp.where((col <= row) & (col > row - window), scores + bias[:, None, None],
                       -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1),
                      v.astype(jnp.float32))
    err = float(jnp.abs(got.astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
    say("kernel", f"{name} q {q.shape} k {k.shape} window {window}: error over "
        f"largest reference value {err:.2e}")
    check(np.isfinite(np.asarray(got, np.float32)).all() and err <= BF16_TOL,
          f"{name}: the windowed flash_fwd differs from dense attention by "
          f"{err:.2e}")


def phi4_flash_case(name: str, seed: int, hidden=512, heads=8, window=128,
                    vocab=1024, rows=2, prompt=600, new=8, chunk=256) -> None:
    """The ``models/phi4_flash.py`` generator at a small width, eight
    layers (two periods of the pattern), bfloat16: a prompt of two pieces
    and a tail, past the window; the audited state against its definition
    from the audit's own inputs."""
    from paddle_tpu.models import phi4_flash

    cfg = phi4_flash.base_config(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=8,
        num_attention_heads=heads, num_key_value_heads=heads // 2,
        intermediate_size=2 * hidden, sliding_window=window,
        prefill_chunk=chunk)
    prog = pt.build(phi4_flash.make_generator(cfg, max_new_tokens=new))
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, prompt), 3, vocab)
    params, _ = prog.init(jax.random.PRNGKey(seed + 1), prompt_ids=ids)
    out = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0])(params, ids)
    out = {k: np.asarray(v, np.float64) for k, v in out.items()}
    check(out["ids"].shape == (rows, new) and (out["ids"] >= 0).all()
          and (out["ids"] < vocab).all(), f"{name}: ids {out['ids']}")
    # as created, A = -(1 .. d_state) for every channel
    delta, u, b = out["audit_delta"], out["audit_u"], out["audit_b"]
    after = np.cumsum(delta[:, ::-1], axis=1)[:, ::-1] - delta
    a = -np.arange(1.0, b.shape[-1] + 1)
    want = np.einsum("rtnc,rtc,rtn->rnc",
                     np.exp(a[:, None] * after[:, :, None, :]), u, b)
    err = float(np.abs(out["audit_state"] - want).max() / np.abs(want).max())
    say("kernel", f"{name}: {rows} x ({prompt} + {new}) through 8 layers, "
        f"audited state over {delta.shape[1]} positions: error over largest "
        f"reference value {err:.2e}")
    check(np.isfinite(err) and err <= 1e-4,
          f"{name}: the carried state differs from its definition by {err:.2e}")


def kernel_phase(seed: int, gpt_shape=(BATCH, 12, SEQ, 64),
                 transformer_shape=(32, 8, 256, 64), sala=None,
                 brumby=None, phi4=None) -> None:
    with timed("kernel", "twelve cases, compiles included"):
        mamba_case("phi4_mamba", seed + 9, **(phi4 or {}).get("mamba", {}))
        window_case("phi4_window", seed + 10, **(phi4 or {}).get("window", {}))
        phi4_flash_case("phi4_generator", seed + 11,
                        **(phi4 or {}).get("generator", {}))
        retention_case("brumby_retention", seed + 8, **(brumby or {}))
        sparse_case("sala_sparse", seed + 5, **(sala or {}).get("sparse", {}))
        select_case("sala_select", seed + 7, **(sala or {}).get("select", {}))
        lightning_case("sala_lightning", seed + 6,
                       **(sala or {}).get("lightning", {}))
        kernel_case("gpt", gpt_shape, True, "none", seed)
        kernel_case("gpt_packed", gpt_shape, True, "segment_ids", seed + 1)
        kernel_case("transformer_base", transformer_shape, False, "key_bias",
                    seed + 2)
        kernel_case("gpt_bsd", gpt_shape, True, "none", seed + 3, bsd=True)
        kernel_case("transformer_base_bsd", transformer_shape, False,
                    "key_bias", seed + 4, bsd=True)


# ---------------------------------------------------------------------------
# train


def lm_batches(vocab: int, batch: int, seq: int, seed: int, n: int = 4,
               stream: int = 0):
    """``n`` fixed batches from a noisy cycle over 256 token ids (pad id
    0 never drawn): nine times in ten the next token is the next of the
    cycle, so a dozen steps teach the model something that depends on
    the prompt. ``stream`` draws other sequences over the same cycle."""
    cycle = np.random.RandomState(seed).permutation(vocab - 3)[:256] + 3
    rng = np.random.RandomState(seed + 1 + stream)
    out = []
    for _ in range(n):
        hop = np.where(rng.rand(batch, seq + 1) < 0.9, 1,
                       rng.randint(0, len(cycle), (batch, seq + 1)))
        ids = cycle[np.cumsum(hop, axis=1) % len(cycle)].astype(np.int32)
        out.append({"ids": ids[:, :-1], "labels": ids[:, 1:]})
    return out


def make_trainer(cfg, feed, seed: int, mesh=None, rules=None):
    set_flag("seed", seed)
    trainer = pt.Trainer(pt.build(gpt.make_model(cfg)),
                         opt.AdamW(3e-4, weight_decay=0.01),
                         loss_name="loss", fetch_list=["loss"],
                         mesh=mesh, sharding_rules=rules)
    trainer.startup(sample_feed=feed)
    return trainer


def blocked_step(trainer, feed) -> tuple:
    t0 = time.perf_counter()
    loss = float(jax.block_until_ready(trainer.step(feed)["loss"]))
    return loss, time.perf_counter() - t0


def train_phase(cfg, batch: int, seq: int, seed: int, steps: int = 12,
                k: int = 4):
    """A dozen ``step()`` calls and one ``run_steps(k)``; returns the
    trainer (its params are what the serve phase exports)."""
    feeds = lm_batches(cfg.vocab_size, batch, seq, seed)
    with timed("train", "build + startup (init and placement)"):
        trainer = make_trainer(cfg, feeds[0], seed)
    n_params = sum(int(np.prod(v.shape)) for v in trainer.scope.params.values())
    say("train", f"parameters: {n_params:,}")

    calls = debugger.step_kernel_calls(trainer, feeds[0])
    say("train", "attention path traced into the train step: "
        + (f"flash kernel ({calls} tpu_custom_call)" if calls
           else "no Pallas kernel call (dense, or interpreted off-chip)"))
    if on_tpu():
        check(calls >= 2, f"the train step holds {calls} tpu_custom_call: "
                          f"flash fwd + backward are not both in it")

    loss0, t_first = blocked_step(trainer, feeds[0])
    say("train", f"first step, compile included: {t_first:.2f} s "
                 f"(as seen in a smoke)")
    losses, times = [loss0], []
    for i in range(1, steps):
        loss, dt = blocked_step(trainer, feeds[i % len(feeds)])
        losses.append(loss)
        times.append(dt)
    say("train", f"steps 2..{steps}: median {np.median(times):.4f} s/step, "
                 f"batch {batch} x seq {seq} (as seen in a smoke)")

    stacked = {name: np.stack([feeds[(steps + j) % len(feeds)][name]
                               for j in range(k)]) for name in feeds[0]}
    t0 = time.perf_counter()
    fused = np.asarray(jax.block_until_ready(
        trainer.run_steps(stacked, k=k)["loss"]), np.float32)
    say("train", f"run_steps(k={k}), compile included: "
                 f"{time.perf_counter() - t0:.2f} s (as seen in a smoke)")
    losses += [float(x) for x in fused.reshape(-1)]
    say("train", "losses: " + " ".join(f"{x:.4f}" for x in losses))
    check(len(losses) == steps + k, f"expected {steps + k} losses, "
                                    f"got {len(losses)}")
    check(all(np.isfinite(losses)), "a loss is not finite")
    check(np.mean(losses[-4:]) < np.mean(losses[:4]) and losses[-1] < losses[0],
          f"the loss did not fall: first {losses[0]:.4f}, last {losses[-1]:.4f}")
    check(trainer.global_step == steps + k,
          f"global_step {trainer.global_step} != {steps + k}")
    say("train", f"peak bytes in use on device 0: {peak_bytes()}")
    return trainer


# ---------------------------------------------------------------------------
# serve


def serve_phase(cfg, params, batch: int, prompt_len: int, new_tokens: int,
                seed: int, requests: int = 4) -> None:
    """Export the generator, load it back, serve it from in-process
    workers, and hold the served ids to the un-exported program's."""
    prompts = [f["ids"] for f in lm_batches(cfg.vocab_size, batch, prompt_len,
                                            seed, n=requests, stream=7)]
    gen = pt.build(gpt.make_generator(cfg, max_new_tokens=new_tokens))
    params = dict(params)

    direct = jax.jit(lambda p, ids: gen.apply(p, {}, ids)[0]["ids"])
    with timed("serve", "un-exported generator, compile + "
                        f"{requests} batches"):
        want = [np.asarray(direct(params, ids)) for ids in prompts]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_model_") as dirname:
        with timed("serve", "save_inference_model"):
            io.save_inference_model(dirname, gen, params, {},
                                    {"prompt_ids": prompts[0]})
        with timed("serve", "load_inference_model (AOT compile)"):
            predictor = io.load_inference_model(dirname)
    with timed("serve", "PredictorServer start (workers + warm-up)"):
        server = serving.PredictorServer(predictor, workers=2)
    try:
        t0 = time.perf_counter()
        pending = [server.submit({"prompt_ids": ids}) for ids in prompts]
        got = [np.asarray(p.result(timeout=600)["ids"]) for p in pending]
        say("serve", f"{requests} generate requests (batch {batch}, prompt "
            f"{prompt_len}, {new_tokens} new tokens) answered in "
            f"{time.perf_counter() - t0:.2f} s (as seen in a smoke)")
        report = server.report()
    finally:
        server.close()
    for i, (g, w) in enumerate(zip(got, want)):
        check(g.shape == (batch, new_tokens),
              f"request {i}: ids shape {g.shape} != {(batch, new_tokens)}")
        check(np.array_equal(g, w),
              f"request {i}: served ids differ from the un-exported program "
              f"in {int((g != w).sum())} of {g.size} places")
    say("serve", f"served ids equal the un-exported program's on all "
                 f"{requests} requests ({len(np.unique(got))} distinct ids); "
                 f"first row: {got[0][0, :8].tolist()} ...")
    counters = {c: report[c] for c in ("submitted", "completed", "errors",
                                       "timeouts", "hangs",
                                       "rejected_invalid", "rejected_overload")}
    say("serve", f"server metrics: {json.dumps(counters)}, "
        f"compiles_since_warmup {report['compiles_since_warmup']}, "
        f"latency p50 {report['latency_ms']['p50']} ms (as seen in a smoke)")
    check(report["completed"] == requests and report["submitted"] == requests,
          f"server completed {report['completed']} of {requests}")
    check(report["errors"] == 0 and report["timeouts"] == 0
          and report["hangs"] == 0, "the server counted an error, timeout or hang")
    check(report["compiles_since_warmup"] == 0,
          f"{report['compiles_since_warmup']} compile(s) after warm-up")
    say("serve", f"peak bytes in use on device 0: {peak_bytes()}")


# ---------------------------------------------------------------------------
# four chips: dp2 x tp2 against one device of the same host


def four_chip_phase(cfg, batch: int, seq: int, seed: int, devices,
                    steps: int = 4) -> None:
    feeds = lm_batches(cfg.vocab_size, batch, seq, seed)

    def run(mesh, rules):
        trainer = make_trainer(cfg, feeds[0], seed, mesh=mesh, rules=rules)
        losses = [blocked_step(trainer, feeds[i % len(feeds)])[0]
                  for i in range(steps)]
        return trainer, losses

    with timed("4chip", f"one device, {steps} steps, compile included"):
        single, one = run(None, None)
    del single
    say("4chip", "one-device losses: " + " ".join(f"{x:.4f}" for x in one))

    mesh = pt.make_mesh({"dp": 2, "tp": 2}, devices=list(devices)[:4])
    rules = pt.parallel.transformer_tp_rules()
    with timed("4chip", f"dp2 x tp2, {steps} steps, compile included"):
        trainer, four = run(mesh, rules)
    say("4chip", "dp2 x tp2 losses:   " + " ".join(f"{x:.4f}" for x in four))
    check(all(np.isfinite(one + four)), "a loss is not finite")
    gap = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    say("4chip", f"largest relative loss gap: {gap:.2e} (limit {BF16_TOL})")
    check(gap <= BF16_TOL,
          f"dp2 x tp2 and one-device losses differ by {gap:.2e}")

    tp_ruled = {n: v for n, v in trainer.scope.params.items()
                if "tp" in jax.tree.leaves(tuple(v.sharding.spec))}
    spread = [n for n, v in tp_ruled.items()  # devices hold different slices
              if len({str(s.index) for s in v.addressable_shards}) > 1]
    say("4chip", f"tp-ruled parameters: {len(tp_ruled)}, sharded across "
        f"more than one device: {len(spread)} "
        f"(of {len(trainer.scope.params)} parameters in all)")
    check(len(spread) > 0, "every parameter sits whole on each device")

    calls = debugger.step_kernel_calls(trainer, feeds[0])
    say("4chip", f"tpu_custom_call in the sharded train step: {calls}")
    if on_tpu():
        check(calls >= 2, "the sharded step does not hold the flash kernels")
    report = debugger.collective_report(trainer, feeds[0])
    kinds = {k: v["count"] for k, v in report["collectives"].items()}
    say("4chip", f"collectives in the compiled step: {json.dumps(kinds)}")
    check(sum(kinds.values()) > 0, "no collective is in the compiled step")
    say("4chip", f"peak bytes in use on device 0: {peak_bytes()}")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp2 x tp2 phase and its one-device twin")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()

    device = device_phase(args.chips)

    cache_dir = enable_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event]))
    entries0 = len(os.listdir(cache_dir))
    say("cache", f"persistent compile cache at {cache_dir}: "
                 f"{entries0} entries before")

    set_flag("default_compute_dtype", "bfloat16")
    cfg = gpt2_small()
    if args.chips == 4:
        four_chip_phase(cfg, BATCH, SEQ, args.seed, jax.devices())
    else:
        kernel_phase(args.seed)
        trainer = train_phase(cfg, BATCH, SEQ, args.seed)
        serve_phase(cfg, trainer.scope.params, BATCH, PROMPT, NEW_TOKENS,
                    args.seed)

    say("cache", f"{len(os.listdir(cache_dir))} entries after; this run: "
        f"hits {cache_events['/jax/compilation_cache/cache_hits']}, misses "
        f"{cache_events['/jax/compilation_cache/cache_misses']} (a miss "
        f"writes a new entry; a size-capped cache may evict old ones)")
    say("total", f"wall {time.perf_counter() - t_start:.1f} s, imports "
                 f"excluded (as seen in a smoke)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
