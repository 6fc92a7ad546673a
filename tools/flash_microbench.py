"""Flash-attention kernel microbench: device time of each of the flash
kernels at the shapes the benchmark's cells run, over a sweep of the
plan's compute tiles, beside JAX's own Pallas kernel as the yardstick.

    chiprun -- python3 tools/flash_microbench.py
    chiprun -- python3 tools/flash_microbench.py --tiles plan,128,256
    chiprun -- python3 tools/flash_microbench.py --shapes long_4k_d128 --reference 0
    chiprun -- python3 tools/flash_microbench.py --layouts bhsd,projection,bsd,fused

For every shape and every largest compute tile of ``--tiles`` (``plan``: the
``TILE`` that ``ops/flash_attention.plan_blocks`` uses itself) it runs forward +
backward (forward alone for the prefill shape) ``--iters`` times under the
profiler and reads each kernel's device time from the trace, by the
kernel's name (``flash_fwd``, and ``flash_bwd`` where the plan's backward is
``fused``, ``flash_dq`` / ``flash_dkv`` where it is ``split``): milliseconds a
call and TFLOP/s of the matmuls the algorithm needs (2, and 5, or 3 / 4 of
``2 * sq * sk * d`` a head, halved under causal) against the bf16 peak of
``benchmarks/peaks.json``. A shape whose backward is fused is timed a second
time with the split pair in its place (``"backward": "split"`` in the row),
so that one process gives both. At head_dim 64 the MXU's ceiling for these
kernels is about half the peak: the contraction (q k^T) or the output
width (p v) fills 64 of its 128 columns. ``--reference 1`` times
``jax.experimental.pallas.ops.tpu.flash_attention`` the same way over a few
block sizes and prints its best.

``--layouts`` times a call in each layout the kernels take, at the plan's
own tile: ``bhsd`` (``[b, h, s, d]`` operands, the kernels alone: the
sweep above), ``projection`` (``[b, s, h*d]`` operands, as a projection
leaves them, transposed to ``[b, h, s, d]`` and back round the ``bhsd``
kernels: what a block paid before the kernels read that layout), ``bsd``
(the same operands, read and written in place) and ``fused`` (q, k and v
side by side in one ``[b, s, 3*h*d]`` array, one matmul's output). Beside
each kernel's time stands ``ms_call``: the device's busy time a call,
everything the call runs (transposes, pads, the backward's ``delta``), so
that a kernel PR can compare layouts on the chip without a train step.

    chiprun -- python3 tools/flash_microbench.py --shapes trinity_window,trinity_full

``trinity_window`` and ``trinity_full`` are the two forward calls a piece of a
``trinity-serve-long`` prompt makes (``SERVE_CALLS``: 8 rows x 2,048 queries,
48 heads on 8 key heads of 128, in the projections' layout; 6,144 keys under a
4,096-key window and a key bias, or a 33,792-key cache under a traced
``q_offset`` at pieces 0, 7 and 15). They are timed forward only, with the
walk the plan gives them (``tiles_written`` of ``tiles_run`` in the row's
plan): the program has no switch, so the other side of a comparison is this
tool over the other tree's archive (``--tree <dir>``: where ``paddle_tpu`` is
imported from; a tree whose plan knows no ``tiles_written`` prints none). A row holds the call's time, its
tiles (``tiles_run`` of a causal call as long as the piece's last key) and
microseconds a tile.

One JSON line per measurement goes to ``--out``. One process: it owns the chip and starts no child; it fails
where there is no TPU (a CPU time is not a device time).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> ((b, h, s, d), backward too): what one chip's kernels see in
# gpt2m-train-s1024, gpt2l-train-dp2tp2 (a dp2 x tp2 shard) and the
# serve cells' prefill; then the long-context shapes of test_tpu_compile
SHAPES = {
    "gpt2m_train": ((32, 16, 1024, 64), True),
    "gpt2l_train_shard": ((16, 10, 1024, 64), True),
    "gpt2m_prefill": ((16, 16, 896, 64), False),
    "long_4k_d128": ((2, 8, 4096, 128), True),
    "long_32k": ((1, 8, 32768, 64), True),
}
# name -> (keys, window, the pieces whose ``q_offset`` is timed; None: the
# offset is the call's own): trinity-serve-long's two calls a piece, forward
# only. Every call is (b, 2048, 48 * 128) queries on (b, keys, 8 * 128)
SERVE_CALLS = {
    "trinity_window": (6144, 4096, (None,)),
    "trinity_full": (33792, 0, (0, 7, 15)),
}
SERVE_ROWS, SERVE_PIECE, SERVE_HEADS, SERVE_KV_HEADS, SERVE_D = 8, 2048, 48, 8, 128
# matmuls of 2 * sq * sk * d flops a head that each kernel needs
MATMULS = {"flash_fwd": 2, "flash_bwd": 5, "flash_dq": 3, "flash_dkv": 4}


def kernel_flops(kernel, shape, causal=True):
    b, h, s, d = shape
    return MATMULS[kernel] * 2.0 * b * h * s * s * d / (2 if causal else 1)


def traced_kernel_ms(fn, args, iters, busy=None):
    """{kernel name: (ms a call, calls)} of the Mosaic kernels ``fn`` runs,
    in the order they first ran, from a profiler trace of ``iters`` calls
    after two warm ones. ``busy``, a dict, gets ``ms_call``: the device's
    busy time a call, every operation of ``fn`` counted."""
    import jax

    from benchmarks.tracing import Tracer

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    tracer = Tracer(chips=1)
    tracer.start()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    trace = tracer.stop()
    if busy is not None:
        busy["ms_call"] = round(trace.busy_s() * 1e3 / iters, 4)
    by_name = {}
    for label, _, dur in sorted(trace.ops.get(0, []), key=lambda e: e[1]):
        if trace.is_kernel(label):
            # jvp_flash_fwd_.3 [custom-call] -> flash_fwd; other names whole
            name = re.sub(r"\.\d+$", "", label.split(" ")[0])
            ours = re.search(r"flash_(fwd|bwd|dq|dkv)", name)
            name = ours.group(0) if ours else name
            ms, n = by_name.get(name, (0.0, 0))
            by_name[name] = (ms + dur / 1e6, n + 1)
    return {k: (ms / n, n) for k, (ms, n) in by_name.items()}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="gpt2m_train,gpt2l_train_shard,"
                                        "gpt2m_prefill")
    ap.add_argument("--tiles", default="plan",
                    help="comma list of largest compute tiles (the plan's "
                         "TILE), 'plan' = its own")
    ap.add_argument("--layouts", default="bhsd",
                    help="comma list of bhsd, projection, bsd, fused")
    ap.add_argument("--causal", type=int, default=1,
                    help="0: the whole score rectangle, no mask")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--reference", type=int, default=1,
                    help="also time the jax pallas reference kernel")
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose paddle_tpu is timed")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "flash_microbench.jsonl"))
    args = ap.parse_args()
    causal = bool(args.causal)
    sys.path.insert(0, os.path.abspath(args.tree))

    import inspect

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.config import enable_compile_cache
    from paddle_tpu.ops import flash_attention as fa

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"flash_microbench: no TPU here ({dev.platform}): a time "
                 f"from this device is not a device time")
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peak = json.load(f)[dev.device_kind]["bf16_flops_per_s"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def record(row):
        row["device"], row["causal"] = dev.device_kind, causal
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    def rates(shape, ms_by_kernel, names):
        """ms and TFLOP/s per kernel; ``names`` maps the trace's kernel
        names onto flash_fwd / flash_bwd / flash_dq / flash_dkv."""
        out = {}
        for traced, (ms, calls) in ms_by_kernel.items():
            kernel = names(traced)
            tf = kernel_flops(kernel, shape, causal) / (ms / 1e3) / 1e12
            out[kernel] = {"ms": round(ms, 4), "calls": calls,
                           "tflops": round(tf, 2),
                           "of_peak": round(tf * 1e12 / peak, 4)}
        return out

    @contextlib.contextmanager
    def split_backward():
        """Calls traced inside run the streaming pair on a shape whose
        plan would fuse the backward: the tool steers the plan as it
        steers ``TILE``, the program has no switch."""
        own = fa.plan_blocks
        fa.plan_blocks = lambda *a, **kw: own(*a, **kw)._replace(
            backward="split")
        try:
            yield
        finally:
            fa.plan_blocks = own

    def time_call(shape, base, plan, make_fn, operands, grad, note_best):
        """One row for the call as planned and, where its backward is
        fused, one more with the split pair forced onto the same operands;
        ``note_best(row)`` sees the planned one."""
        forced = (False, True) if grad and plan.backward == "fused" else (
            False,)
        for split in forced:
            row = dict(base, backward="split" if split else plan.backward,
                       plan=plan._asdict())
            try:
                with split_backward() if split else contextlib.nullcontext():
                    row["kernels"] = rates(shape, traced_kernel_ms(
                        make_fn(), operands, args.iters, busy=row),
                        lambda n: n)
                row["ms_all"] = round(sum(
                    v["ms"] for v in row["kernels"].values()), 4)
                if not split:
                    note_best(row)
            except Exception as e:  # e.g. a tile the compiler refuses
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            record(row)

    def step_fn(attn, grad):
        if not grad:
            return jax.jit(attn)
        return jax.jit(jax.grad(
            lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    def heads_apart(x, h):
        b, s, width = x.shape
        return x.reshape(b, s, h, width // h).transpose(0, 2, 1, 3)

    def layout_call(layout, h):
        """(attention over the layout's operands, how many it takes)"""
        if layout == "projection":
            def attn(q, k, v):
                o = fa.flash_attention(*(heads_apart(x, h) for x in (q, k, v)),
                                       causal=causal)
                return o.transpose(0, 2, 1, 3).reshape(q.shape)
            return attn, 3
        if layout == "bsd":
            return (lambda q, k, v: fa.flash_attention(
                q, k, v, causal=causal, num_heads=h)), 3
        return (lambda qkv: fa.flash_attention(
            qkv, causal=causal, num_heads=h)), 1

    def serve_call(name):
        """One row a piece for a call of ``SERVE_CALLS``."""
        sk, window, pieces = SERVE_CALLS[name]
        b, sq, h, kvh, d = (SERVE_ROWS, SERVE_PIECE, SERVE_HEADS,
                            SERVE_KV_HEADS, SERVE_D)
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, sq, h * d), jnp.bfloat16)
        k, v = (jnp.asarray(rng.randn(b, sk, kvh * d), jnp.bfloat16)
                for _ in range(2))
        # the ring's first 1,024 slots hold nothing yet: a bias like any other
        bias = jnp.broadcast_to(jnp.where(jnp.arange(sk) < 1024, fa.NEG_INF,
                                          0.0)[None], (b, sk)) if window else None
        fn = jax.jit(lambda q, k, v, bias, off: fa.flash_attention(
            q, k, v, causal=True, num_heads=h, kv_heads=kvh, window=window,
            key_bias=bias, q_offset=off))
        for piece in pieces:
            off = None if piece is None else jnp.int32(piece * sq)
            row = {"kernel": "repo", "shape": name, "piece": piece}
            try:
                plan = fa.plan_blocks(
                    sq, sk, d, jnp.bfloat16, True, bh=b * h, num_heads=h,
                    window=window, group=h // kvh,
                    **({"q_offset": True} if piece is not None and "q_offset"
                       in inspect.signature(fa.plan_blocks).parameters
                       else {}))
                (ms, calls), = traced_kernel_ms(
                    fn, (q, k, v, bias, off), args.iters, busy=row).values()
                row["plan"] = plan._asdict()
                # the tiles a piece whose last key is its own walks
                tiles = plan.tiles_run if piece is None else fa.plan_blocks(
                    sq, (piece + 1) * sq, d, jnp.bfloat16, True,
                    block_k=plan.block_k).tiles_run
                tf = (2 * 2.0 * plan.tile_q * plan.tile_k * d * tiles
                      * b * h / (ms / 1e3) / 1e12)
                row["kernels"] = {"flash_fwd": {
                    "ms": round(ms, 4), "calls": calls, "tiles": tiles,
                    "tiles_written": plan._asdict().get("tiles_written"),
                    "us_tile": round(ms * 1e3 / (tiles * b * h), 4),
                    "tflops": round(tf, 2),
                    "of_peak": round(tf * 1e12 / peak, 4)}}
                best[name, f"repo piece {piece}"] = (row["ms_call"], "ms_call")
            except Exception as e:  # e.g. a walk the compiler refuses
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            record(row)

    plan_tile = fa.TILE
    layouts = args.layouts.split(",")
    best = {}
    for name in args.shapes.split(","):
        if name in SERVE_CALLS:
            serve_call(name)
            continue
        shape, grad = SHAPES[name]
        b, h, s, d = shape
        rng = np.random.RandomState(0)
        for layout in (x for x in layouts if x != "bhsd"):
            attn, n = layout_call(layout, h)
            ops = [jnp.asarray(rng.randn(b, s, (3 if n == 1 else 1) * h * d),
                               jnp.bfloat16) for _ in range(n)]
            plan = fa.plan_blocks(
                s, s, d, jnp.bfloat16, causal, bh=b * h,
                num_heads=None if layout == "projection" else h)

            def make_fn(attn=attn, n=n):
                return jax.jit(jax.grad(
                    lambda *a: attn(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(n)))) if grad else jax.jit(attn)

            def note_best(row, layout=layout):
                best[name, "repo " + layout] = (row["ms_call"], "ms_call")

            time_call(shape, {"kernel": "repo", "shape": name,
                              "layout": layout},
                      plan, make_fn, ops, grad, note_best)
            del ops
        qkv = [jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(3)]
        for spec in args.tiles.split(",") if "bhsd" in layouts else ():
            fa.TILE = plan_tile if spec == "plan" else int(spec)
            plan = fa.plan_blocks(s, s, d, jnp.bfloat16, causal, bh=b * h)

            def note_best(row, spec=spec):
                if row["ms_all"] < best.get((name, "repo"), (math.inf,))[0]:
                    best[name, "repo"] = (row["ms_all"], spec)

            time_call(shape, {"kernel": "repo", "shape": name,
                              "layout": "bhsd", "tiles": spec},
                      plan, lambda: step_fn(lambda q, k, v: fa.flash_attention(
                          q, k, v, causal=causal), grad), qkv, grad, note_best)
        fa.TILE = plan_tile

        if not args.reference:
            continue
        from jax.experimental.pallas.ops.tpu import flash_attention as jfa

        # its pallas_calls carry no name of ours: the kernels are told
        # apart by the order they first run (forward, then dk/dv, then dq)
        ref_order = ("flash_fwd", "flash_dkv", "flash_dq")
        for bq, bkm, bk in ((512, 512, 512), (512, 1024, 512),
                            (1024, 1024, 512), (256, 512, 256),
                            (512, 512, 128), (128, 128, 128)):
            if s % bq or s % bkm:
                continue
            sizes = jfa.BlockSizes(
                block_q=bq, block_k_major=bkm, block_k=bk, block_b=1,
                block_q_major_dkv=bq, block_k_major_dkv=bkm, block_k_dkv=bk,
                block_q_dkv=bq, block_k_major_dq=bkm, block_k_dq=bk,
                block_q_dq=bq)
            row = {"kernel": "jax_reference", "shape": name,
                   "blocks": [bq, bkm, bk]}
            try:
                fn = step_fn(lambda q, k, v: jfa.flash_attention(
                    q, k, v, causal=causal, sm_scale=1.0 / math.sqrt(d),
                    block_sizes=sizes), grad)
                by_kernel = traced_kernel_ms(fn, qkv, args.iters)
                row["traced_names"] = list(by_kernel)
                as_ours = dict(zip(by_kernel, ref_order))
                row["kernels"] = rates(shape, by_kernel, as_ours.get)
                row["ms_all"] = round(sum(
                    v["ms"] for v in row["kernels"].values()), 4)
                if row["kernels"] and row["ms_all"] < best.get(
                        (name, "jax_reference"), (math.inf,))[0]:
                    best[name, "jax_reference"] = (row["ms_all"],
                                                   f"{bq}/{bkm}/{bk}")
            except Exception as e:
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            record(row)

    for (name, kernel), (ms, spec) in sorted(best.items()):
        print(f"# best {kernel:<16} {name:<18} {ms:8.3f} ms "
              f"{'a call, all of it' if spec == 'ms_call' else 'all kernels at ' + spec}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
