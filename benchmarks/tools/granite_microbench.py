#!/usr/bin/env python3
"""The two things ISSUE 49 says to read on the chip before anything is tuned,
each alone at the cell's shapes: the expert layer's pair walk (a piece of
``rows x chunk`` tokens through ``parallel/moe.moe_held``: the walk of 512
pairs with a scatter-add that ``k25-serve-batch`` and ``trinity-serve-long``
take, and the walk by gathers at several block lengths), a step's 320 pairs,
and ``ssd_fwd`` over a piece.

    python3 benchmarks/tools/granite_microbench.py [--rows 32] [--chunk 256]
    python3 benchmarks/tools/granite_microbench.py --values

Prints one JSON line a reading: milliseconds, median of ``--repeat`` calls.
``--values`` reads no time: it holds the piece's expert layer, in bfloat16 as
the cell runs it, to the same layer in float32 at ``highest`` an expert at a
time, by every walk (the scatter walk of 512, and the walk by gathers at the
given tokens a walk and block lengths), then block by block (the three
grouped products of ``moe._block_rows`` against the same rows through their
experts' own matrices), so that a walk whose values depend on how the pairs
are cut shows where (PERF.md section 7, After PR 49 (4)).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def timed(fn, *args, repeat):
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return sorted(out)[len(out) // 2]


def exact_experts(x, share, banks32):
    """``sum over e of share[:, e] * E_e(x)`` in float32 at ``highest``, an
    expert at a time over every row. ``x [n, d]``; ``share [n, held]``, what
    a row gives each held expert (zero where it did not select it)."""
    import jax
    import jax.numpy as jnp

    gate, up, down = banks32
    x = x.astype(jnp.float32)

    def one(e, out):
        with jax.default_matmul_precision("highest"):
            y = (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e]
        return out + y * share[:, e][:, None]

    return jax.lax.fori_loop(0, gate.shape[0], one,
                             jnp.zeros((x.shape[0], down.shape[-1]), jnp.float32))


def values(args, say, moe, h, router, banks, held, total, k):
    """The value readings of ``--values`` (the module's docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    banks32 = tuple(b.astype(jnp.float32) for b in banks)
    t, d = h.shape
    experts, weights = jax.jit(
        lambda h: moe.softmax_topk_route(h, router, k))(h)
    # a second routing: the later half of the tokens select nothing held here,
    # so their rows of the result are rows no block wrote
    nothing = jnp.where((jnp.arange(t) >= t // 2)[:, None],
                        held + jnp.arange(k)[None, :], experts)
    rel = lambda got, want: float(jnp.linalg.norm(got - want)
                                  / jnp.linalg.norm(want))

    # [n, k] selections and their weights -> [n, held] shares
    shares = lambda e, w: jnp.sum(jnp.where(
        e[..., None] == jnp.arange(held), w[..., None], 0.0), axis=1)
    # (the banks are an argument: closed over, each shape's executable would
    # hold 1.4 GB of constants, and the host ran out of its 40 GiB, call 272)
    exact_given = jax.jit(lambda x, e, w, banks32: exact_experts(
        x, shares(e, w), banks32))
    exact = lambda x, e, w: exact_given(x, e, w, banks32)

    forms = [("scatter 512", None, dict())] + [
        (f"gather {at_once} tokens a walk, blocks of {block}", at_once,
         dict(pair_block=block, back="gather"))
        for at_once in (int(a) for a in args.at_once.split(","))
        for block in (int(b) for b in args.blocks.split(","))]
    for routing, chosen in (("as routed", experts), ("half hold nothing", nothing)):
        want = exact(h, chosen, weights)
        norm = jnp.linalg.norm(want, axis=-1)
        first = None        # the first walk by gathers: the others' are its sums
        for name, at_once, how in forms:
            if at_once:
                moe.GATHER_TOKENS = at_once
            got = jax.jit(lambda h, chosen, weights, banks: moe.moe_held(
                h, chosen, weights, *banks, first_expert=0, experts_held=held,
                experts_total=total, **how))(h, chosen, weights, banks)
            by_token = jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
                norm, 1e-6 * jnp.max(norm))
            empty = norm == 0
            if at_once and first is None:
                first = got
            say(what="values of " + name, routing=routing, tokens=t,
                relative_error=rel(got, want),
                largest_gap_to_the_first_walk_by_gathers=(
                    float(jnp.max(jnp.abs(got - first))) if at_once else None),
                tokens_over_2pct=int(jnp.sum(by_token > 0.02)),
                tokens_over_20pct=int(jnp.sum(by_token > 0.2)),
                worst_token=float(jnp.max(by_token)),
                tokens_that_hold_nothing=int(jnp.sum(empty)),
                largest_in_their_rows=float(jnp.max(jnp.where(
                    empty[:, None], jnp.abs(got), 0.0))),
                finite=bool(jnp.isfinite(got).all()))

    # block by block: the grouped products alone, the rows of one walk
    flat_e = np.asarray(experts)
    block_rows = jax.jit(moe._block_rows, static_argnums=(8, 9, 10))
    for name, at_once, how in forms:
        n, block = at_once or t, how.get("pair_block", moe.PAIR_BLOCK)
        order, sizes, ends = moe._sorted_pairs(experts[:n], 0, held)
        n_pairs, flat_w = int(ends[-1]), weights[:n].reshape(-1)
        blocks = -(-n_pairs // block)
        order = jnp.pad(order, (0, blocks * block - min(n * k, blocks * block)))
        worst = []
        for i in sorted({0, 1, blocks // 2, blocks - 2, blocks - 1} & set(
                range(blocks))):
            token, live, y = block_rows(h[:n], banks, flat_w, order, sizes, ends,
                                        n_pairs, i * block, block, k, 0)
            pair = order[i * block:(i + 1) * block]
            e = jnp.where(live, jnp.asarray(flat_e[:n].reshape(-1))[pair], -1)
            want = exact(h[:n][token], e[:, None], flat_w[pair][:, None])
            err = np.asarray(jnp.linalg.norm(y - want, axis=-1))
            size = np.asarray(jnp.linalg.norm(want, axis=-1))
            e = np.asarray(e)
            groups = {int(g): float(np.linalg.norm(err[e == g])
                                    / np.linalg.norm(size[e == g]))
                      for g in np.unique(e[e >= 0])}
            g_worst = max(groups, key=groups.get)
            worst.append({"block": i, "live_rows": int((e >= 0).sum()),
                          "groups": len(groups),
                          "relative_error": float(np.linalg.norm(err)
                                                  / np.linalg.norm(size)),
                          "worst_group": g_worst,
                          "worst_group_rows": int((e == g_worst).sum()),
                          "worst_group_error": groups[g_worst],
                          "rows_over_5pct": int((err > 0.05 * np.maximum(
                              size, 1e-30))[e >= 0].sum())})
        say(what="blocks of " + name, pairs=n_pairs, blocks=blocks, read=worst)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks", "configs", "granite-4.0-h-small-ep2.json"))
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--blocks", default="512,2048,4096,8192")
    ap.add_argument("--at-once", default="2048",
                    help="tokens a walk by gathers takes at once "
                    "(parallel/moe.GATHER_TOKENS), a list")
    ap.add_argument("--values", action="store_true",
                    help="no times: each walk's values against float32")
    ap.add_argument("--profile", action="store_true",
                    help="also trace the piece's walk and print its operations")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks import harness
    from paddle_tpu.ops import ssd
    from paddle_tpu.parallel import moe

    cfg = harness.read_json(args.config)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    held, total, k = (cfg["num_local_experts"],
                      cfg["published"]["num_local_experts"],
                      cfg["num_experts_per_tok"])
    say = lambda **kw: print(json.dumps(kw), flush=True)
    walk = moe.GATHER_TOKENS
    say(device=jax.devices()[0].device_kind, rows=args.rows, chunk=args.chunk)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bank = lambda key, a, b: (jax.random.normal(key, (held, a, b), jnp.float32)
                              * a ** -0.5).astype(jnp.bfloat16)
    w_gate, w_up, w_down = bank(ks[0], d, f), bank(ks[1], d, f), bank(ks[2], f, d)
    router = jax.random.normal(ks[3], (d, total), jnp.float32) * d ** -0.5

    def layer(**how):
        def fn(h):
            experts, weights = moe.softmax_topk_route(h, router, k)
            return moe.moe_held(h, experts, weights, w_gate, w_up, w_down,
                                first_expert=0, experts_held=held,
                                experts_total=total, **how)
        return jax.jit(fn)

    if args.values:
        h = jax.random.normal(ks[4], (args.rows * args.chunk, d),
                              jnp.float32).astype(jnp.bfloat16)
        values(args, say, moe, h, router, (w_gate, w_up, w_down), held, total, k)
        return 0
    for t in (args.rows * args.chunk, args.rows):
        h = jax.random.normal(ks[4], (t, d), jnp.float32).astype(jnp.bfloat16)
        say(what="route alone", tokens=t, ms=timed(jax.jit(
            lambda h: moe.softmax_topk_route(h, router, k)), h,
            repeat=args.repeat))
        say(what="moe_held scatter 512", tokens=t,
            ms=timed(layer(), h, repeat=args.repeat))
        if t <= 64:
            say(what="moe_held dense", tokens=t, ms=timed(
                layer(back="gather"), h, repeat=args.repeat))
        if t * k > 512:
            for at_once in (int(a) for a in args.at_once.split(",")):
                moe.GATHER_TOKENS = at_once
                for block in (int(b) for b in args.blocks.split(",")):
                    say(what=f"moe_held gather {block}", tokens=t,
                        tokens_a_walk=at_once, ms=timed(
                            layer(pair_block=block, back="gather"), h,
                            repeat=args.repeat))
            moe.GATHER_TOKENS = walk

    if args.profile:
        from benchmarks.tracing import Tracer

        h = jax.random.normal(ks[4], (args.rows * args.chunk, d),
                              jnp.float32).astype(jnp.bfloat16)
        for name, fn in (("gather 4096", layer(pair_block=4096, back="gather")),
                         ("scatter 512", layer())):
            jax.block_until_ready(fn(h))
            tracer = Tracer(1)
            tracer.start()
            for _ in range(3):
                jax.block_until_ready(fn(h))
            ops = tracer.stop().breakdown(top=18)["device_ops"]
            say(what=f"profile of {name}, three calls, ms an operation",
                ops=[[n, round(s * 1e3, 3)] for n, s in ops])

    heads, hd, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    for s in (args.chunk, 2 * args.chunk):
        x = jax.random.normal(ks[5], (args.rows, s, heads * hd), jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(ks[6], (args.rows, s, heads)) - 3)
        b = jax.random.normal(ks[7], (args.rows, s, n), jnp.bfloat16)
        a = -jnp.linspace(1.0, 16.0, heads)
        state = ssd.empty_state(args.rows, heads, hd, n)
        fn = jax.jit(lambda dt, x, b, c, state: ssd.ssd(dt, x, b, c, a, state))
        say(what="ssd", tokens=s, ms=timed(fn, dt, x, b, b, state,
                                           repeat=args.repeat))
    x1 = jax.random.normal(ks[5], (args.rows, heads * hd), jnp.bfloat16)
    step = jax.jit(lambda dt, x, b, c, state: ssd.ssd_step(dt, x, b, c, a, state),
                   donate_argnums=4)
    state = ssd.empty_state(args.rows, heads, hd, n)
    _, state = step(dt[:, 0], x1, b[:, 0], b[:, 0], state)
    t0 = time.perf_counter()
    for _ in range(20):
        _, state = step(dt[:, 0], x1, b[:, 0], b[:, 0], state)
    jax.block_until_ready(state)
    say(what="ssd_step", ms=(time.perf_counter() - t0) * 1e3 / 20)
    return 0


if __name__ == "__main__":
    sys.exit(main())
