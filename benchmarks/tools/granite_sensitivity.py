#!/usr/bin/env python3
"""Is the served check of the ``granite_hybrid`` family a check? One
sensitivity run on the chip, recorded in PERF.md and not repeated in every
run:

    python3 benchmarks/tools/granite_sensitivity.py --seed <n> [--rows 8] [--fault-rows 2] [--forms] [--out chiprun_out/granite_sensitivity.json]
    python3 benchmarks/tools/granite_sensitivity.py --seed <n> --scales

The cell's generator (its configuration, traffic and seeded weights)
generates ``--rows`` rows as it is (must pass), and the first ``--fault-rows``
of them are held to the check again under one fault at a time (each must fail
one limit at least). What a program carries is faulted in the program, which
generates again:

- ``state_in_bfloat16``: a Mamba-2 layer's state rounded to bfloat16 wherever
  it is handed on (the nearest precision below the float32 the configuration
  states; this is what the fourth limit, on the carried state itself, is
  for);
- ``state_zeroed_between_pieces``: a layer's state and convolution tail
  zeroed before every piece of the prefill.

What is a function of the weights alone is faulted in the reference, which
the sound ids are then held to:

- ``dx_dropped``: no skip ``D x``;
- ``norm_before_gate``: ``rms(y) * silu(z)`` for ``rms(y * silu(z))``;
- ``softmax_over_all_72``: the router's weights from a softmax over every
  logit, the ten largest kept as they are;
- ``residual_multiplier_left_out``, ``scale_1_over_sqrt_128``: a multiplier
  at what another model would have it;
- ``neighbouring_key_head``: query head ``i`` on key head ``i // 4 + 1``;
- ``reference_in_float8``: every matrix of the reference rounded to an 8-bit
  float, the nearest precision below the bfloat16 of the weights.

A fault wraps a name of ``layers/mamba2.py`` or of the reference for the
length of one trace, or changes a field of the reference's shape; neither has
a switch for any of them. ``--forms`` adds witnesses that are no faults and
must pass: the same weights and prompts through other walks of the expert
pairs (``parallel/moe.py``: the walk by scatter-add in blocks of 512 that
the other two expert models take, and the walk by gathers cut otherwise),
generated with all ``--rows`` (how a piece's pairs are cut depends on them)
and held to the check on the first ``--form-rows``. ``--scales`` prints instead what each half of each
layer adds to the stream under the seeded weights (the readings the family's
gains were set from). The last line of output is the verdicts as one JSON
object.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def program_faults():
    """``{name: (module, wrappers)}``: a name of ``layers/mamba2.py`` -> a
    function that takes the sound one and returns the faulty one."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.layers import mamba2

    # (``reduce_precision``, which the compiler has to keep: it may drop a
    # conversion to bfloat16 and back as excess precision, PERF.md section 6,
    # PR 39)
    round_state = lambda state: jax.lax.reduce_precision(
        state, exponent_bits=8, mantissa_bits=7)

    def rounded_piece(fn):
        def call(x, p, dims, carried):
            x, (tail, state), given = fn(x, p, dims, carried)
            return x, (tail, round_state(state)), given
        return call

    def rounded_step(fn):
        def call(x, p, dims, carried, index, write):
            x, (ring, state), given = fn(x, p, dims, carried, index, write)
            return x, (ring, round_state(state)), given
        return call

    zeroed = lambda fn: lambda x, p, dims, carried: fn(
        x, p, dims, jax.tree.map(jnp.zeros_like, carried))
    return {"state_in_bfloat16": (mamba2, {"mamba2_prefill": rounded_piece,
                                           "mamba2_decode": rounded_step}),
            "state_zeroed_between_pieces": (mamba2, {"mamba2_prefill": zeroed})}


def program_forms():
    """``{name: (module, wrappers)}``: other walks of the expert pairs, each
    as sound as the one the cell takes, by names of ``parallel/moe.py``."""
    from paddle_tpu.parallel import moe

    told = lambda **how: lambda fn: lambda *a, **kw: fn(*a, **{**kw, **how})
    return {"walk_by_scatter_512": (moe, {"moe_held": told(
                back="scatter", pair_block=moe.PAIR_BLOCK)}),
            "walks_of_2048_in_blocks_of_4096": (moe, {
                "moe_held": told(pair_block=4096),
                "GATHER_TOKENS": lambda _: 2048})}


def reference_faults():
    """``{name: (wrappers, edit)}``: ``wrappers`` maps a name of the
    reference to ``sound -> faulty``; ``edit`` is what
    ``family.reference_hidden`` takes."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import granite_hybrid as ref

    same = lambda sh, part, layer, kind, lp: (sh, lp)

    def no_skip(sh, part, layer, kind, lp):
        return sh, ({**lp, "d_skip": jnp.zeros_like(lp["d_skip"])}
                    if "d_skip" in lp else lp)

    def float8(sh, part, layer, kind, lp):
        return sh, {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                        if v.ndim >= 2 else v) for k, v in lp.items()}

    def norm_first(sound):
        def mamba(u, lp, sh):
            rows, s, _ = u.shape
            x, b, c, dt, z = ref.mamba_inputs(u, lp, sh)
            y, _ = ref.recurrence(x, b, c, dt, -jnp.exp(lp["a_log"]),
                                  lp["d_skip"])
            y = ref.rms_norm(y.reshape(rows, s, sh.d_inner), lp["gate_norm"],
                             sh.eps) * jax.nn.silu(z)
            return y @ lp["out_proj"]
        return mamba

    def over_all(sound):
        def route(h, lp, sh):
            probs = jax.nn.softmax(h @ lp["router"], axis=-1)
            picked, idx = jax.lax.top_k(probs, sh.top_k)
            return idx, picked
        return route

    def neighbour(sound):
        def attention(a, lp, sh):
            kvw = sh.kv_heads * sh.head_dim
            turn = lambda w: jnp.roll(w.reshape(-1, sh.kv_heads, sh.head_dim),
                                      -1, axis=1).reshape(-1, kvw)
            return sound(a, {**lp, "k": turn(lp["k"]), "v": turn(lp["v"])}, sh)
        return attention

    return {
        "dx_dropped": ({}, no_skip),
        "norm_before_gate": ({"mamba": norm_first}, same),
        "softmax_over_all_72": ({"route": over_all}, same),
        "residual_multiplier_left_out": ({}, lambda sh, *a: (
            sh._replace(residual_multiplier=1.0), a[-1])),
        "scale_1_over_sqrt_128": ({}, lambda sh, *a: (
            sh._replace(attention_multiplier=sh.head_dim ** -0.5), a[-1])),
        "neighbouring_key_head": ({"attention": neighbour}, same),
        "reference_in_float8": ({}, float8)}


@contextlib.contextmanager
def faulted(module, wrappers):
    """The module's names wrapped while a program is traced; the family's
    compiled reference parts are dropped before and after, so that a name
    wrapped here is traced again."""
    from benchmarks.families import granite_hybrid as family

    sound = {name: getattr(module, name) for name in wrappers}
    family._jitted.cache_clear()
    for name, wrap in wrappers.items():
        setattr(module, name, wrap(sound[name]))
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(module, name, fn)
        family._jitted.cache_clear()


def generate(fam, config, new_tokens: int, module=None, wrappers=None):
    """The jitted generator ``(params, prompt_ids) -> outputs`` traced with
    ``module``'s names under ``wrappers``."""
    import jax

    prog = fam._program(config, new_tokens)

    def run(params, ids):
        with faulted(module, wrappers or {}):
            return prog.apply(params, {}, training=False, prompt_ids=ids)[0]

    return jax.jit(run)


def scales(fam, config, weights, ids):
    """What each half of each held layer adds to the stream of the reference
    (the root mean square of the half's addition, an element) and the share
    of a layer's (token, expert) pairs that fall to the experts held here,
    the embedding's size in the stream, and how far a token's own row stands
    over the other logits at the end, in their deviations."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import granite_hybrid as ref

    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    # the part of ``a [1, tokens, d]`` every token shares, as a share of its size
    shared_by_all = lambda a: rms(jnp.mean(a, axis=1)) / rms(a)
    sh = ref.shape_of(config, query_block=fam.CHECK_QUERY_BLOCK)
    out = {"layers": []}
    with jax.default_matmul_precision("highest"):
        x = ref.embed(weights.reference_ends()["emb"], jnp.asarray(ids), sh)
        out["embedding"] = rms(x)
        for index, kind in ref.layers_of(config):
            mixed = jax.jit(ref.mixer_part, static_argnums=(2, 3))(
                x, weights.reference_mixer(index, kind), sh, kind)
            lp = weights.reference_ffn(index)
            m, idx, w, shared = jax.jit(ref.routed_setup, static_argnums=2)(
                mixed, lp, sh)
            routed = jnp.zeros_like(shared)
            for j in range(sh.held):
                routed = jax.jit(ref.add_expert)(
                    routed, m, idx, w, sh.rank * sh.held + j,
                    *weights.reference_expert(index, j))
            after = ref.ffn_close(mixed, shared + routed, sh)
            held = (idx >= sh.rank * sh.held) & (idx < (sh.rank + 1) * sh.held)
            out["layers"].append({
                "layer": index, "kind": kind, "mixer": rms(mixed - x),
                "pairs_held_here": float(jnp.mean(held)),
                "mixer_shared_by_all": shared_by_all(mixed - x),
                "router_input_shared_by_all": shared_by_all(m),
                "shared": rms(sh.residual_multiplier * shared),
                "routed_here": rms(sh.residual_multiplier * routed),
                "ffn_half": rms(after - mixed), "stream": rms(after)})
            x = after
        ends = weights.reference_ends()
        logits = ref.head_logits(x[0, -64:], ends["final_norm"], ends["emb"], sh)
        own = jnp.take_along_axis(logits, jnp.asarray(ids[0, -64:])[:, None], 1)
        out["own_row_over_the_rest"] = float(jnp.mean(
            (own[:, 0] - logits.mean(-1)) / logits.std(-1)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="granite-serve-agent")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=8,
                    help="rows generated and held to the check as served")
    ap.add_argument("--fault-rows", type=int, default=2,
                    help="of them, rows each fault is read on")
    ap.add_argument("--only", nargs="*", help="these faults only")
    ap.add_argument("--forms", action="store_true",
                    help="also the other walks of the expert pairs (must pass)")
    ap.add_argument("--form-rows", type=int, default=4,
                    help="rows of each such walk held to the check")
    ap.add_argument("--scales", action="store_true")
    ap.add_argument("--scale-tokens", type=int, default=512)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import harness
    from benchmarks.reference import granite_hybrid as ref

    cell = harness.load_cell(args.workload)
    fam, t = cell.family, cell.traffic
    harness.require_devices(cell.chips, args.allow_cpu)
    weights = fam.decoder_params(cell.config, args.seed, t["prompt"],
                                 t["new_tokens"])
    (prompt,) = fam.prompts(cell.config["vocab_size"], args.rows, t["prompt"],
                            args.seed, 1)
    if args.scales:
        out = scales(fam, cell.config, weights, prompt[:1, :args.scale_tokens])
        print(json.dumps(out), flush=True)
        return 0
    wanted = lambda name: not args.only or name in args.only
    params = jax.device_put(weights.host_params())
    few = args.fault_rows
    served = {"as_served": generate(fam, cell.config, t["new_tokens"])(
        params, prompt)}
    for name, how in program_faults().items():
        if wanted(name):
            served[name] = generate(fam, cell.config, t["new_tokens"], *how)(
                params, prompt[:few])
    forms = program_forms() if args.forms else {}
    for name, how in forms.items():
        served[name] = generate(fam, cell.config, t["new_tokens"], *how)(
            params, prompt)
    served = {name: {k: np.asarray(v)[:args.form_rows if name in forms else None]
                     for k, v in out.items()} for name, out in served.items()}
    del params
    out = {"seed": args.seed, "rows": args.rows, "fault_rows": few,
           "margin": fam.LOGIT_MARGIN, "mean_gap_limit": fam.MEAN_GAP_LIMIT,
           "agree_floor": fam.AGREE_FLOOR,
           "carried_error_limit": fam.CARRIED_ERROR_LIMIT}
    sound = served["as_served"]
    for name, audit in served.items():
        n = len(audit["ids"])
        out[name] = fam.served_check(cell.config, weights, prompt[:n],
                                     audit["ids"], audit=audit)
        out[name]["ids_as_sound"] = float((audit["ids"] == sound["ids"][:n]).mean())
        print(name, out[name], flush=True)
    cut = {k: v[:few] for k, v in sound.items()}
    out["as_served_on_fault_rows"] = fam.served_check(
        cell.config, weights, prompt[:few], cut["ids"], audit=cut)
    for name, (wrappers, edit) in reference_faults().items():
        if wanted(name):
            with faulted(ref, wrappers):
                out[name] = fam.served_check(cell.config, weights, prompt[:few],
                                             cut["ids"], audit=cut, edit=edit)
            print(name, out[name], flush=True)
    out["passed_though_faulty"] = [
        k for k, v in out.items() if isinstance(v, dict)
        and not k.startswith("as_served") and k not in forms and v["ok"]]
    out["sound_forms_refused"] = [k for k in forms if not out[k]["ok"]]
    out["a_check"] = bool(out["as_served"]["ok"]
                          and not out["passed_though_faulty"]
                          and not out["sound_forms_refused"])
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if out["a_check"] else 1


if __name__ == "__main__":
    sys.exit(main())
