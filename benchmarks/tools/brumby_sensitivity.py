#!/usr/bin/env python3
"""Is the served check of the ``brumby`` family a check? One sensitivity run
on the chip, recorded in PERF.md and not repeated in every run:

    python3 benchmarks/tools/brumby_sensitivity.py --seed <n> [--out chiprun_out/brumby_sensitivity.json]

The reference has no state to fault (it computes the attention form), so
here the faults are the program's: the cell's generator (its configuration,
traffic and seeded weights) generates one request's rows as it is (must
pass) and four more times with one fault each time in what it carries (each
must fail), the ids of every run held to the sound reference after the
weights are freed: the state rounded to bfloat16 wherever it is handed on
(the nearest precision below the float32 the configuration states); the
carried key sum dropped (the divisor keeps a chunk's or a step's own terms);
a gate of 1 (nothing forgotten); the state zeroed before every chunk of the
prompt. (PERF.md section 6, PR 39, has the chip's readings: the last three
fail every limit; the first is what the fourth limit, on the carried sums
themselves, is for.) ``--carried-only`` makes the fourth reading alone,
without the reference's forward. A sixth reading holds the sound ids to a
reference whose every matrix is rounded to an 8-bit float, the nearest
precision below the bfloat16 of the weights. A fault wraps ``layers/retention.py``'s two calls
into ``ops/power_retention.py`` for the length of one trace; the program has
no switch for any of them. The last line of output is the verdicts as one
JSON object.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def faults():
    """``{name: (wrap the chunked call, wrap the one-token call)}``; each
    wrapper takes the sound function and returns the faulty one. Both calls
    have the signature ``(q, k, v, log_gamma, state, heads, kv_heads)``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.power_retention import NORM_ROWS

    def handed_on(change):
        def wrap(fn):
            def call(q, k, v, log_gamma, state, *heads):
                o, state = fn(q, k, v, log_gamma, state, *heads)
                return o, change(state)
            return call
        return wrap

    def taken_in(change):
        def wrap(fn):
            return lambda q, k, v, log_gamma, state, *heads: fn(
                *change(q, k, v, log_gamma, state), *heads)
        return wrap

    sound = lambda fn: fn
    # (``reduce_precision``, which the compiler has to keep: it may drop a
    # conversion to bfloat16 and back as excess precision, and on the chip
    # it did: PERF.md section 6, PR 39, after review)
    rounded = handed_on(lambda s: jax.lax.reduce_precision(
        s, exponent_bits=8, mantissa_bits=7))
    no_sum = handed_on(lambda s: s.at[:, :, -NORM_ROWS:].set(0.0))
    gate_1 = taken_in(lambda q, k, v, lg, s: (q, k, v, jnp.zeros_like(lg), s))
    zeroed = taken_in(lambda q, k, v, lg, s: (q, k, v, lg, jnp.zeros_like(s)))
    return {"as_served": (sound, sound), "state_in_bfloat16": (rounded, rounded),
            "key_sum_dropped": (no_sum, no_sum), "gate_of_1": (gate_1, gate_1),
            "state_zeroed_between_chunks": (zeroed, sound)}


@contextlib.contextmanager
def faulted(fault):
    """The layer's two calls into the op wrapped by ``fault`` while a
    program is traced."""
    from paddle_tpu.layers import retention as layer

    chunked, step = layer.retention, layer.retention_step
    layer.retention, layer.retention_step = fault[0](chunked), fault[1](step)
    try:
        yield
    finally:
        layer.retention, layer.retention_step = chunked, step


def float8(lp):
    import jax.numpy as jnp

    return {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype) if v.ndim >= 2 else v)
            for k, v in lp.items()}


def generate(fam, config, new_tokens: int, fault):
    """The jitted generator ``(params, prompt_ids) -> outputs`` traced under
    ``fault``."""
    import jax

    prog = fam._program(config, new_tokens)

    def run(params, ids):
        with faulted(fault):
            return prog.apply(params, {}, training=False, prompt_ids=ids)[0]

    return jax.jit(run)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="brumby-serve-decode")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", nargs="*", help="these faults only")
    ap.add_argument("--carried-only", action="store_true",
                    help="the check of the carried sums alone")
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    fam, t = cell.family, cell.traffic
    harness.require_devices(cell.chips)
    weights = fam.decoder_params(cell.config, args.seed, t["prompt"],
                                 t["new_tokens"])
    (prompt,) = fam.prompts(cell.config["vocab_size"], t["rows"], t["prompt"],
                            args.seed, 1)
    params = jax.device_put(weights.host_params())
    served = {}
    for name, fault in faults().items():
        if args.only and name not in args.only and name != "as_served":
            continue
        served[name] = {k: np.asarray(v) for k, v in generate(
            fam, cell.config, t["new_tokens"], fault)(params, prompt).items()}
        print(name, "generated", flush=True)
    del params
    out = {"seed": args.seed, "rows": t["rows"], "margin": fam.LOGIT_MARGIN,
           "mean_gap_limit": fam.MEAN_GAP_LIMIT, "agree_floor": fam.AGREE_FLOOR,
           "carried_error_limit": fam.CARRIED_ERROR_LIMIT}
    sound = served["as_served"]
    for name, audit in served.items():
        out[name] = (fam.carried_check(audit) if args.carried_only else
                     fam.served_check(cell.config, weights, prompt, audit["ids"],
                                      audit=audit))
        out[name]["ids_as_sound"] = float((audit["ids"] == sound["ids"]).mean())
        print(name, out[name], flush=True)
    if not args.carried_only and (not args.only
                                  or "reference_in_float8" in args.only):
        out["reference_in_float8"] = fam.served_check(
            cell.config, weights, prompt, sound["ids"], edit=float8, audit=sound)
        print("reference_in_float8", out["reference_in_float8"], flush=True)
    out["passed_though_faulty"] = [k for k, v in out.items() if isinstance(v, dict)
                                   and k != "as_served" and v["ok"]]
    out["a_check"] = bool(out["as_served"]["ok"]
                          and not out["passed_though_faulty"])
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if out["a_check"] else 1


if __name__ == "__main__":
    sys.exit(main())
