#!/usr/bin/env python3
"""Is the served check of the ``phi4_flash`` family a check? One sensitivity
run on the chip, recorded in PERF.md and not repeated in every run:

    python3 benchmarks/tools/phi4_flash_sensitivity.py --seed <n> [--rows 8] [--out chiprun_out/phi4_flash_sensitivity.json]

The twin of ``brumby_sensitivity.py``. The reference carries nothing that
could be faulted (no state, no ring, no cache, the cross-decoder over every
position), so the faults are the program's: the cell's generator (its
configuration, traffic and seeded weights) generates some of a request's rows
as it is (must pass) and six more times with one edit each time in what it
carries (each must fail one limit at least), the ids of every run held to
the sound reference after the weights are freed:

- ``state_dropped_between_pieces``: a Mamba layer's state and convolution
  tail zeroed before every piece of the prefill;
- ``window_of_256``: the program built with half the window;
- ``shared_kv_cut_to_512``: the readers of (K*, V*) see its last 512
  positions only;
- ``memory_zeroed``: the gated memory units read zeros;
- ``lambda_of_0``: no second softmax map is subtracted;
- ``state_in_bfloat16``: the state rounded to bfloat16 wherever it is handed
  on (the nearest precision below the float32 the configuration states;
  this is what the fourth limit, on the carried state itself, is for).

An eighth reading holds the sound ids to a reference whose every matrix is
rounded to an 8-bit float, the nearest precision below the bfloat16 of the
weights. A fault wraps names of ``layers/sambay.py`` for the length of one
trace (or changes one key of the configuration); the program has no switch
for any of them. The last line of output is the verdicts as one JSON object.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def faults():
    """``{name: (wrappers, config edit)}``: ``wrappers`` maps a name of
    ``layers/sambay.py`` to a function that takes the sound one and returns
    the faulty one; the edit is keys of the configuration file."""
    import jax
    import jax.numpy as jnp

    def dropped(fn):
        return lambda x, p, dims, carried: fn(
            x, p, dims, jax.tree.map(jnp.zeros_like, carried))

    def last_512(fn):
        def call(q, k_cache, v_cache, live, dims, lam):
            if k_cache.shape[1] != dims.window:         # (K*, V*), not a ring
                at = jnp.arange(live.shape[0])
                live = live & (at > jnp.sum(live) - 1 - 512)
            return fn(q, k_cache, v_cache, live, dims, lam)
        return call

    # (``reduce_precision``, which the compiler has to keep: it may drop a
    # conversion to bfloat16 and back as excess precision, PERF.md section 6,
    # PR 39)
    def rounded(fn):
        def call(*args, **kw):
            y, state = fn(*args, **kw)
            return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)
        return call

    no_memory = lambda fn: lambda x, p, dims, memory: fn(
        x, p, dims, jnp.zeros_like(memory))
    return {
        "as_served": ({}, {}),
        "state_dropped_between_pieces": ({"mamba_prefill": dropped}, {}),
        "window_of_256": ({}, {"sliding_window": 256}),
        "shared_kv_cut_to_512": ({"cache_attention": last_512}, {}),
        "memory_zeroed": ({"gmu": no_memory}, {}),
        "lambda_of_0": ({"_lambda": lambda fn: lambda p, layer: 0.0}, {}),
        "state_in_bfloat16": ({"selective_scan": rounded,
                               "mamba_step": rounded}, {})}


@contextlib.contextmanager
def faulted(wrappers):
    """The layer's names wrapped while a program is traced."""
    from paddle_tpu.layers import sambay as layer

    sound = {name: getattr(layer, name) for name in wrappers}
    for name, wrap in wrappers.items():
        setattr(layer, name, wrap(sound[name]))
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(layer, name, fn)


def float8(lp, layer):
    import jax.numpy as jnp

    return {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype) if v.ndim >= 2 else v)
            for k, v in lp.items()}


def generate(fam, config, new_tokens: int, fault):
    """The jitted generator ``(params, prompt_ids) -> outputs`` traced under
    ``fault``."""
    import jax

    wrappers, edit = fault
    prog = fam._program({**config, **edit}, new_tokens)

    def run(params, ids):
        with faulted(wrappers):
            return prog.apply(params, {}, training=False, prompt_ids=ids)[0]

    return jax.jit(run)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="phi4flash-serve-reason")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, help="rows generated and checked "
                    "(default: the traffic's)")
    ap.add_argument("--only", nargs="*", help="these faults only")
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    fam, t = cell.family, cell.traffic
    harness.require_devices(cell.chips)
    rows = args.rows or t["rows"]
    weights = fam.decoder_params(cell.config, args.seed, t["prompt"],
                                 t["new_tokens"])
    (prompt,) = fam.prompts(cell.config["vocab_size"], rows, t["prompt"],
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
    out = {"seed": args.seed, "rows": rows, "margin": fam.LOGIT_MARGIN,
           "mean_gap_limit": fam.MEAN_GAP_LIMIT, "agree_floor": fam.AGREE_FLOOR,
           "carried_error_limit": fam.CARRIED_ERROR_LIMIT}
    sound = served["as_served"]
    for name, audit in served.items():
        out[name] = fam.served_check(cell.config, weights, prompt, audit["ids"],
                                     audit=audit)
        out[name]["ids_as_sound"] = float((audit["ids"] == sound["ids"]).mean())
        print(name, out[name], flush=True)
    if not args.only or "reference_in_float8" in args.only:
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
