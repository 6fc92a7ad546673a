#!/usr/bin/env python3
"""Is the served check of the ``kimi_k2`` family a check? One sensitivity run
on the chip, recorded in PERF.md and not repeated in every run:

    python3 benchmarks/tools/k25_sensitivity.py --seed <n> [--out chiprun_out/k25_sensitivity.json]

Generates ``SERVE_CHECK_ROWS`` rows with the cell's generator (its
configuration, traffic and seeded weights), frees the weights, and holds the
ids to the reference five times: as it is (must pass), with the routed
experts left out of the reference, with the reference's keys rotated one
position on (what a cache written at the wrong index does), with the
selection bias dropped, and with every matrix of the reference rounded to
an 8-bit float, the nearest precision below the bfloat16 the configuration
states (each must fail). The last line of output is the five verdicts as
one JSON object.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def edits():
    import jax.numpy as jnp

    from benchmarks.reference import kimi_k2 as reference

    def no_experts(sh, part, layer, lp):
        return sh._replace(held=0), lp

    def keys_one_on(sh, part, layer, lp):
        # a rotation is linear and rotations commute: the rotary columns
        # of kv_a turned by one position's angles give every key the
        # rotation of the position after its own
        if part == "attention":
            w = lp["kv_a"]
            turned = reference.rotate(w[:, sh.kv_lora:], jnp.ones(w.shape[0]),
                                      reference.yarn_inv_freq(sh.rope, sh), 1.0)
            lp = dict(lp, kv_a=jnp.concatenate([w[:, :sh.kv_lora], turned], 1))
        return sh, lp

    def no_bias(sh, part, layer, lp):
        if "select_bias" in lp:
            lp = dict(lp, select_bias=jnp.zeros_like(lp["select_bias"]))
        return sh, lp

    def float8(sh, part, layer, lp):
        return sh, {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                        if v.ndim >= 2 else v) for k, v in lp.items()}

    return {"as_served": None, "routed_experts_left_out": no_experts,
            "keys_one_position_on": keys_one_on,
            "selection_bias_dropped": no_bias, "reference_in_float8": float8}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="k25-serve-batch")
    ap.add_argument("--seed", type=int, required=True)
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
    batches = fam.prompts(cell.config["vocab_size"], t["rows"], t["prompt"],
                          args.seed, fam.SERVE_CHECK_ROWS // t["rows"])
    prog = fam._program(cell.config, t["new_tokens"])
    params = jax.device_put(weights.host_params())
    generate = jax.jit(
        lambda p, i: prog.apply(p, {}, training=False, prompt_ids=i)[0]["ids"])
    served = np.concatenate([np.asarray(generate(params, b)) for b in batches])
    prompt = np.concatenate(batches)
    del params
    out = {"seed": args.seed, "margin": fam.LOGIT_MARGIN,
           "mean_gap_limit": fam.MEAN_GAP_LIMIT,
           "agree_floor": fam.AGREE_FLOOR}
    for name, edit in edits().items():
        out[name] = fam.served_check(cell.config, weights, prompt, served,
                                     edit=edit)
        print(name, out[name], flush=True)
    out["a_check"] = bool(out["as_served"]["ok"] and not any(
        v["ok"] for k, v in out.items()
        if isinstance(v, dict) and k != "as_served"))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if out["a_check"] else 1


if __name__ == "__main__":
    sys.exit(main())
