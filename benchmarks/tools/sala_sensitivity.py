#!/usr/bin/env python3
"""Is the served check of the ``minicpm_sala`` family a check? One
sensitivity run on the chip, recorded in PERF.md and not repeated in every
run:

    python3 benchmarks/tools/sala_sensitivity.py --seed <n> [--rows 2] [--out chiprun_out/sala_sensitivity.json]

Generates ``--rows`` rows with the cell's generator (its configuration,
traffic and seeded weights), frees the weights, and holds the ids to the
reference seven times: as it is (must pass), and with one fault each time
(each must fail): the chosen blocks left out (window and first block only);
lightning's decays taken from another layer's index; rotary positions left
off lightning's q and k; rotary positions applied in the sparse layers; a
group's scores summed before the scorer's softmax instead of after it; and
every matrix of the reference rounded to an 8-bit float, the nearest
precision below the bfloat16 the configuration states. The last line of
output is the verdicts as one JSON object.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def edits():
    import jax.numpy as jnp

    def forced_only(sh, part, layer, kind, index, lp):
        return (sh._replace(topk=sh.init_blocks + sh.window_size // sh.block_size),
                kind, index, lp)

    def another_decay(sh, part, layer, kind, index, lp):
        return sh, kind, sh.published_layers - 1 - index, lp

    def lightning_unrotated(sh, part, layer, kind, index, lp):
        return sh._replace(lightning_use_rope=False), kind, index, lp

    def sparse_rotated(sh, part, layer, kind, index, lp):
        return sh._replace(attn_use_rope=True), kind, index, lp

    def summed_first(sh, part, layer, kind, index, lp):
        return sh._replace(scorer_sums_first=True), kind, index, lp

    def float8(sh, part, layer, kind, index, lp):
        return sh, kind, index, {
            k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype) if v.ndim >= 2 else v)
            for k, v in lp.items()}

    return {"as_served": None, "chosen_blocks_left_out": forced_only,
            "decay_of_another_layer": another_decay,
            "lightning_rotary_left_off": lightning_unrotated,
            "sparse_rotary_applied": sparse_rotated,
            "scores_summed_before_softmax": summed_first,
            "reference_in_float8": float8}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="sala-serve-long")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None,
                    help="served rows held to the reference (default: the "
                         "family's SERVE_CHECK_ROWS)")
    ap.add_argument("--only", nargs="*", help="these faults only")
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    fam, t = cell.family, cell.traffic
    harness.require_devices(cell.chips)
    rows = args.rows or fam.SERVE_CHECK_ROWS
    weights = fam.decoder_params(cell.config, args.seed, t["prompt"],
                                 t["new_tokens"])
    batches = fam.prompts(cell.config["vocab_size"], t["rows"], t["prompt"],
                          args.seed, -(-rows // t["rows"]))
    prog = fam._program(cell.config, t["new_tokens"])
    params = jax.device_put(weights.host_params())
    generate = jax.jit(
        lambda p, i: prog.apply(p, {}, training=False, prompt_ids=i)[0]["ids"])
    served = np.concatenate([np.asarray(generate(params, b)) for b in batches])[:rows]
    prompt = np.concatenate(batches)[:rows]
    del params, generate
    out = {"seed": args.seed, "rows": rows, "margin": fam.LOGIT_MARGIN,
           "mean_gap_limit": fam.MEAN_GAP_LIMIT, "agree_floor": fam.AGREE_FLOOR}
    for name, edit in edits().items():
        if args.only and name not in args.only and name != "as_served":
            continue
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
