#!/usr/bin/env python3
"""Is the served check of the ``trinity`` family a check? One sensitivity run
on the chip, recorded in PERF.md and not repeated in every run:

    python3 benchmarks/tools/trinity_sensitivity.py --seed <n> [--fault-rows 2]
        [--out chiprun_out/trinity_sensitivity.json]

Generates one request (``rows`` rows) with the cell's generator (its
configuration, traffic and seeded weights), frees the weights, and holds the
ids to the reference: as served (the first ``SERVE_CHECK_ROWS`` rows; must
pass), and the first ``--fault-rows`` rows under each of these faults of the
reference (each must fail a limit):

- ``window_mask_left_off``: the first sliding layer with experts sees every
  key before a query, not the last 4,096;
- ``full_layer_rotated``: the full layer rotates its queries and keys as a
  sliding layer does;
- ``output_gate_dropped``: the gate's projection zeroed in every layer
  (``sigmoid(0)`` is one half for every channel, which the norm after the
  mixer takes out again: the attention ungated);
- ``selection_bias_dropped``, ``route_scale_left_out``: the router's;
- ``neighbouring_key_head``: every query head reads the key/value head
  after its own (the columns of ``k`` and ``v`` turned by one head), what an
  index map off by one group does;
- ``reference_in_float8``: every matrix of the reference rounded to an 8-bit
  float, the nearest precision below the bfloat16 the configuration states.

And one fault of the program, for which the request is generated again
(``ring_written_one_slot_off``: a step's key and value go to slot ``(index
+ 1) % window``, over a key still inside the window) and held to the sound
reference. The last line of output is the verdicts as one JSON object.
"""

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def edits(config):
    """``{name: edit}`` for ``families/trinity.reference_logits``: ``edit(shape,
    part, layer, kind, params) -> (shape, kind, params)``, ``layer`` counting
    the held layers from 0."""
    import jax.numpy as jnp

    from benchmarks.reference import trinity as reference

    held = reference.layers_of(config)
    first_sliding = next(l for l, (_, kind, dense) in enumerate(held)
                         if kind == reference.SLIDING and not dense)
    everything = 10 ** 9

    def no_window(sh, part, layer, kind, lp):
        if part == "attention" and layer == first_sliding:
            sh = sh._replace(window=everything)
        return sh, kind, lp

    def rotated(sh, part, layer, kind, lp):
        if part == "attention" and kind == reference.FULL:
            return sh._replace(window=everything), reference.SLIDING, lp
        return sh, kind, lp

    def no_gate(sh, part, layer, kind, lp):
        if part == "attention":
            lp = dict(lp, gate=jnp.zeros_like(lp["gate"]))
        return sh, kind, lp

    def no_bias(sh, part, layer, kind, lp):
        if "select_bias" in lp:
            lp = dict(lp, select_bias=jnp.zeros_like(lp["select_bias"]))
        return sh, kind, lp

    def no_scale(sh, part, layer, kind, lp):
        return sh._replace(route_scale=1.0), kind, lp

    def next_head(sh, part, layer, kind, lp):
        if part == "attention":
            lp = dict(lp, k=jnp.roll(lp["k"], -sh.head_dim, axis=1),
                      v=jnp.roll(lp["v"], -sh.head_dim, axis=1))
        return sh, kind, lp

    def float8(sh, part, layer, kind, lp):
        return sh, kind, {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                              if v.ndim >= 2 else v) for k, v in lp.items()}

    return {"window_mask_left_off": no_window, "full_layer_rotated": rotated,
            "output_gate_dropped": no_gate, "selection_bias_dropped": no_bias,
            "route_scale_left_out": no_scale,
            "neighbouring_key_head": next_head, "reference_in_float8": float8}


@contextlib.contextmanager
def ring_one_slot_off():
    """``layers/gqa.window_decode`` writing a step's key and value one slot
    further on, while a program is traced."""
    from paddle_tpu.layers import gqa

    sound = gqa.window_decode

    def faulty(x, p, dims, ring, index):
        import jax

        with jax.named_scope("swa"):
            return gqa._decode(x, p, dims, ring, index,
                               (index + 1) % dims.window,
                               gqa.kv_ring.live(dims.window, index), gqa.WINDOW)

    gqa.window_decode = faulty
    try:
        yield
    finally:
        gqa.window_decode = sound


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="trinity-serve-long")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault-rows", type=int, default=2)
    ap.add_argument("--only", nargs="*", help="fault names (default: all)")
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
    prompt = fam.prompts(cell.config["vocab_size"], t["rows"], t["prompt"],
                         args.seed, 1)[0]
    wanted = lambda name: not args.only or name in args.only

    def generated(fault=contextlib.nullcontext):
        """One request's ids from a program traced under ``fault``; the
        weights are on the device for the call alone."""
        with fault():
            prog = fam._program(cell.config, t["new_tokens"])
            generate = jax.jit(lambda p, i: prog.apply(
                p, {}, training=False, prompt_ids=i)[0]["ids"])
            params = jax.device_put(weights.host_params())
            ids = np.asarray(generate(params, prompt))
        del params, generate
        return ids

    n = args.fault_rows
    out = {"seed": args.seed, "margin": fam.LOGIT_MARGIN,
           "mean_gap_limit": fam.MEAN_GAP_LIMIT, "agree_floor": fam.AGREE_FLOOR,
           "fault_rows": n}

    clock = [time.perf_counter()]

    def note(name, verdict):
        clock.append(time.perf_counter())
        out[name] = dict(verdict, seconds=round(clock[-1] - clock[-2], 1))
        print(name, out[name], flush=True)

    served = generated()
    print(f"generated in {time.perf_counter() - clock[0]:.1f} s", flush=True)
    clock.append(time.perf_counter())
    if wanted("as_served"):
        sound = fam.SERVE_CHECK_ROWS
        note("as_served", fam.served_check(cell.config, weights, prompt[:sound],
                                           served[:sound]))
    for name, edit in edits(cell.config).items():
        if wanted(name):
            note(name, fam.served_check(cell.config, weights, prompt[:n],
                                        served[:n], edit=edit))
    if wanted("ring_written_one_slot_off"):
        faulty = generated(ring_one_slot_off)
        note("ring_written_one_slot_off", dict(
            fam.served_check(cell.config, weights, prompt[:n], faulty[:n]),
            ids_changed=float((faulty != served).mean())))
    faults = [v for k, v in out.items()
              if isinstance(v, dict) and k != "as_served"]
    out["a_check"] = bool(out.get("as_served", {"ok": True})["ok"]
                          and not any(v["ok"] for v in faults))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if out["a_check"] else 1


if __name__ == "__main__":
    sys.exit(main())
