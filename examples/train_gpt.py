"""Train a small GPT language model end to end — the runnable
counterpart of MIGRATION.md's patterns (the reference's book chapters
played this role).

Single device:
    python examples/train_gpt.py --steps 50

Data parallel over every local device (TPU chips or a virtual CPU mesh
via XLA_FLAGS=--xla_force_host_platform_device_count=8):
    python examples/train_gpt.py --steps 50 --dp

Resume from a checkpoint directory:
    python examples/train_gpt.py --steps 50 --ckpt /tmp/gpt_ckpt

Gradient accumulation with the hoisted (once-per-step) exchange:
    python examples/train_gpt.py --steps 50 --dp --accum 2 --hoisted

Generate a continuation with the trained weights (optionally with the
int8 KV cache — half the bf16 cache bytes on the HBM-bound decode):
    python examples/train_gpt.py --steps 100 --generate 16 --int8-kv
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np


def synthetic_batches(vocab, batch, seq, seed=0):
    """A learnable synthetic LM stream: each sequence is an arithmetic
    progression mod vocab, so next-token prediction is solvable."""
    rng = np.random.RandomState(seed)
    while True:
        start = rng.randint(3, vocab, (batch, 1))
        step = rng.randint(1, 7, (batch, 1))
        ids = (start + step * np.arange(seq)[None, :]) % (vocab - 3) + 3
        ids = ids.astype(np.int32)
        labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        yield {"ids": ids, "labels": labels.astype(np.int32)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d_model", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--dp", action="store_true",
                   help="data-parallel over all local devices")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation microbatches per step")
    p.add_argument("--hoisted", action="store_true",
                   help="with --dp --accum N: shard_map-local "
                        "accumulation, ONE gradient exchange per step")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-decode N tokens from a "
                        "training-stream prompt")
    p.add_argument("--int8-kv", action="store_true",
                   help="decode with the int8 KV cache")
    p.add_argument("--ckpt", default=None,
                   help="checkpoint dir: resumes if present, saves at end")
    args = p.parse_args()
    if args.hoisted and args.accum <= 1:
        p.error("--hoisted requires --accum N>1 (there is no "
                "accumulation loop to hoist the exchange out of)")
    if args.int8_kv and not args.generate:
        p.error("--int8-kv applies to decoding: pass --generate N")

    import jax

    import paddle_tpu as pt
    from paddle_tpu import io, optimizer as opt
    from paddle_tpu.models import gpt

    cfg = gpt.base_config(vocab_size=args.vocab, max_len=args.seq,
                          d_model=args.d_model, d_inner=4 * args.d_model,
                          num_heads=4, num_layers=args.layers,
                          fused_ce=False, use_flash=False)
    prog = pt.build(gpt.make_model(cfg))

    mesh = rules = strategy = None
    if args.dp:
        mesh = pt.make_mesh({"dp": jax.device_count()})
        rules = pt.parallel.replicated()
        print(f"data-parallel over {jax.device_count()} devices")
    if args.accum > 1:
        from paddle_tpu.parallel import DistStrategy
        strategy = DistStrategy(
            accum_steps=args.accum,
            accum_exchange="hoisted" if args.hoisted else "gspmd")
        print(f"accumulating {args.accum} microbatches per step"
              + (" (hoisted: one exchange/step)" if args.hoisted else ""))

    trainer = pt.Trainer(prog, opt.AdamW(3e-3, weight_decay=0.01),
                         loss_name="loss", fetch_list=["loss"],
                         mesh=mesh, sharding_rules=rules,
                         strategy=strategy)
    batches = synthetic_batches(args.vocab, args.batch, args.seq)
    trainer.startup(sample_feed=next(batches))
    if args.ckpt and os.path.isdir(args.ckpt):
        io.load_trainer(args.ckpt, trainer)
        print(f"resumed from {args.ckpt} at step {trainer.global_step}")

    first = last = None
    for i in range(args.steps):
        out = trainer.step(next(batches))
        loss = float(out["loss"])
        first = loss if first is None else first
        last = loss
        if i % max(1, args.steps // 10) == 0:
            print(f"step {trainer.global_step:5d}  loss {loss:.4f}")

    if first is not None:
        print(f"loss {first:.4f} -> {last:.4f} over {args.steps} steps")
    if args.ckpt:
        io.save_trainer(args.ckpt, trainer)
        print(f"checkpoint saved to {args.ckpt}")

    if args.generate:
        import dataclasses

        import jax.numpy as jnp
        gen_cfg = dataclasses.replace(
            cfg, max_len=args.seq + args.generate,
            kv_cache_dtype="int8" if args.int8_kv else "compute")
        gen = pt.build(gpt.make_generator(gen_cfg,
                                          max_new_tokens=args.generate))
        prompt = next(batches)["ids"][:2, : args.seq // 2]
        outs, _ = gen.apply(dict(trainer.scope.params), {},
                            jnp.asarray(prompt))
        kv = "int8" if args.int8_kv else "compute-dtype"
        print(f"prompt[0] tail: {prompt[0, -8:].tolist()}")
        print(f"continuation ({kv} KV cache): "
              f"{np.asarray(outs['ids'])[0].tolist()}")
    return last


if __name__ == "__main__":
    main()
