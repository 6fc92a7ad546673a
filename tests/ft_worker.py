"""Fault-tolerance e2e worker: drains the C++ master task queue while
checkpointing; can be told to crash mid-task (lease held, work lost
since last checkpoint) to exercise lease-timeout requeue + resume.

Run: ft_worker.py <port> <ckpt_dir> <kill_after_tasks|-1> <worker_id>
Prints: RESUMED step=<s> loss=<x> | DONE <shard> step=<s>
        CKPT step=<s> loss=<x>    | EXIT ok
"""

import os
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")  # a test worker: never the chip

import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio, optimizer as opt  # noqa: E402
from paddle_tpu.data.master import MasterClient  # noqa: E402
from paddle_tpu.models import mnist  # noqa: E402


def shard_batches(shard: str, n=2, bs=16):
    seed = int(shard.split("-")[1])
    rng = np.random.RandomState(1000 + seed)
    return [{"image": rng.randn(bs, 784).astype(np.float32),
             "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)}
            for _ in range(n)]


def main():
    port, ckpt_dir, kill_after, worker_id = (
        int(sys.argv[1]), sys.argv[2], int(sys.argv[3]), sys.argv[4])

    probe = {"image": np.random.RandomState(999).randn(16, 784).astype(np.float32),
             "label": np.random.RandomState(999).randint(0, 10, (16, 1)).astype(np.int64)}
    prog = pt.build(mnist.mlp)
    trainer = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss")
    trainer.startup(sample_feed=probe)

    def probe_loss():
        return float(trainer.eval(probe)["loss"])

    # warm up the step/eval compiles BEFORE taking any lease — the first
    # jit compile takes longer than a realistic lease timeout, and a
    # lease must only cover actual work (the Go master's lease assumes
    # task time, not startup time). Runs before the checkpoint load, so
    # restored params/step are untouched.
    trainer.step(trainer._put_feed(shard_batches("shard-0")[0]))
    probe_loss()

    if os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir):
        pio.load_trainer_sharded(ckpt_dir, trainer)
        print(f"RESUMED step={trainer.global_step} loss={probe_loss():.6f}",
              flush=True)

    client = MasterClient(("127.0.0.1", port))
    done_since_start = 0
    idle_deadline = None
    while True:
        t = client.get_task(wait=False)
        if t is None:
            st = client.status()
            if st["todo"] == 0 and st["leased"] == 0:
                break  # queue fully drained
            # leased tasks may still requeue (a peer might have crashed)
            if idle_deadline is None:
                idle_deadline = time.time() + 30
            if time.time() > idle_deadline:
                print("EXIT idle-timeout", flush=True)
                sys.exit(3)
            time.sleep(0.2)
            continue
        idle_deadline = None
        tid, payload = t
        shard = payload.decode()
        for b in shard_batches(shard):
            trainer.step(trainer._put_feed(b))
        if kill_after >= 0 and done_since_start == kill_after:
            # crash mid-task: lease held, steps since last CKPT lost
            os._exit(137)
        client.finish_task(tid)
        done_since_start += 1
        print(f"DONE {shard} step={trainer.global_step}", flush=True)
        pio.save_trainer_sharded(ckpt_dir, trainer, async_save=False)
        print(f"CKPT step={trainer.global_step} loss={probe_loss():.6f}",
              flush=True)
    client.close()
    print("EXIT ok", flush=True)


if __name__ == "__main__":
    main()
