"""Collective inventory + roofline scaling projection for the BASELINE
configs (round-4 verdict #7; reference anchor: the published 4-GPU
scaling tables, benchmark/README.md:70-95 — 3.85x on AlexNet — which
this parallels with the evidence producible without a pod).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/scaling_model.py [--out SCALING.json] [--only a,b]

For each of the five BASELINE configs this builds the sharded train
step on the 8-virtual-device CPU mesh with its representative
parallelism at SMALL probe shapes, runs ``debugger.collective_report``
over the compiled HLO (op counts + payload bytes + ring-formula wire
bytes — the committed collective inventory, pinned by
tests/test_scaling_model.py), then projects scaling efficiency to a
v5e-256 pod with an alpha-beta roofline evaluated at the FULL bench
shapes:

    grad_bytes = full-size trainable params x 4  (jax.eval_shape over
                 the real model's init — no compile, exact counts)
    T_ici  = 2 * grad_bytes * (8-1)/8 / B_ici      (intra-host ring)
    T_dcn  = 2 * grad_bytes * (H-1)/H / B_dcn      (inter-host ring)
    eff    = T_comp / (T_comp + max(0, T_comm - f_overlap * T_comp))

T_comp uses the measured on-chip compute-only MFU where one exists
(BENCH records) and a conservative default otherwise; f_overlap
reflects XLA's latency-hiding of the grad all-reduce behind the
backward pass. Non-dp axes (tp/pp) stay inside a host's ICI domain by
construction (mesh axes ordered with pp/tp innermost), so the DCN hop
only ever carries the dp all-reduce — the layout rule the projection
assumes and the mesh builders enforce.

Assumed hardware budgets (stated, not measured — this repo has one
chip): v5e ICI ~45e9 B/s effective per-direction ring bandwidth per
chip; DCN ~6.25e9 B/s per host (50 Gbps NIC), 8 chips/host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

# -- hardware model (assumptions; see module doc) ---------------------------
PEAK_BF16 = 197e12          # v5e-class chip, bf16
ICI_BW = 45e9               # B/s per-direction ring bandwidth per chip
DCN_BW = 6.25e9             # B/s per host (50 Gbps)
CHIPS_PER_HOST = 8
# measured compute-only MFU where an on-chip BENCH row exists
# (a 2026-07 capture, record since deleted: resnet50 0.271,
# transformer 0.168); conservative
# default for configs never captured on chip
MEASURED_MFU = {"resnet50": 0.271, "transformer": 0.168}
DEFAULT_MFU = 0.30
OVERLAP = 0.5               # fraction of T_comp usable to hide all-reduce


def _param_bytes(prog, feed):
    """Full-size trainable-param bytes via eval_shape (no compile)."""
    params, _ = jax.eval_shape(lambda k: prog.init(k, **feed),
                               jax.random.PRNGKey(0))
    return float(sum(int(np.prod(p.shape)) * p.dtype.itemsize
                     for p in jax.tree.leaves(params)))


def _configs():
    """[(name, probe() -> (trainer, feed), full() -> dict)]. probe
    builds the SMALL sharded step whose compiled HLO supplies the
    collective inventory; full computes the real bench config's
    flops/step/chip and gradient bytes for the roofline."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import flops
    from paddle_tpu.models import bert, deepfm, mnist, resnet, transformer
    from paddle_tpu.parallel import DistStrategy, fsdp, replicated, \
        transformer_tp_rules

    def mnist_probe():
        prog = pt.build(mnist.mlp)
        feed = {"image": np.zeros((8, 784), np.float32),
                "label": np.zeros((8, 1), np.int64)}
        tr = pt.Trainer(prog, opt.SGD(0.01), loss_name="loss",
                        mesh=pt.make_mesh({"dp": 8}),
                        sharding_rules=replicated())
        tr.startup(sample_feed=feed)
        return tr, feed

    def mnist_full():
        prog = pt.build(mnist.mlp)
        feed = {"image": np.zeros((128, 784), np.float32),
                "label": np.zeros((128, 1), np.int64)}
        return {"grad_bytes": _param_bytes(prog, feed), "pure_dp": True,
                "flops": flops.mlp_train_flops(128, (784, 200, 200, 10))}

    def resnet_probe():
        prog = pt.build(resnet.make_model(depth=50, class_num=100,
                                          image_size=64,
                                          data_format="NHWC"))
        feed = {"image": np.zeros((8, 64, 64, 3), np.float32),
                "label": np.zeros((8, 1), np.int64)}
        tr = pt.Trainer(prog, opt.Momentum(0.1, 0.9), loss_name="loss",
                        mesh=pt.make_mesh({"dp": 8}),
                        sharding_rules=replicated())
        tr.startup(sample_feed=feed)
        return tr, feed

    def resnet_full():
        prog = pt.build(resnet.make_model(depth=50, class_num=1000,
                                          image_size=224,
                                          data_format="NHWC"))
        feed = {"image": np.zeros((64, 224, 224, 3), np.float32),
                "label": np.zeros((64, 1), np.int64)}
        return {"grad_bytes": _param_bytes(prog, feed), "pure_dp": True,
                "flops": flops.convnet_train_flops(
                    flops.resnet_fwd_flops(50, 224), 64)}

    def transformer_probe():
        cfg = transformer.base_config(
            src_vocab=64, trg_vocab=64, d_model=32, d_inner=64,
            num_heads=4, num_encoder_layers=4, num_decoder_layers=4,
            dropout=0.0, stacked=True)
        prog = pt.build(transformer.make_model(cfg))
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(3, 64, (8, 12)).astype(np.int32),
                "trg_ids": rng.randint(3, 64, (8, 12)).astype(np.int32),
                "labels": rng.randint(3, 64, (8, 12)).astype(np.int32)}
        tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss",
                        mesh=pt.make_mesh({"dp": 2, "tp": 2, "pp": 2}),
                        sharding_rules=transformer_tp_rules(),
                        strategy=DistStrategy(pp_microbatches=2))
        tr.startup(sample_feed=feed)
        return tr, feed

    def transformer_full():
        cfg = transformer.base_config()
        prog = pt.build(transformer.make_model(cfg))
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(3, 100, (32, 256)).astype(np.int32),
                "trg_ids": rng.randint(3, 100, (32, 256)).astype(np.int32),
                "labels": rng.randint(3, 100, (32, 256)).astype(np.int32)}
        # pod layout dp64 x tp2 x pp2: each dp replica's grad ring
        # carries only its tp/pp shard of the parameters
        return {"grad_bytes": _param_bytes(prog, feed),
                "model_shards": 4,
                "flops": flops.transformer_train_flops(32, 256, cfg)}

    def bert_probe():
        cfg = bert.base_config(vocab_size=128, d_model=32, d_inner=64,
                               num_heads=4, num_layers=2, max_len=64,
                               dropout=0.0)
        prog = pt.build(bert.make_pretrain_model(cfg))
        rng = np.random.RandomState(0)
        feed = {
            "input_ids": rng.randint(0, 128, (8, 16)).astype(np.int32),
            "token_type_ids": rng.randint(0, 2, (8, 16)).astype(np.int32),
            "mlm_positions": rng.randint(0, 16, (8, 4)).astype(np.int32),
            "mlm_labels": rng.randint(0, 128, (8, 4, 1)).astype(np.int64),
            "nsp_label": rng.randint(0, 2, (8, 1)).astype(np.int64),
        }
        tr = pt.Trainer(prog, opt.AdamW(1e-4), loss_name="loss",
                        mesh=pt.make_mesh({"dp": 4, "fsdp": 2}),
                        sharding_rules=fsdp(min_size_to_shard=64))
        tr.startup(sample_feed=feed)
        return tr, feed

    def bert_full():
        cfg = bert.base_config()
        prog = pt.build(bert.make_pretrain_model(cfg))
        rng = np.random.RandomState(0)
        feed = {
            "input_ids": rng.randint(0, cfg.vocab_size, (32, 128)).astype(np.int32),
            "token_type_ids": rng.randint(0, 2, (32, 128)).astype(np.int32),
            "mlm_positions": rng.randint(0, 128, (32, 20)).astype(np.int32),
            "mlm_labels": rng.randint(0, cfg.vocab_size, (32, 20, 1)).astype(np.int64),
            "nsp_label": rng.randint(0, 2, (32, 1)).astype(np.int64),
        }
        return {"grad_bytes": _param_bytes(prog, feed),
                "flops": flops.bert_train_flops(32, 128, 20, cfg)}

    def deepfm_probe():
        prog = pt.build(deepfm.make_model(num_sparse_fields=26,
                                          sparse_feature_dim=50,
                                          embedding_size=8,
                                          hidden_dims=(32, 32)))
        rng = np.random.RandomState(0)
        feed = {"dense": rng.randn(8, 13).astype(np.float32),
                "sparse_ids": rng.randint(0, 50, (8, 26)).astype(np.int32),
                "label": rng.randint(0, 2, (8, 1)).astype(np.float32)}
        tr = pt.Trainer(prog, opt.Adagrad(0.05), loss_name="loss",
                        mesh=pt.make_mesh({"dp": 8}),
                        sharding_rules=replicated())
        tr.startup(sample_feed=feed)
        return tr, feed

    def deepfm_full():
        prog = pt.build(deepfm.make_model())
        rng = np.random.RandomState(0)
        feed = {"dense": rng.randn(2048, 13).astype(np.float32),
                "sparse_ids": rng.randint(0, 1000, (2048, 26)).astype(np.int32),
                "label": rng.randint(0, 2, (2048, 1)).astype(np.float32)}
        return {"grad_bytes": _param_bytes(prog, feed), "pure_dp": True,
                "flops": flops.deepfm_train_flops(2048, 26, 16, 13,
                                                  (400, 400, 400))}

    return [("mnist_mlp", mnist_probe, mnist_full),
            ("resnet50", resnet_probe, resnet_full),
            ("transformer", transformer_probe, transformer_full),
            ("bert", bert_probe, bert_full),
            ("deepfm", deepfm_probe, deepfm_full)]


def project(name, full, n_chips=256):
    mfu = MEASURED_MFU.get(name, DEFAULT_MFU)
    t_comp = full["flops"] / (PEAK_BF16 * mfu)
    # dp all-reduce rides ICI inside a host and DCN across hosts; the
    # cross-host stage moves (almost) the same bytes through the much
    # thinner pipe, so it dominates: model a two-stage hierarchical
    # reduce (ring over ICI per host, then ring over DCN across hosts).
    # Each dp replica's ring carries only its model shard of the grads
    # (grad_bytes / model_shards) under the pp/tp-innermost layout; an
    # fsdp axis does NOT reduce the per-chip bytes (reduce-scatter of
    # grads + all-gather of params moves the same ~2P per chip), so
    # fsdp configs keep model_shards=1.
    n_hosts = max(1, n_chips // CHIPS_PER_HOST)
    p = full["grad_bytes"] / full.get("model_shards", 1)

    def eff_with(p_bytes, compute_scale=1):
        # compute_scale > 1 models more compute per exchange: a larger
        # per-chip batch, or accum_steps under
        # DistStrategy(accum_exchange="hoisted") — the shard_map-local
        # accumulation that exchanges once per optimizer step
        # (tests/test_hoisted_accum.py). The DEFAULT gspmd accumulation
        # does NOT qualify: its all-reduce rides inside the microbatch
        # loop (pinned by tests/test_collective_report.py::
        # test_accum_grad_exchange_is_per_microbatch), which is why the
        # hoisted projection below is emitted only for the pure-dp
        # configs where the hoisted mode applies
        tc = t_comp * compute_scale
        ti = 2 * p_bytes * (CHIPS_PER_HOST - 1) / CHIPS_PER_HOST / ICI_BW
        td = (2 * p_bytes * (n_hosts - 1) / n_hosts / DCN_BW
              if n_hosts > 1 else 0.0)
        return round(tc / (tc + max(0.0, ti + td - OVERLAP * tc)), 4)

    t_ici = 2 * p * (CHIPS_PER_HOST - 1) / CHIPS_PER_HOST / ICI_BW
    t_dcn = (2 * p * (n_hosts - 1) / n_hosts / DCN_BW) if n_hosts > 1 else 0.0
    return {"grad_bytes_mb": round(full["grad_bytes"] / 1e6, 2),
            "model_shards": full.get("model_shards", 1),
            "dp_ring_bytes_mb": round(p / 1e6, 2),
            "flops_per_step_per_chip": full["flops"],
            "t_comp_ms": round(t_comp * 1e3, 3),
            "t_ici_ms": round(t_ici * 1e3, 3),
            "t_dcn_ms": round(t_dcn * 1e3, 3),
            "assumed_mfu": mfu,
            "efficiency_at_256": eff_with(p),
            # implemented counter-measures, projected: int8 ring
            # all-reduce (parallel/quantized_collectives.py) quarters
            # the wire bytes; doubling the per-chip batch (a bench
            # config knob — LAMB/LARS ship for the large-global-batch
            # regime) doubles compute per exchange; they compose
            "efficiency_at_256_int8": eff_with(p / 4),
            "efficiency_at_256_int8_2x_batch": eff_with(p / 4,
                                                        compute_scale=2),
            # pure-dp replicated stateless configs can additionally run
            # DistStrategy(accum_exchange="hoisted"): the shard_map-
            # local accumulation exchanges once per optimizer step
            # (parity- and HLO-structure-tested, tests/
            # test_hoisted_accum.py), making accum_steps=4 a real 4x
            # compute-per-exchange lever
            "efficiency_at_256_int8_hoisted_accum4": (
                eff_with(p / 4, compute_scale=4)
                if full.get("pure_dp") else None)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "SCALING.json"))
    ap.add_argument("--only", default=None)
    ap.add_argument("--project-only", action="store_true",
                    help="recompute roofline projections (cheap eval_shape) "
                         "into existing rows without re-lowering the probes")
    args = ap.parse_args()

    from paddle_tpu import debugger

    out = {"mesh_devices": 8, "assumptions": {
        "peak_bf16_flops": PEAK_BF16, "ici_bw_Bps": ICI_BW,
        "dcn_bw_Bps": DCN_BW, "chips_per_host": CHIPS_PER_HOST,
        "overlap_fraction": OVERLAP, "default_mfu": DEFAULT_MFU,
        "measured_mfu": MEASURED_MFU}, "configs": {}}
    if os.path.exists(args.out):
        try:
            prev = json.load(open(args.out))
            # merge prior rows ONLY under identical assumptions: stale
            # projections must never ship under a constants block they
            # were not computed with
            if prev.get("assumptions") == out["assumptions"]:
                out["configs"].update(prev.get("configs", {}))
            elif args.project_only:
                ap.error("assumptions changed since the committed record; "
                         "--project-only would strand stale probe rows — "
                         "re-run the full probes (no --project-only)")
            else:
                print("[scaling] assumptions changed — regenerating all "
                      "rows (prior rows dropped)")
        except (OSError, json.JSONDecodeError):
            pass
    names = [n for n, _, _ in _configs()]
    only = ([s.strip() for s in args.only.split(",")] if args.only else None)
    if only:
        unknown = set(only) - set(names)
        if unknown:
            ap.error(f"--only names not in the config list {names}: "
                     f"{sorted(unknown)}")
    for name, probe, full in _configs():
        if only and name not in only:
            continue
        if args.project_only:
            row = out["configs"].get(name)
            if not row or "error" in row:
                print(f"[scaling] {name}: no probe row to project onto")
                continue
            row["projection_v5e_256"] = project(name, full())
            _write(out, args.out)
            print(f"[scaling] {name} eff@256 = "
                  f"{row['projection_v5e_256']['efficiency_at_256']} "
                  f"(int8: "
                  f"{row['projection_v5e_256']['efficiency_at_256_int8']}, "
                  f"int8+2x batch: "
                  f"{row['projection_v5e_256']['efficiency_at_256_int8_2x_batch']})")
            continue
        print(f"[scaling] {name}: building + lowering ...", flush=True)
        try:
            tr, feed = probe()
            rep = debugger.collective_report(tr, feed)
            fs = full()
        except Exception as e:  # record the failure, keep going
            out["configs"][name] = {"error": f"{type(e).__name__}: {e}"}
            _write(out, args.out)
            print(f"          -> ERROR {e}")
            continue
        row = {"mesh": rep["mesh"], "collectives": rep["collectives"],
               "probe_payload_mb": rep["total_payload_mb"],
               "probe_wire_mb_per_device": rep["est_wire_mb_per_device"],
               "projection_v5e_256": project(name, fs)}
        out["configs"][name] = row
        _write(out, args.out)
        print(f"          -> {json.dumps(row['collectives'])[:140]}")
        print(f"          -> eff@256 = "
              f"{row['projection_v5e_256']['efficiency_at_256']}")
    print("wrote", args.out)


def _write(out, path):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1)
    os.replace(tmp, path)


if __name__ == "__main__":
    main()
