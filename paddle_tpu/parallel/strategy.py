"""Distributed/execution strategy objects.

Analog of ExecutionStrategy/BuildStrategy (pybind.cc:675/:757,
details/build_strategy.h:34) and DistributeTranspilerConfig
(distribute_transpiler.py:127) — the knob surface, as a dataclass.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class DistStrategy:
    # multi_batch_merge_pass analog: microbatch gradient accumulation.
    accum_steps: int = 1
    # how accumulated gradients are exchanged across the data axes:
    # - "gspmd" (default): the model runs under GSPMD inside the
    #   microbatch scan; the partitioner reduces EVERY microbatch's
    #   gradients (it does not hoist the exchange past the accumulator
    #   — pinned by tests/test_collective_report.py), so accumulation
    #   is a memory lever only. Fully general (any sharding rules,
    #   stateful models).
    # - "hoisted": the microbatch loop runs shard_map-LOCAL per data
    #   shard and the summed gradients are pmean'd ONCE per optimizer
    #   step — accum_steps becomes a wire lever (the DCN-scaling
    #   recipe). Requires fully replicated params (no fsdp/tp/pp/sp),
    #   stateless models (no BN running stats), and divisible batches;
    #   dropout masks decorrelate per shard via axis-index rng folds
    #   (same-in-distribution as GSPMD, not bitwise).
    accum_exchange: str = "gspmd"
    # kAllReduce vs kReduce (build_strategy.h:55): 'allreduce' replicates
    # params; 'sharded' (fsdp) shards params+optimizer state.
    reduce_strategy: str = "allreduce"
    # donation / rematerialization knobs (memory_optimize analog).
    # remat flips framework.remat_mode during the Trainer's trace: zoo
    # models' maybe_remat blocks become per-block jax.checkpoint.
    donate_buffers: bool = True
    remat: bool = False
    # what checkpointed blocks KEEP: None/'nothing' = full recompute,
    # 'dots' = save matmul outputs (skip MXU recompute, drop elementwise
    # intermediates), 'dots_no_batch', 'everything', or a
    # jax.checkpoint_policies callable
    remat_policy: Any = None
    # store float optimizer accumulators (Adam moments etc.) in this
    # dtype ('bfloat16' halves optimizer HBM); update math stays f32
    opt_state_dtype: Optional[str] = None
    # loss scaling for mixed precision: a float enables scaling at that
    # initial value; dynamic_loss_scale grows/shrinks it from overflow
    # history (non-finite grads always skip the step when enabled).
    loss_scale: Optional[float] = None
    dynamic_loss_scale: bool = False
    loss_scale_growth_interval: int = 1000
    # debug dump of the compiled HLO (debug_graphviz_path analog).
    dump_hlo_path: Optional[str] = None
    # pipeline parallelism: >0 routes zoo models' stacked block stacks
    # through parallel.pipeline.pipeline_apply with this many
    # microbatches (Trainer enters framework.pipeline_mode when the mesh
    # has a 'pp' axis). Bubble fraction = (pp-1)/(m+pp-1); see
    # parallel.pipeline.bubble_fraction.
    pp_microbatches: int = 0
    # virtual pipeline stages per rank (Megatron interleaved schedule):
    # >1 splits each rank's layer span into this many non-adjacent
    # chunks, shrinking the bubble by the same factor at the cost of
    # proportionally more neighbor-hop activation traffic. Layers must
    # divide by pp·pp_interleave.
    pp_interleave: int = 1
    # sequence/context parallelism: sp-aware zoo models (models/gpt.py)
    # run their attention over the mesh's 'sp' axis. Mutually exclusive
    # with pp_microbatches on the same stack. sp_impl picks the scheme:
    # 'ring' = zigzag ring attention, activations kept in zigzag layout
    # end-to-end (no head-count constraint); 'ulysses' = all-to-all
    # head<->sequence reshard (needs num_heads % sp == 0; full-sequence
    # inner kernel).
    sequence_parallel: bool = False
    sp_impl: str = "ring"
    # quantized gradient exchange (EQuARX lineage, PAPERS.md): "int8" /
    # "int4" replaces the per-step gradient all-reduce with the block-
    # scaled quantized ring (parallel.quantized_collectives) — and, in
    # async PS mode, routes gradient pushes through the block-scaled
    # PUSHQB wire verb. "none" (default) keeps today's exact exchange,
    # bit-identically. The collective path runs the grad exchange
    # shard_map-local over the data axes (same preconditions as
    # accum_exchange="hoisted": fully replicated params, stateless
    # model, divisible batch) so the ring carries int8/int4 on the wire
    # instead of letting GSPMD insert a f32 all-reduce.
    quantized_allreduce: str = "none"
    # elements per f32 abs-max scale block; one outlier only flattens
    # its own block's resolution. Smaller = tighter error, more scale
    # bytes (overhead 4/block_size of the int8 payload).
    quant_block_size: int = 256
    # carry the per-rank quantization error (grad - its wire roundtrip)
    # in the step/scan carry and add it back into the NEXT step's
    # gradient before encoding — error telescopes across the fused
    # K-step program instead of compounding (1-bit SGD / EF-SGD
    # lineage). Residual lives in UNSCALED gradient units and is rolled
    # back on skipped (non-finite) steps.
    error_feedback: bool = True
    # stochastic rounding on the encode path, keyed off the step rng:
    # floor(x/scale*qmax + u), unbiased per element. Applied to the
    # initial quantization and reduce-scatter hops only — all-gather
    # hops stay deterministic, preserving cross-rank bitwise identity.
    quant_stochastic_rounding: bool = False
    # ZeRO-style cross-replica sharded weight update ("Automatic
    # Cross-Replica Sharding of Weight Update in Data-Parallel
    # Training", PAPERS.md): each data-parallel replica owns a 1/N
    # flat shard of params + optimizer state, applies the optimizer
    # update to its shard only, and fresh params are all-gathered at
    # the top of every (fused-scan) step — optimizer HBM drops ~N×.
    # Same preconditions as accum_exchange="hoisted": a mesh with data
    # axes, fully replicated params (no fsdp/tp/pp/sp), stateless
    # models. Composes with accum_exchange, quantized_allreduce,
    # dynamic loss scaling, and remat; checkpoints become shard-aware
    # (per-shard manifest entries, meta.zero_axes) with an explicit
    # gather-then-repartition elastic door for N→M restores. False
    # keeps today's replicated update bit-identically.
    zero_sharding: bool = False
    # async parameter-server mode (listen_and_serv RunAsyncLoop analog):
    # barrier-free grad push / param pull through the C++ pserver
    # (parallel.async_ps) instead of SPMD collectives. Set by
    # DistributeTranspiler(sync_mode=False); consumed by driver code that
    # routes the program to AsyncPSTrainer.
    async_mode: bool = False
