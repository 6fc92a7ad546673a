"""Device mesh construction.

The TPU-native replacement for the reference's device-list + NCCL
communicator plumbing (parallel_executor.cc:94-107 NCCLContextMap,
nccl_helper.h:81): a named ``jax.sharding.Mesh`` over which all
parallelism is expressed as sharding annotations. Axis names:

- ``dp``   — data parallel (allreduce-mode analog, build_strategy.h:55 kAllReduce)
- ``fsdp`` — data parallel with sharded params/optimizer state
             (reduce-mode + pserver param-slicing analog — the ZeRO-ish
             capability of distribute_transpiler.py:81 slice_variable)
- ``tp``   — tensor parallel (gap-fill per SURVEY §2.2: absent in reference)
- ``sp``   — sequence/context parallel (ring attention; gap-fill)
- ``pp``   — pipeline stages (gap-fill)
- ``ep``   — expert / embedding-shard parallel (distributed-lookup-table
             analog, distribute_transpiler.py:1100)

Multi-host: ``initialize()`` wraps jax.distributed.initialize — the
gen_nccl_id_op.cc:31 bootstrap analog (coordinator address instead of
broadcasting an ncclUniqueId over gRPC).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DP, FSDP, TP, SP, PP, EP = "dp", "fsdp", "tp", "sp", "pp", "ep"
DATA_AXES = (DP, FSDP)  # axes the batch dimension is sharded over


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Create a named mesh. ``axes`` maps axis name → size; a -1 size is
    inferred from the device count. Default: all devices on ``dp``.

    Axis order follows the dict order; put the fastest-varying
    (innermost, highest-bandwidth ICI) axis last — conventionally ``tp``
    — so tensor-parallel collectives ride nearest-neighbor links.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {DP: n}
    axes = dict(axes)
    unknown = [k for k, v in axes.items() if v == -1]
    if unknown:
        known = int(np.prod([v for v in axes.values() if v != -1]))
        if n % known:
            raise ValueError(f"cannot infer axis {unknown[0]}: {n} devices not divisible by {known}")
        axes[unknown[0]] = n // known
    total = int(np.prod(list(axes.values())))
    if total != n:
        raise ValueError(f"mesh axes {axes} need {total} devices, have {n}")
    arr = np.asarray(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def pvary(x, axis_names):
    """Mark ``x`` as device-varying over ``axis_names`` inside shard_map
    (vma bookkeeping for mixing replicated operands with sharded ones)."""
    return jax.lax.pcast(x, axis_names, to="varying")


def dividing_axes(mesh: Mesh, n: int, names: Sequence[str]) -> tuple:
    """Those of ``names`` that are mesh axes larger than 1, if their
    product divides ``n``; else none: a dim the axes do not divide stays
    whole on every shard, as GSPMD itself would leave it."""
    names = tuple(a for a in names
                  if a in mesh.axis_names and mesh.shape[a] > 1)
    return names if n % math.prod(mesh.shape[a] for a in names) == 0 else ()


def data_axis_names(mesh: Mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in DATA_AXES)


def data_parallel_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axis_names(mesh)] or [1]))


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap (gen_nccl_id / jax.distributed.initialize
    analog). No-op when args are absent and env vars are unset."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    jax.distributed.initialize(**kwargs)
