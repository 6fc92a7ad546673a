"""Tensor-parallel matmuls whose exchange runs under the matmul itself.

A Megatron block closes each row-parallel matmul with an all-reduce of
the whole activation, and nothing can run beside it: its only consumer
is the next operation. Here the activation stays sharded over the ``tp``
axis BY BATCH ROWS between matmuls, and the two exchanges a block needs
are cut into the ``n = tp`` chunks a ring has, each sent with
``ppermute`` while the chunk already here is multiplied (Wang et al.,
"Overlap communication with dependent computation via decomposition",
ASPLOS '23):

- :func:`gather_matmul` for a column-parallel projection: the local
  chunk goes round the ring and each chunk is multiplied by the local
  weight columns as it arrives;
- :func:`matmul_scatter` for a row-parallel one: the chunk that belongs
  to the next rank is multiplied first and sent on, the own chunk is
  multiplied while it travels, and what arrives is added.

Under ``jax.grad`` each is the other's transpose (``ppermute``
transposes to the inverse ``ppermute``), so the backward pass gets the
same overlap with its ``dx`` and ``dw`` matmuls. Both run inside a
``shard_map`` in which ``axis_name`` is manual.

Chunks are held in RING ORDER: entry ``k`` of a list is the chunk of
rank ``(me - k) % n``, the order in which they arrive. Nothing between
the two exchanges depends on which row is which (attention, too, works
row by row), so nothing is ever laid back into the batch's own order:
a chunk of rows is a static slice. Chunks of the SEQUENCE, the usual
choice, have to be laid into sequence order for attention at an offset
that depends on the rank, and on the chip those copies cost more than
the waits they were to hide (PERF.md, PR 30).
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp


class BatchSharded(str):
    """The name of a manual mesh axis over which the activation is
    sharded by batch rows between matmuls: what a block's ``tp_axis`` is
    when its projections run through this module, where a plain name
    means a whole activation and a ``psum`` of partial sums."""


def _send_on(x, axis_name: str, n: int):
    return jax.lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def gather_matmul(x, fn: Callable, axis_name: str) -> List[jax.Array]:
    """``fn`` of every rank's chunk of ``x``, in ring order: the own
    chunk is sent on before it is multiplied, so each hop has a matmul
    beside it. ``x`` is this rank's chunk, ``[b/n, ...]``."""
    n = jax.lax.axis_size(axis_name)
    out = []
    for k in range(n):
        coming = _send_on(x, axis_name, n) if k < n - 1 else None
        out.append(fn(x))
        x = coming
    return out


def matmul_scatter(chunks: Sequence[jax.Array], fn: Callable, axis_name: str):
    """This rank's chunk of the sum over ranks of ``fn(chunk)``, from the
    chunks in ring order; ``fn`` gives one chunk's partial sum. The sum
    for rank ``r`` starts at rank ``r + 1`` and goes once round; every
    rank adds its partial as it passes, the own chunk's last."""
    n = jax.lax.axis_size(axis_name)
    total = None
    for t in range(n):
        part = fn(chunks[(t + 1) % n])
        total = part if total is None else part + total
        if t < n - 1:
            total = _send_on(total, axis_name, n)
    return total


def ring_order(whole, axis_name: str):
    """The rows every rank holds whole (a side input of the block), cut
    into the ``n`` chunks of the ranks and put in this rank's ring order."""
    n = jax.lax.axis_size(axis_name)
    rows = whole.shape[0] // n
    me = jax.lax.axis_index(axis_name)
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(
        whole, ((me - k) % n) * rows, rows) for k in range(n)])
