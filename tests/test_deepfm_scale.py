"""DeepFM / sharded-embedding vocab-at-scale (VERDICT r2 #6): the
distributed-lookup-table workload (distribute_transpiler.py:1100-1339)
at multi-million-row vocab — correctness of sharded lookup + row-wise
update at scale, and the memory story (updates touch only the gathered
rows; the table never densifies a gradient)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import sparse


VOCAB = 1_048_576  # 2^20 rows per field-group
DIM = 16


def test_sharded_lookup_at_1m_vocab_matches_dense():
    """dp×ep sharded lookup over a ~1M-row table == dense gather."""
    mesh = pt.make_mesh({"dp": 2, "ep": 4})
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(VOCAB, DIM).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, VOCAB, (8, 26)).astype(np.int32))
    got = sparse.sharded_embedding_lookup(table, ids, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(table[ids]),
                               atol=1e-6)


def test_rowwise_update_touches_only_gathered_rows_at_scale():
    """Row-wise lazy-adam over a 1M-row table: only the rows in the
    batch move; the rest are bit-identical (the pserver row-update
    semantics, go/pserver + _create_table_optimize_block)."""
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.randn(VOCAB, DIM).astype(np.float32))
    m1 = jnp.zeros_like(table)
    m2 = jnp.zeros_like(table)
    ids = jnp.asarray(rng.randint(0, VOCAB, (256,)).astype(np.int32))
    grad_out = jnp.asarray(rng.randn(256, DIM).astype(np.float32))

    sr = sparse.lookup_rowwise_grad(ids, grad_out, VOCAB)
    new_table, m1n, m2n = sparse.apply_adam_lazy(table, m1, m2, sr, 0.01, 1)

    touched = np.unique(np.asarray(ids))
    untouched = np.setdiff1d(np.arange(0, VOCAB, 4099), touched)  # sample
    np.testing.assert_array_equal(np.asarray(new_table[untouched]),
                                  np.asarray(table[untouched]))
    assert not np.allclose(np.asarray(new_table[touched]),
                           np.asarray(table[touched]))
    # optimizer state stays zero off the touched rows (lazy semantics)
    assert float(jnp.abs(m1n[untouched]).max()) == 0.0


def test_deepfm_model_trains_at_1m_rows_per_field():
    """The zoo DeepFM end-to-end at 26×40k ≈ 1M embedding rows on the
    default device: loss decreases over a few steps (the single-chip leg
    of the bench's 10M-row config, kept small enough for the CPU test
    tier)."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models import deepfm

    fields, vocab_per_field = 26, 40_000
    model = pt.build(deepfm.make_model(
        num_sparse_fields=fields, sparse_feature_dim=vocab_per_field,
        embedding_size=8, num_dense=13, hidden_dims=(64, 64)))
    rng = np.random.RandomState(2)
    feed = {"dense": rng.randn(256, 13).astype(np.float32),
            "sparse_ids": rng.randint(0, vocab_per_field, (256, 26)).astype(np.int32),
            "label": rng.randint(0, 2, (256, 1)).astype(np.int64)}
    tr = pt.Trainer(model, opt.Adagrad(0.05), loss_name="loss")
    tr.startup(sample_feed=feed)
    first = float(tr.step(tr._put_feed(feed))["loss"])
    for _ in range(10):
        out = tr.step(tr._put_feed(feed))
    assert float(out["loss"]) < first, (first, float(out["loss"]))
