"""MoE transformer as a TRAINING PATH: the GShard-style zoo model trains
through the Trainer on a dp×ep mesh with experts sharded and tokens
all-to-all-dispatched — the model-level realization of parallel/moe.py
(exists ≠ integrated guard, like the pp/sp siblings)."""

import jax
import numpy as np

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.parallel import moe_ep_rules
from paddle_tpu.parallel.sharding import ShardingRules
from paddle_tpu.models import moe_transformer


def _cfg(**kw):
    base = dict(vocab_size=64, max_len=32, d_model=32, d_inner=64,
                d_expert=64, num_heads=4, num_layers=2, num_experts=8,
                top_k=2, moe_every=2, fused_ce=False)
    base.update(kw)
    return moe_transformer.base_config(**base)


def _feed(bs, seq=16, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(3, vocab, (bs, seq)).astype(np.int32)
    labels = np.concatenate([ids[:, 1:], np.full((bs, 1), 2)], axis=1).astype(np.int32)
    return {"ids": ids, "labels": labels}


def test_moe_lm_trains_dense():
    prog = pt.build(moe_transformer.make_model(_cfg()))
    feed = _feed(4)
    tr = pt.Trainer(prog, opt.Adam(1e-2), loss_name="loss",
                    fetch_list=["loss", "ce_loss", "aux_loss"])
    tr.startup(sample_feed=feed)
    first = float(tr.step(tr._put_feed(feed))["loss"])
    for _ in range(10):
        out = tr.step(tr._put_feed(feed))
    assert float(out["loss"]) < first
    assert float(out["aux_loss"]) > 0  # routing actually happened


def test_moe_lm_ep_mesh_parity_with_dense():
    """dp2×ep4 expert-parallel training == dense single-device training
    step for step (aux off, ample capacity → identical routing)."""
    feeds = [_feed(8, seed=i) for i in range(2)]
    kw = dict(aux_weight=0.0, capacity_factor=4.0)

    prog_ref = pt.build(moe_transformer.make_model(_cfg(**kw)))
    tr_ref = pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss")
    tr_ref.startup(sample_feed=feeds[0])
    ref = [float(tr_ref.step(f)["loss"]) for f in feeds]

    mesh = pt.make_mesh({"dp": 2, "ep": 4})
    prog_ep = pt.build(moe_transformer.make_model(_cfg(**kw), mesh=mesh))
    tr_ep = pt.Trainer(
        prog_ep, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
        sharding_rules=ShardingRules(list(moe_ep_rules()), default=None))
    tr_ep.startup(sample_feed=feeds[0])
    got = [float(tr_ep.step(f)["loss"]) for f in feeds]

    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


def test_moe_expert_params_sharded_over_ep():
    mesh = pt.make_mesh({"dp": 2, "ep": 4})
    prog = pt.build(moe_transformer.make_model(_cfg(), mesh=mesh))
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    sharding_rules=ShardingRules(list(moe_ep_rules()),
                                                 default=None))
    tr.startup(sample_feed=_feed(8))
    ew = [k for k in tr.scope.params if k.endswith("expert_w1")]
    assert ew, sorted(tr.scope.params)[:10]
    assert tr.scope.params[ew[0]].sharding.spec[0] == "ep"
