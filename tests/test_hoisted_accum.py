"""accum_exchange="hoisted": shard_map-local gradient accumulation
with ONE pmean per optimizer step, which makes accum_steps a wire lever
(the default GSPMD path reduces every microbatch, pinned by
test_collective_report.test_accum_grad_exchange_is_per_microbatch).
"""

import re

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import debugger, optimizer as opt
from paddle_tpu.core.errors import EnforceError
from paddle_tpu.debugger import _parse_hlo_collectives
from paddle_tpu.models import transformer
from paddle_tpu.parallel import DistStrategy


def _feed(bs, seq=16, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    return {"src_ids": rng.randint(3, vocab, (bs, seq)).astype(np.int32),
            "trg_ids": rng.randint(3, vocab, (bs, seq)).astype(np.int32),
            "labels": rng.randint(3, vocab, (bs, seq)).astype(np.int32)}


def _trainer(strategy, mesh=None, rules=None, fetch_list=("loss",)):
    cfg = transformer.base_config(src_vocab=64, trg_vocab=64, d_model=32,
                                  d_inner=64, num_heads=4,
                                  num_encoder_layers=2, num_decoder_layers=2,
                                  dropout=0.0)
    prog = pt.build(transformer.make_model(cfg))
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    sharding_rules=rules, strategy=strategy,
                    fetch_list=list(fetch_list) if fetch_list else None)
    tr.startup(sample_feed=_feed(16))
    return tr


def test_hoisted_accum_matches_gspmd_and_single_device():
    """Same seed, dropout 0: hoisted accumulation must reproduce the
    GSPMD accumulation path and plain single-device accumulation, step
    for step (pmean of per-shard grad sums == global mean grad)."""
    feeds = [_feed(16, seed=i) for i in range(3)]

    def run(strategy, mesh=None, rules=None):
        tr = _trainer(strategy, mesh=mesh, rules=rules)
        return [float(tr.step(f)["loss"]) for f in feeds]

    ref = run(DistStrategy(accum_steps=2))
    mesh = pt.make_mesh({"dp": 8})
    gspmd = run(DistStrategy(accum_steps=2), mesh, pt.parallel.replicated())
    hoisted = run(DistStrategy(accum_steps=2, accum_exchange="hoisted"),
                  mesh, pt.parallel.replicated())
    np.testing.assert_allclose(gspmd, ref, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(hoisted, ref, atol=2e-4, rtol=2e-4)


def test_hoisted_accum_has_no_in_loop_grad_exchange():
    """The point of the mode: grad-order all-reduce bytes inside while
    bodies drop to ~nothing (vs the GSPMD path where they are the full
    param bytes — see the companion pin in test_collective_report)."""
    mesh = pt.make_mesh({"dp": 8})
    tr = _trainer(DistStrategy(accum_steps=4, accum_exchange="hoisted"),
                  mesh, pt.parallel.replicated())
    feed = _feed(32)  # accum 4 x dp 8 shards
    hlo = debugger._lower_step(tr, feed).compile().as_text()
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    in_body = 0.0
    for block in re.split(r"\n(?=[%\w].*\{)", hlo):
        name = re.match(r"%?([\w.\-]+)", block.split("\n", 1)[0].lstrip())
        if name and name.group(1) in bodies:
            in_body += sum(p for kind, p, _ in
                           _parse_hlo_collectives(block,
                                                  fallback_group_size=8)
                           if kind == "all-reduce")
    param_bytes = sum(v.size * 4 for v in jax.tree.leaves(tr.scope.params))
    assert in_body < 0.05 * param_bytes, (
        f"{in_body:.0f}B of all-reduce inside loop bodies — the hoisted "
        "mode is not hoisting")
    # and the exchange still exists somewhere (once, outside the loop)
    total = sum(p for kind, p, _ in
                _parse_hlo_collectives(hlo, fallback_group_size=8)
                if kind == "all-reduce")
    assert total > 0.5 * param_bytes, "grad exchange disappeared entirely"


def test_hoisted_accum_preconditions_enforced():
    mesh = pt.make_mesh({"dp": 4, "fsdp": 2})
    with pytest.raises(EnforceError, match="fully replicated"):
        _trainer(DistStrategy(accum_steps=2, accum_exchange="hoisted"),
                 mesh, pt.parallel.fsdp(min_size_to_shard=64))
    with pytest.raises(EnforceError, match="needs a mesh"):
        _trainer(DistStrategy(accum_steps=2, accum_exchange="hoisted"))
    with pytest.raises(EnforceError, match="gspmd.hoisted"):
        _trainer(DistStrategy(accum_steps=2, accum_exchange="typo"),
                 pt.make_mesh({"dp": 8}), pt.parallel.replicated())
    # the knob must never be a silent no-op (typo'd mode or hoisted
    # without an accumulation loop fail even at accum_steps=1)
    with pytest.raises(EnforceError, match="gspmd.hoisted"):
        _trainer(DistStrategy(accum_exchange="hoist"),
                 pt.make_mesh({"dp": 8}), pt.parallel.replicated())
    with pytest.raises(EnforceError, match="no loop to hoist"):
        _trainer(DistStrategy(accum_exchange="hoisted"),
                 pt.make_mesh({"dp": 8}), pt.parallel.replicated())
    # per-sample / integer outputs cannot be replicated across shards:
    # without fetch_list pruning, the logits leaf fails loudly
    with pytest.raises(EnforceError, match="float scalar outputs"):
        tr = _trainer(DistStrategy(accum_steps=2,
                                   accum_exchange="hoisted"),
                      pt.make_mesh({"dp": 8}), pt.parallel.replicated(),
                      fetch_list=None)
        tr.step(_feed(16))


def test_hoisted_accum_composes_with_loss_scaling():
    """bf16 AMP + dynamic loss scaling over the hoisted path: the
    scaled loss is computed inside the shard_map microbatch loop (ls
    enters via closure), grads unscale outside, and the overflow-skip
    machinery sees the pmean'd grads — training stays finite and the
    scale is reported."""
    feeds = [_feed(16, seed=i) for i in range(4)]
    mesh = pt.make_mesh({"dp": 8})
    with pt.amp_guard("bfloat16"):
        tr = _trainer(DistStrategy(accum_steps=2,
                                   accum_exchange="hoisted",
                                   dynamic_loss_scale=True),
                      mesh, pt.parallel.replicated())
        losses = [float(tr.step(f)["loss"]) for f in feeds]
    assert all(np.isfinite(l) for l in losses), losses
    out = tr.step(feeds[0])
    assert "loss_scale" in out and float(out["loss_scale"]) > 0
