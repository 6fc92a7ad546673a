"""Sequence parallelism as a first-class training path: GPT (decoder-
only causal LM) trains through zigzag ring attention via Trainer +
DistStrategy(sequence_parallel), loss parity vs single device. The sp
sibling of test_pipeline_transformer_e2e (exists ≠ integrated)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.parallel import DistStrategy, transformer_tp_rules
from paddle_tpu.parallel.sharding import ShardingRules
from paddle_tpu.models import gpt


def _cfg(**kw):
    base = dict(vocab_size=128, max_len=64, d_model=32, d_inner=64,
                num_heads=4, num_layers=3, use_flash=False, fused_ce=False)
    base.update(kw)
    return gpt.base_config(**base)


def _feed(bs, seq=32, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(3, vocab, (bs, seq)).astype(np.int32)
    labels = np.concatenate([ids[:, 1:], np.full((bs, 1), 2)], axis=1).astype(np.int32)
    return {"ids": ids, "labels": labels}


def _run_steps(trainer, feeds):
    trainer.startup(sample_feed=feeds[0])
    return [float(trainer.step(f)["loss"]) for f in feeds]


def test_gpt_trains_single_device():
    prog = pt.build(gpt.make_model(_cfg()))
    feed = _feed(4)
    tr = pt.Trainer(prog, opt.Adam(1e-2), loss_name="loss")
    tr.startup(sample_feed=feed)
    first = float(tr.step(tr._put_feed(feed))["loss"])
    for _ in range(10):
        out = tr.step(tr._put_feed(feed))
    assert float(out["loss"]) < first


def test_sp_training_loss_parity():
    """dp2×sp4 ring-attention training == single-device training, step
    for step (zigzag permutation of ids/labels/positions is loss-
    invariant; attention numerics match dense)."""
    feeds = [_feed(8, seed=i) for i in range(3)]

    prog_ref = pt.build(gpt.make_model(_cfg()))
    ref = _run_steps(pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"),
                     feeds)

    mesh = pt.make_mesh({"dp": 2, "sp": 4})
    prog_sp = pt.build(gpt.make_model(_cfg()))
    sp = _run_steps(
        pt.Trainer(prog_sp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=ShardingRules(seq_axis="sp"),
                   strategy=DistStrategy(sequence_parallel=True)),
        feeds)

    np.testing.assert_allclose(sp, ref, atol=2e-4, rtol=2e-4)


def test_sp_with_fused_ce_and_flash():
    """The production long-context config: flash attention inside the
    ring + chunked logits-free CE, still parity with the dense path."""
    feeds = [_feed(4, seed=7)]

    prog_ref = pt.build(gpt.make_model(_cfg(use_flash=True, fused_ce=True)))
    ref = _run_steps(pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"),
                     feeds)

    mesh = pt.make_mesh({"sp": 8})
    prog_sp = pt.build(gpt.make_model(_cfg(use_flash=True, fused_ce=True)))
    sp = _run_steps(
        pt.Trainer(prog_sp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=ShardingRules(seq_axis="sp"),
                   strategy=DistStrategy(sequence_parallel=True)),
        feeds)
    np.testing.assert_allclose(sp, ref, atol=2e-4, rtol=2e-4)


def test_sp_ulysses_loss_parity():
    """sp_impl='ulysses': all-to-all head-sharded attention trains to
    the same losses as single device (natural layout, no permutation)."""
    feeds = [_feed(8, seed=i) for i in range(2)]

    prog_ref = pt.build(gpt.make_model(_cfg()))
    ref = _run_steps(pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"),
                     feeds)

    mesh = pt.make_mesh({"dp": 2, "sp": 4})  # num_heads=4 % sp=4 == 0
    prog_sp = pt.build(gpt.make_model(_cfg()))
    sp = _run_steps(
        pt.Trainer(prog_sp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=ShardingRules(seq_axis="sp"),
                   strategy=DistStrategy(sequence_parallel=True,
                                         sp_impl="ulysses")),
        feeds)
    np.testing.assert_allclose(sp, ref, atol=2e-4, rtol=2e-4)


def test_sp_ulysses_with_flash_parity():
    """ulysses + the pallas flash kernel as the full-sequence inner
    attention (the composition DESIGN.md advertises)."""
    feeds = [_feed(4, seed=11)]

    prog_ref = pt.build(gpt.make_model(_cfg(use_flash=True, fused_ce=True)))
    ref = _run_steps(pt.Trainer(prog_ref, opt.Adam(1e-3), loss_name="loss"),
                     feeds)

    mesh = pt.make_mesh({"dp": 2, "sp": 4})
    prog_sp = pt.build(gpt.make_model(_cfg(use_flash=True, fused_ce=True)))
    sp = _run_steps(
        pt.Trainer(prog_sp, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                   sharding_rules=ShardingRules(seq_axis="sp"),
                   strategy=DistStrategy(sequence_parallel=True,
                                         sp_impl="ulysses")),
        feeds)
    np.testing.assert_allclose(sp, ref, atol=2e-4, rtol=2e-4)


def test_sp_ulysses_seq_divisibility_enforced():
    from paddle_tpu.core.errors import EnforceError

    mesh = pt.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    prog = pt.build(gpt.make_model(_cfg()))
    feed = _feed(4, seq=30)  # 30 % 4 != 0
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    strategy=DistStrategy(sequence_parallel=True,
                                          sp_impl="ulysses"))
    tr.startup(sample_feed=feed)
    with pytest.raises(EnforceError):
        tr.step(tr._put_feed(feed))


def test_sp_bad_impl_rejected():
    from paddle_tpu.core.errors import EnforceError

    mesh = pt.make_mesh({"sp": 8})
    prog = pt.build(gpt.make_model(_cfg()))
    feed = _feed(8)
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    strategy=DistStrategy(sequence_parallel=True,
                                          sp_impl="rings"))
    tr.startup(sample_feed=feed)
    with pytest.raises(EnforceError):
        tr.step(tr._put_feed(feed))


def test_sp_unconsumed_warns():
    """sequence_parallel with a model that never reads the sp context
    must warn (silent no-sp training was the pipeline review finding)."""
    from paddle_tpu.models import mnist

    mesh = pt.make_mesh({"sp": 8})
    prog = pt.build(mnist.mlp)
    feed = {"image": np.random.randn(8, 784).astype(np.float32),
            "label": np.random.randint(0, 10, (8, 1)).astype(np.int64)}
    tr = pt.Trainer(prog, opt.SGD(0.1), loss_name="loss", mesh=mesh,
                    sharding_rules=ShardingRules(),
                    strategy=DistStrategy(sequence_parallel=True))
    tr.startup(sample_feed=feed)
    with pytest.warns(UserWarning, match="never consumed the context"):
        tr.step(tr._put_feed(feed))


def test_sp_seq_divisibility_enforced():
    from paddle_tpu.core.errors import EnforceError

    mesh = pt.make_mesh({"sp": 8})
    prog = pt.build(gpt.make_model(_cfg()))
    feed = _feed(8, seq=24)  # 24 % 16 != 0
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    strategy=DistStrategy(sequence_parallel=True))
    tr.startup(sample_feed=feed)
    with pytest.raises(EnforceError):
        tr.step(tr._put_feed(feed))


def test_gpt_generator_continues_overfit_pattern():
    """Train GPT on a periodic token stream, then the KV-cache
    incremental generator must continue the period from a prompt —
    proves cache indexing/positions and train↔generate param-name
    compatibility in one shot."""
    cfg = _cfg(vocab_size=16, max_len=48, num_layers=2)
    prog = pt.build(gpt.make_model(cfg))
    period = [3, 4, 5, 6]
    seq = np.array([period[i % 4] for i in range(32)], np.int32)
    ids = np.tile(seq, (4, 1))
    labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
    feed = {"ids": ids, "labels": labels.astype(np.int32)}
    tr = pt.Trainer(prog, opt.Adam(1e-2), loss_name="loss")
    tr.startup(sample_feed=feed)
    for _ in range(60):
        out = tr.step(tr._put_feed(feed))
    assert float(out["loss"]) < 0.1, float(out["loss"])

    gen_prog = pt.build(gpt.make_generator(cfg, max_new_tokens=8))
    prompt = ids[:2, :8]  # ends with ...3,4,5,6 -> expect 3,4,5,6,3,4,5,6
    outs, _ = gen_prog.apply(dict(tr.scope.params), {},
                             jnp.asarray(prompt))
    got = np.asarray(outs["ids"])[0].tolist()
    expect = [period[i % 4] for i in range(8)]
    assert got == expect, (got, expect)

    # beam path: per-layer cache lists obey beam_search's [B*beam, ...]
    # state contract, so lane reordering reaches the KV caches — the top
    # beam of the overfit model must equal the greedy continuation
    beam_prog = pt.build(gpt.make_generator(cfg, max_new_tokens=8,
                                            beam_size=2))
    bouts, _ = beam_prog.apply(dict(tr.scope.params), {},
                               jnp.asarray(prompt))
    assert np.asarray(bouts["ids"]).shape == (2, 2, 8)
    assert np.asarray(bouts["ids"])[0, 0].tolist() == expect


def test_gpt_generator_exports_to_aot_predictor(tmp_path):
    """The generation program exports through save_inference_model
    (StableHLO) and serves via the AOT Predictor — the decoder-only
    serving story end-to-end (api_impl.cc Run analog for LMs)."""
    from paddle_tpu import io as pio

    cfg = _cfg(num_layers=2)
    prog = pt.build(gpt.make_generator(cfg, max_new_tokens=8))
    prompt = np.random.RandomState(0).randint(3, 128, (2, 8)).astype(np.int32)
    params, state = prog.init(jax.random.PRNGKey(0), prompt)
    direct, _ = prog.apply(params, state, jnp.asarray(prompt))

    pio.save_inference_model(str(tmp_path / "g"), prog, params, state,
                             {"prompt_ids": prompt})
    pred = pio.load_inference_model(str(tmp_path / "g"))
    assert type(pred._compiled).__name__ == "Compiled"  # AOT, no retrace
    served = pred.run({"prompt_ids": prompt})
    np.testing.assert_array_equal(np.asarray(served["ids"]),
                                  np.asarray(direct["ids"]))


def test_gpt_generator_param_names_subset_of_train():
    cfg = _cfg(num_layers=2)
    train_params, _ = pt.build(gpt.make_model(cfg)).init(
        jax.random.PRNGKey(0), **_feed(2))
    gen_params, _ = pt.build(gpt.make_generator(cfg, max_new_tokens=4)).init(
        jax.random.PRNGKey(0), np.zeros((2, 8), np.int32))
    assert set(gen_params) == set(train_params), (
        set(gen_params) ^ set(train_params))


def test_sp_and_pp_mutually_exclusive():
    from paddle_tpu.core.errors import EnforceError

    mesh = pt.make_mesh({"sp": 2, "pp": 4})
    prog = pt.build(gpt.make_model(_cfg(num_layers=4)))
    feed = _feed(8)
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    sharding_rules=transformer_tp_rules(),
                    strategy=DistStrategy(sequence_parallel=True,
                                          pp_microbatches=2))
    tr.startup(sample_feed=feed)
    with pytest.raises(EnforceError):
        tr.step(tr._put_feed(feed))
