"""Block-scaled quantized gradient exchange on the Trainer hot path
(DistStrategy.quantized_allreduce): train-equivalence vs the fp32
pmean, the error-feedback residual contract across step()/run_steps,
the collective-bytes attribution the acceptance gate reads, and the
profile-driven ``sharding:unquantized-exchange`` advisory."""

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import analysis, optimizer as opt
from paddle_tpu.analysis.report import LintReport
from paddle_tpu.core.errors import EnforceError
from paddle_tpu.data.feeder import stack_batches
from paddle_tpu.models import mnist
from paddle_tpu.parallel import DistStrategy


def _feed(bs=32, seed=0):
    rng = np.random.RandomState(seed)
    return {"image": rng.randn(bs, 784).astype(np.float32),
            "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)}


def _trainer(strategy=None, devices=2, **quant):
    if quant:
        strategy = DistStrategy(**quant)
    mesh = pt.make_mesh({"dp": devices}, devices=jax.devices()[:devices])
    tr = pt.Trainer(pt.build(mnist.mlp), opt.Adam(1e-3), loss_name="loss",
                    fetch_list=["loss"], mesh=mesh,
                    sharding_rules=pt.parallel.replicated(),
                    strategy=strategy)
    tr.startup(sample_feed=_feed())
    return tr


def _params(tr):
    return {k: np.asarray(v) for k, v in tr.scope.params.items()}


# --------------------------------------------------------------------------
# default tier: the acceptance pins that must gate every run
# --------------------------------------------------------------------------


def test_collective_bytes_attribution_meets_gate():
    """The ISSUE acceptance: int8 bytes-on-wire drop >= 3.5x vs fp32,
    as reported by the trainer's OWN collective-bytes attribution (the
    same numbers bench and profile_report surface). Startup-only — no
    step compile is paid here."""
    tr = _trainer(quantized_allreduce="int8")
    c = tr.collective_bytes
    assert c["mode"] == "int8" and c["axes"] == ("dp",)
    assert c["ranks"] == {"dp": 2}
    n = sum(int(np.prod(v.shape)) for v in tr.scope.params.values())
    assert c["grad_elems"] == n
    assert c["reduction"] >= 3.5, c
    assert c["wire_bytes_per_step"] * 3.5 <= c["fp32_bytes_per_step"]
    # the "none" entry is still present (reduction 1.0) for diffing
    t0 = _trainer(quantized_allreduce="none")
    assert t0.collective_bytes["mode"] == "none"
    assert t0.collective_bytes["reduction"] == 1.0
    # off-mesh: no entry
    t1 = pt.Trainer(pt.build(mnist.mlp), opt.Adam(1e-3), loss_name="loss")
    t1.startup(sample_feed=_feed())
    assert t1.collective_bytes is None


def test_none_mode_is_bitwise_identical_to_default():
    """quantized_allreduce="none" must be a no-op: same compiled path,
    bit-for-bit the same params as a strategy-less trainer after real
    optimizer steps (the ISSUE's "bit-identical to today" pin)."""
    feeds = [_feed(seed=i) for i in range(3)]
    a = _trainer(strategy=None)
    b = _trainer(quantized_allreduce="none")
    for f in feeds:
        la, lb = float(a.step(f)["loss"]), float(b.step(f)["loss"])
        assert la == lb, (la, lb)
    pa, pb = _params(a), _params(b)
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_array_equal(pa[k], pb[k])


def test_int8_smoke_trains_and_threads_residual():
    """Fast default-run smoke (the int4 sweep rides the slow tier):
    an int8+EF trainer takes real steps, keeps losses finite and
    decreasing-ish, populates the error-feedback residual, and the
    profile grows the collective line."""
    tr = _trainer(quantized_allreduce="int8")
    assert tr._quant_ef and tr.scope.quant_resid is not None
    # residual starts at zero, becomes nonzero once quantization bites
    assert all(not np.asarray(v).any()
               for v in tr.scope.quant_resid.values())
    losses = [float(tr.step(_feed(seed=i))["loss"]) for i in range(3)]
    assert all(np.isfinite(losses)), losses
    assert any(np.asarray(v).any() for v in tr.scope.quant_resid.values())
    # residual leaves stay sharded [dshard, *param.shape]
    for k, v in tr.scope.quant_resid.items():
        assert v.shape == (2,) + tuple(tr.scope.params[k].shape)
    prof = tr.profile_report()
    assert prof["collective"]["mode"] == "int8"
    assert prof["collective"]["reduction"] >= 3.5


def test_quantized_preconditions_enforced():
    with pytest.raises(EnforceError, match="none|int8|int4"):
        _trainer(quantized_allreduce="fp8")
    with pytest.raises(EnforceError, match="needs a mesh"):
        tr = pt.Trainer(pt.build(mnist.mlp), opt.Adam(1e-3),
                        loss_name="loss",
                        strategy=DistStrategy(quantized_allreduce="int8"))
        tr.startup(sample_feed=_feed())
    with pytest.raises(EnforceError, match="int4.*even|even.*block"):
        _trainer(quantized_allreduce="int4", quant_block_size=33)


def test_unquantized_exchange_advisory_needs_profile_evidence():
    """The sharding:unquantized-exchange lint is evidence-gated: config
    alone never fires it; a link-bound profile on a multi-shard data
    mesh with the knob off does."""
    mesh = pt.make_mesh({"dp": 8})
    params = {"w": np.zeros((64, 64), np.float32)}
    fire = LintReport("t")
    analysis.rules.check_quantized_exchange(
        DistStrategy(), mesh, params, fire,
        profile={"bottleneck": "h2d_s"})
    (f,) = fire.by_code("sharding:unquantized-exchange")
    assert f.severity == "info" and f.data["data_shards"] == 8
    assert f.data["per_step_bytes"] == pytest.approx(
        2 * 7 / 8 * 64 * 64 * 4)
    # link_bound flag is an equivalent trigger
    fire2 = LintReport("t")
    analysis.rules.check_quantized_exchange(
        DistStrategy(), mesh, params, fire2, profile={"link_bound": True})
    assert fire2.by_code("sharding:unquantized-exchange")
    # no profile / compute-bound profile / knob already on: silent
    for strat, prof in ((DistStrategy(), None),
                        (DistStrategy(), {"bottleneck": "compute"}),
                        (DistStrategy(quantized_allreduce="int8"),
                         {"bottleneck": "h2d_s"})):
        rep = LintReport("t")
        analysis.rules.check_quantized_exchange(strat, mesh, params, rep,
                                                profile=prof)
        assert not rep.findings, (strat.quantized_allreduce, prof)


# --------------------------------------------------------------------------
# slow tier: train-equivalence tolerances and the fused-K matrix
# --------------------------------------------------------------------------


def test_int8_ef_losses_track_fp32():
    """The pinned train-equivalence tolerance: int8 block-scaled
    exchange with error feedback stays within 5e-3 of the fp32 loss
    curve over real optimizer steps (same seed, same feeds)."""
    feeds = [_feed(seed=i) for i in range(6)]
    ref = _trainer(strategy=None)
    q = _trainer(quantized_allreduce="int8")
    lr = [float(ref.step(f)["loss"]) for f in feeds]
    lq = [float(q.step(f)["loss"]) for f in feeds]
    np.testing.assert_allclose(lq, lr, atol=5e-3, rtol=0)


def test_int4_ef_losses_track_fp32():
    """int4 is coarse; error feedback is what keeps the curve attached.
    Wider tolerance, same contract."""
    feeds = [_feed(seed=i) for i in range(6)]
    ref = _trainer(strategy=None)
    q = _trainer(quantized_allreduce="int4")
    lr = [float(ref.step(f)["loss"]) for f in feeds]
    lq = [float(q.step(f)["loss"]) for f in feeds]
    np.testing.assert_allclose(lq, lr, atol=5e-2, rtol=0)


@pytest.mark.parametrize("k", [2, 4])
def test_fused_k_matches_sequential_with_residual_carry(k):
    """run_steps(k) threads the error-feedback residual through the
    scan carry: K fused int8+EF steps must reproduce K sequential
    step() calls bit-for-bit (params AND residual)."""
    feeds = [_feed(seed=i) for i in range(k)]
    seq = _trainer(quantized_allreduce="int8")
    fused = _trainer(quantized_allreduce="int8")
    seq_losses = [float(seq.step(f)["loss"]) for f in feeds]
    out = fused.run_steps(fused._put_feed(stack_batches(feeds),
                                          stacked=True), k=k)
    np.testing.assert_array_equal(
        np.asarray(out["loss"]).reshape(-1), np.asarray(seq_losses))
    ps, pf = _params(seq), _params(fused)
    for name in ps:
        np.testing.assert_array_equal(ps[name], pf[name])
    for name in seq.scope.quant_resid:
        np.testing.assert_array_equal(
            np.asarray(seq.scope.quant_resid[name]),
            np.asarray(fused.scope.quant_resid[name]))


def test_int4_sweep_block_sizes():
    """int4 multi-block-size sweep: every configuration trains with
    finite losses and honors its own bytes attribution."""
    for block in (64, 256):
        tr = _trainer(quantized_allreduce="int4", quant_block_size=block)
        losses = [float(tr.step(_feed(seed=i))["loss"]) for i in range(2)]
        assert all(np.isfinite(losses)), (block, losses)
        assert tr.collective_bytes["block_size"] == block
        assert tr.collective_bytes["reduction"] > 5.0


def test_check_trainer_clean_on_quantized_ef_trainer():
    """The static analyzer must trace the 7-arg EF step (quant_resid
    rides the signature) without findings on a healthy config."""
    tr = _trainer(quantized_allreduce="int8")
    rep = analysis.check_trainer(tr, _feed())
    assert rep.ok("warning"), [f.code for f in rep.findings]
