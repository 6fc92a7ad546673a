"""paddle_tpu.analysis — the jaxpr-level static program checker.

Covers every rule family with a program that violates it and one that
doesn't, the Trainer.startup(lint=...) integration levels, and the
report/collector machinery (sharding._warn_drop routing)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import shard_map as _sm
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import analysis, optimizer as opt
from paddle_tpu import layers as L
from paddle_tpu.analysis import LintError, LintReport, LintWarning
from paddle_tpu.core.errors import EnforceError
from paddle_tpu.framework import create_parameter
from paddle_tpu.parallel import DistStrategy, sharding


def _shard_map(fn, mesh, in_specs, out_specs):
    try:
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    except (AttributeError, TypeError):
        return _sm.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_rep=False)


@pytest.fixture
def dp_mesh():
    return pt.make_mesh({"dp": 8})


# --------------------------------------------------------------------------
# 1. collective placement — the unhoisted-accum regression pair
# --------------------------------------------------------------------------


def _unhoisted_program(mesh):
    """psum INSIDE the microbatch scan: the hazard class of the
    per-microbatch gradient exchange
    (test_collective_report.test_accum_grad_exchange_is_per_microbatch)."""
    def fn(x):
        w = create_parameter((4, 4), name="w")

        def body(c, t):
            g = jnp.matmul(t, w)
            g = _shard_map(lambda q: jax.lax.psum(q, "dp"),
                           mesh, P(), P())(g)
            return c + g.sum(), ()

        out, _ = jax.lax.scan(body, jnp.float32(0.0), x.reshape(4, -1, 4))
        return {"loss": out}
    return pt.build(fn, name="unhoisted")


def _hoisted_program(mesh):
    """Same compute, exchange hoisted: ONE psum after the scan."""
    def fn(x):
        w = create_parameter((4, 4), name="w")

        def body(c, t):
            return c + jnp.matmul(t, w).sum(), ()

        out, _ = jax.lax.scan(body, jnp.float32(0.0), x.reshape(4, -1, 4))
        out = _shard_map(lambda q: jax.lax.psum(q, "dp"),
                         mesh, P(), P())(out)
        return {"loss": out}
    return pt.build(fn, name="hoisted")


def test_unhoisted_flags_collective_in_scan_hoisted_clean(dp_mesh):
    feed = {"x": np.random.rand(8, 4).astype(np.float32)}
    bad = analysis.check(_unhoisted_program(dp_mesh), feed, mesh=dp_mesh)
    assert "collective:in-scan" in bad.codes()
    f = bad.by_code("collective:in-scan")[0]
    assert f.severity == "warning"
    assert f.data["trips"] == 4          # per-step multiplier from scan length
    assert "scan" in f.data["path"]
    good = analysis.check(_hoisted_program(dp_mesh), feed, mesh=dp_mesh)
    assert "collective:in-scan" not in good.codes()
    assert good.ok("warning")


def test_ppermute_in_scan_is_info_not_warning(dp_mesh):
    """Neighbor permutes inside loops are the deliberate structure of
    ring/pipeline schedules — inventoried, not warned."""
    def fn(x):
        def inner(xs):
            def body(c, _):
                c = jax.lax.ppermute(c, "dp",
                                     [(i, (i + 1) % 8) for i in range(8)])
                return c, ()
            out, _ = jax.lax.scan(body, xs, None, length=3)
            return out
        return {"loss": _shard_map(inner, dp_mesh, P("dp"), P("dp"))(x).sum()}

    rep = analysis.check(pt.build(fn), {"x": np.ones((8, 4), np.float32)},
                         mesh=dp_mesh)
    assert "collective:permute-in-scan" in rep.codes()
    assert "collective:in-scan" not in rep.codes()
    assert rep.ok("warning")


def test_microbatch_exchange_config_rule(dp_mesh):
    rep = LintReport("t")
    params = {"w": jnp.zeros((64, 64))}
    analysis.rules.check_accum_exchange(
        DistStrategy(accum_steps=4), dp_mesh, params, rep)
    (f,) = rep.by_code("collective:microbatch-exchange")
    assert f.data["accum_steps"] == 4 and f.data["data_shards"] == 8
    assert f.data["per_step_bytes"] == pytest.approx(
        4 * 2 * 7 / 8 * 64 * 64 * 4)
    # hoisted mode: nothing to flag
    rep2 = LintReport("t")
    analysis.rules.check_accum_exchange(
        DistStrategy(accum_steps=4, accum_exchange="hoisted"), dp_mesh,
        params, rep2)
    assert not rep2.findings


# --------------------------------------------------------------------------
# 2. dtype flow
# --------------------------------------------------------------------------


def test_amp_f32_matmul_flagged_only_for_uncast_layers():
    def uncast(x):
        w = create_parameter((8, 8), name="w")
        return {"loss": jnp.matmul(x, w).sum()}      # bypasses cast_compute

    def cast(x):
        return {"loss": L.fc(x, 8).sum()}            # cast_compute inside

    feed = {"x": np.ones((2, 8), np.float32)}
    bad = analysis.check(pt.build(uncast), feed, amp="bfloat16")
    assert "dtype:amp-f32-matmul" in bad.codes()
    good = analysis.check(pt.build(cast), feed, amp="bfloat16")
    assert "dtype:amp-f32-matmul" not in good.codes()
    # without amp there is nothing to enforce
    plain = analysis.check(pt.build(uncast), feed)
    assert "dtype:amp-f32-matmul" not in plain.codes()


def test_cast_roundtrip_flagged():
    def fn(x):
        y = x.astype(jnp.bfloat16).astype(jnp.float32)  # no-op pair
        return {"loss": y.sum()}

    rep = analysis.check(pt.build(fn), {"x": np.ones((4,), np.float32)})
    assert "dtype:cast-roundtrip" in rep.codes()
    assert rep.ok("warning")  # info severity


def test_f64_feed_flagged():
    def fn(x):
        return {"loss": x.sum()}

    rep = analysis.check(pt.build(fn), {"x": np.ones((4,), np.float64)})
    assert "dtype:f64-leak" in rep.codes()


def test_amp_lint_runs_on_train_path():
    """check_trainer(amp=...) re-traces the STEP under the amp compute
    dtype, so dtype-flow findings that only exist on the train path —
    here an uncast f32 aux head gated on in_training() — are caught
    even though the forward program (training=False trace) hides them."""
    from paddle_tpu.framework import create_parameter, in_training

    def model(x):
        h = L.fc(x, 8)
        w = create_parameter((8, 8), name="aux_w")
        loss = h.sum() + (w * 0.0).sum()
        if in_training():   # train-only branch bypassing cast_compute
            loss = loss + jnp.matmul(h.astype(jnp.float32), w).sum()
        return {"loss": loss}

    feed = {"x": np.ones((2, 8), np.float32)}
    prog = pt.build(model)
    # forward-only lint cannot see the branch
    fwd = analysis.check(prog, feed, amp="bfloat16")
    assert "dtype:amp-f32-matmul" not in fwd.codes()
    tr = pt.Trainer(prog, opt.SGD(0.1), loss_name="loss")
    tr.startup(sample_feed=feed)
    rep = analysis.check_trainer(tr, feed, amp="bfloat16")
    assert "dtype:amp-f32-matmul" in rep.codes()
    # without amp the rule has nothing to enforce on the step either
    plain = analysis.check_trainer(tr, feed)
    assert "dtype:amp-f32-matmul" not in plain.codes()
    # family selection still isolates: dtype excluded -> no dtype codes
    sel = analysis.check_trainer(tr, feed, select={"donation"},
                                 amp="bfloat16")
    assert not [c for c in sel.codes() if c.startswith("dtype")]


# --------------------------------------------------------------------------
# 3. sharding audit
# --------------------------------------------------------------------------


def test_sharding_audit_codes(dp_mesh):
    mesh = pt.make_mesh({"fsdp": 8})
    params = {"enc/w": jnp.zeros((15, 16)), "big/w": jnp.zeros((64, 64)),
              "small/b": jnp.zeros((4,))}
    rules = pt.parallel.ShardingRules([
        (r".*enc/w$", P("fsdp", None)),       # 15 % 8 -> indivisible
        (r".*stale_pattern.*", P("fsdp")),    # matches nothing
    ], default=P())
    rep = LintReport("t")
    analysis.rules.check_sharding(params, mesh, rules, rep,
                                  large_param_bytes=1024)
    assert {"sharding:unmatched-rule", "sharding:indivisible",
            "sharding:replicated-large"} <= rep.codes()


def test_sharding_audit_flags_typo_axis_despite_adaptation(dp_mesh):
    """adapted_to strips unknown axes (memoized, one-shot warning at
    Trainer construction) — the audit must still surface the typo from
    the RAW rule table every run."""
    rules = pt.parallel.ShardingRules([(r".*/w$", P("fdsp", "tp"))])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rules.adapted_to(dp_mesh)  # consume the one-shot adapt-time warning
    rep = analysis.report.LintReport("t")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        analysis.rules.check_sharding({"a/w": jnp.zeros((16, 16))},
                                      dp_mesh, rules, rep)
    (f,) = rep.by_code("sharding:unknown-axis")
    assert f.data["axis"] == "fdsp"
    # canonical preset vocabulary on a smaller mesh: silent (intended)
    rep2 = analysis.report.LintReport("t")
    analysis.rules.check_sharding({"a/w": jnp.zeros((16, 16))}, dp_mesh,
                                  pt.parallel.ShardingRules([(r".*/w$", P("tp", "fsdp"))]),
                                  rep2)
    assert not rep2.by_code("sharding:unknown-axis")


def test_warn_drop_routes_into_active_report(dp_mesh):
    """satellite: sharding._warn_drop feeds the LintReport collector
    when one is installed (no warning emitted), else warns once per key
    through the warnings module."""
    sharding.reset_drop_warnings()
    rules = pt.parallel.ShardingRules([(r".*w$", P("tp"))], default=P())
    rep = LintReport("t")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with analysis.collect_into(rep):
            rules.spec_for("a/w", (16, 16), dp_mesh)   # no 'tp' in mesh
    assert "sharding:unknown-axis" in rep.codes()
    assert not [w for w in rec
                if isinstance(w.message, sharding.ShardingRuleWarning)]
    # outside the collector: the warnings module carries it
    sharding.reset_drop_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("default")
        rules.spec_for("a/w", (16, 16), dp_mesh)
        rules.spec_for("b/w", (16, 16), dp_mesh)       # same key: deduped
    ours = [w for w in rec if isinstance(w.message, sharding.ShardingRuleWarning)]
    assert len(ours) == 1


# --------------------------------------------------------------------------
# 4. dead / zero-grad params
# --------------------------------------------------------------------------


def _deadzero_program():
    def fn(x):
        w = create_parameter((4, 4), name="w")
        dead = create_parameter((8, 8), name="dead_w")          # never read
        aux = create_parameter((4,), name="aux_w")              # not in loss
        frozen = create_parameter((4,), name="frozen_w", attr=False)
        return {"loss": jnp.matmul(x, w).sum() + (x * frozen).sum(),
                "aux": (x * aux).sum()}
    return pt.build(fn, name="deadzero")


def test_dead_and_zero_grad_params():
    rep = analysis.check(_deadzero_program(),
                         {"x": np.zeros((2, 4), np.float32)})
    assert [f.where for f in rep.by_code("params:dead")] == ["dead_w"]
    assert [f.where for f in rep.by_code("params:zero-grad")] == ["aux_w"]
    # frozen_w is trainable=False (stop_gradient): deliberate, no finding
    assert "frozen_w" not in {f.where for f in rep.findings}


def test_clean_program_has_no_param_findings():
    def fn(x):
        return {"loss": L.fc(x, 4).sum()}

    rep = analysis.check(pt.build(fn), {"x": np.ones((2, 8), np.float32)})
    assert not rep.by_code("params:dead")
    assert not rep.by_code("params:zero-grad")


# --------------------------------------------------------------------------
# 5. donation aliasing (the donated-buffer-reuse footgun)
# --------------------------------------------------------------------------


def test_donation_lint_flags_fetched_param_passthrough():
    """A fetched step output that IS a donated param passed through
    unchanged: the classic footgun, sharpened by the fused K-step
    dispatch donating the whole training carry."""
    def fn(x):
        w = create_parameter((4,), name="w")
        return {"loss": (x * w).sum(), "w_snapshot": w}

    tr = pt.Trainer(pt.build(fn), opt.SGD(0.1), loss_name="loss")
    feed = {"x": np.ones((4,), np.float32)}
    tr.startup(sample_feed=feed)
    rep = analysis.check_trainer(tr, feed)
    hits = rep.by_code("donation:fetched-alias")
    assert len(hits) == 1
    assert "w_snapshot" in hits[0].where
    assert "params" in hits[0].data["donated_input"]


def test_donation_lint_clean_for_computed_outputs():
    """Computed outputs (even trivially derived from donated inputs)
    are NOT aliases — only raw passthrough is the footgun. And with
    donation off there is nothing to flag."""
    def fn(x):
        w = create_parameter((4,), name="w")
        return {"loss": (x * w).sum(), "w_copy": w + 0.0}

    tr = pt.Trainer(pt.build(fn), opt.SGD(0.1), loss_name="loss")
    feed = {"x": np.ones((4,), np.float32)}
    tr.startup(sample_feed=feed)
    assert not analysis.check_trainer(tr, feed).by_code(
        "donation:fetched-alias")

    def fn2(x):
        w = create_parameter((4,), name="w")
        return {"loss": (x * w).sum(), "w_snapshot": w}

    tr2 = pt.Trainer(pt.build(fn2), opt.SGD(0.1), loss_name="loss",
                     donate=False)
    tr2.startup(sample_feed=feed)
    assert not analysis.check_trainer(tr2, feed).by_code(
        "donation:fetched-alias")


def test_donation_lint_select_family():
    def fn(x):
        w = create_parameter((4,), name="w")
        return {"loss": (x * w).sum(), "w_snapshot": w}

    tr = pt.Trainer(pt.build(fn), opt.SGD(0.1), loss_name="loss")
    feed = {"x": np.ones((4,), np.float32)}
    tr.startup(sample_feed=feed)
    only = analysis.check_trainer(tr, feed, select={"donation"})
    assert set(only.codes()) == {"donation:fetched-alias"}
    without = analysis.check_trainer(tr, feed, select={"collective"})
    assert "donation:fetched-alias" not in without.codes()


# --------------------------------------------------------------------------
# 6. recompilation hazards
# --------------------------------------------------------------------------


def test_retrace_hazards():
    def fn(x, scale, cfg):
        return {"loss": (x * scale).sum()}

    rep = analysis.check(
        pt.build(fn),
        {"x": np.ones((4,), np.float32), "scale": 2.0, "cfg": [1, 2, 3]})
    assert {f.where for f in rep.by_code("retrace:weak-scalar")} == {"scale"}
    assert {f.where for f in rep.by_code("retrace:unhashable-arg")} == {"cfg"}


# --------------------------------------------------------------------------
# report machinery
# --------------------------------------------------------------------------


def test_report_severity_api():
    rep = LintReport("t")
    rep.add("a:b", "info", "m1")
    rep.add("c:d", "warning", "m2", where="here")
    assert rep.ok("error") and not rep.ok("warning")
    assert len(rep.at_least("info")) == 2
    with pytest.raises(LintError):
        rep.enforce_clean("warning")
    rep.enforce_clean("error")  # no error findings: passes
    assert "c:d" in rep.render("warning") and "a:b" not in rep.render("warning")
    d = rep.to_dict()
    assert d["counts"]["warning"] == 1 and len(d["findings"]) == 2


# --------------------------------------------------------------------------
# Trainer integration
# --------------------------------------------------------------------------


def _mlp(image, label):
    h = L.fc(image, 32, act="tanh")
    logits = L.fc(h, 10)
    loss = L.mean(L.softmax_with_cross_entropy(logits, label))
    return {"loss": loss}


def _mlp_feed(bs=16):
    rng = np.random.RandomState(0)
    return {"image": rng.rand(bs, 784).astype(np.float32),
            "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)}


def test_trainer_lint_error_raises_on_microbatch_collective(dp_mesh):
    tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3), mesh=dp_mesh,
                    sharding_rules=pt.parallel.replicated(),
                    strategy=DistStrategy(accum_steps=2))
    with pytest.raises(LintError):
        tr.startup(sample_feed=_mlp_feed(), lint="error")
    assert "collective:microbatch-exchange" in tr.lint_report.codes()


def test_trainer_door_reports_typo_axis(dp_mesh):
    """Trainer.__init__ adapts its working rule table (stripping typo'd
    axes); the lint must still audit the pre-adaptation table."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3), mesh=dp_mesh,
                        sharding_rules=pt.parallel.ShardingRules(
                            [(r".*/w$", P("fdsp"))]))
        tr.startup(sample_feed=_mlp_feed(), lint="warn")
    assert "sharding:unknown-axis" in tr.lint_report.codes()


def test_check_survives_untraceable_required_arg():
    """An unhashable/ragged feed value is the retrace family's finding,
    not a crash: the jaxpr rules degrade to an info finding."""
    def fn(x, label):
        return {"loss": x.sum()}

    rep = analysis.check(pt.build(fn),
                         {"x": np.ones((2, 2), np.float32),
                          "label": [[1, 2], [3]]})
    assert "retrace:unhashable-arg" in rep.codes()
    assert "analysis:trace-failed" in rep.codes()
    assert rep.ok("warning") or rep.by_code("retrace:unhashable-arg")


def test_trainer_lint_error_on_model_collective_in_scan(dp_mesh):
    """The step-trace path: an explicit in-jaxpr collective inside the
    model's own scan is visible through the built step function."""
    tr = pt.Trainer(_unhoisted_program(dp_mesh), opt.SGD(0.1))
    feed = {"x": np.random.rand(8, 4).astype(np.float32)}
    with pytest.raises(LintError):
        tr.startup(sample_feed=feed, lint="error")
    assert "collective:in-scan" in tr.lint_report.codes()


def test_trainer_lint_warn_emits_and_proceeds(dp_mesh):
    tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3), mesh=dp_mesh,
                    sharding_rules=pt.parallel.replicated(),
                    strategy=DistStrategy(accum_steps=2))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        tr.startup(sample_feed=_mlp_feed(), lint="warn")
    assert [w for w in rec if isinstance(w.message, LintWarning)]
    out = tr.step(_mlp_feed())
    assert np.isfinite(float(out["loss"]))


def test_trainer_lint_error_passes_clean_program():
    tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3))
    tr.startup(sample_feed=_mlp_feed(), lint="error")
    assert tr.lint_report is not None and tr.lint_report.ok("warning")
    assert np.isfinite(float(tr.step(_mlp_feed())["loss"]))


def test_trainer_lint_off_and_bad_value():
    tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3))
    tr.startup(sample_feed=_mlp_feed())
    assert tr.lint_report is None
    tr2 = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3))
    with pytest.raises(EnforceError):
        tr2.startup(sample_feed=_mlp_feed(), lint="loud")


# --------------------------------------------------------------------------
# satellites riding along: eval divisibility + row-perm walk
# --------------------------------------------------------------------------


def test_eval_enforces_pp_microbatch_divisibility():
    """Round-5 advisor finding: interleaved-pp eval runs the training
    schedule; the enforce must name pp_microbatches."""
    tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3),
                    strategy=DistStrategy(pp_microbatches=3, pp_interleave=2))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "pp set but no mesh" ambient warn
        tr.startup(sample_feed=_mlp_feed())
    tr._pp_perm = {"stack/w": np.arange(4)}  # simulate interleaved layout
    tr._build_step()
    with pytest.raises(EnforceError, match="pp_microbatches=3"):
        tr.eval(_mlp_feed(16))  # 16 % 3 != 0


def test_apply_row_perm_walks_all_name_keyed_state():
    """Round-5 advisor finding: per-param opt state OUTSIDE 'accums'
    (but keyed by param name per the Optimizer contract) must round-trip
    through the interleaved layout too."""
    tr = pt.Trainer(pt.build(_mlp), opt.Adam(1e-3))
    perm = np.array([2, 0, 3, 1])
    tr._pp_perm = {"stack/w": perm}
    rows = jnp.arange(4.0)[:, None] * jnp.ones((4, 3))
    params = {"stack/w": rows}
    opt_state = {"step": jnp.int32(7),
                 "global": {"stack/w": rows * 10.0},     # non-accums slot
                 "accums": {"stack/w": {"m": rows * 100.0},
                            "other/w": {"m": rows * 7.0}},
                 "extra4": jnp.arange(4.0)}              # NOT name-keyed
    p2, o2 = tr.stacked_to_logical(params, opt_state)
    inv = np.argsort(perm)
    np.testing.assert_allclose(np.asarray(p2["stack/w"])[:, 0], inv)
    np.testing.assert_allclose(np.asarray(o2["global"]["stack/w"])[:, 0],
                               inv * 10.0)
    np.testing.assert_allclose(np.asarray(o2["accums"]["stack/w"]["m"])[:, 0],
                               inv * 100.0)
    # untouched: other params' slots, scalars, non-name-keyed leaves
    np.testing.assert_allclose(np.asarray(o2["accums"]["other/w"]["m"]),
                               np.asarray(rows * 7.0))
    np.testing.assert_allclose(np.asarray(o2["extra4"]), np.arange(4.0))
    assert int(o2["step"]) == 7
    # round trip back to interleaved
    p3, o3 = tr.stacked_from_logical(p2, o2)
    np.testing.assert_allclose(np.asarray(p3["stack/w"]),
                               np.asarray(params["stack/w"]))
    np.testing.assert_allclose(np.asarray(o3["accums"]["stack/w"]["m"]),
                               np.asarray(rows * 100.0))


# --------------------------------------------------------------------------
# CLI exit codes: findings (1) vs internal error (3)
# --------------------------------------------------------------------------


def test_cli_exit1_on_findings_vs_exit3_on_crash(tmp_path, capsys):
    """The CI contract of `python -m paddle_tpu.analysis`: exit 1 means
    YOUR program has findings; exit 3 means the CHECKER broke (unknown
    model, bad baseline file) — a crash must never read as a lint
    verdict in either direction."""
    import json

    from paddle_tpu.analysis.__main__ import main as lint_main

    # findings present (the tight-MoE golden) -> 1
    argv = ["--model", "moe_transformer", "--variant", "tight"]
    assert lint_main(argv) == 1
    assert "moe:capacity" in capsys.readouterr().out

    # checker crash (unknown zoo model) -> 3, with the traceback shown
    assert lint_main(["--model", "no_such_model"]) == 3
    assert "internal error" in capsys.readouterr().err

    # a malformed baseline file is a checker problem, not a verdict -> 3
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as fh:
        json.dump({"version": 99, "baseline": {}}, fh)
    assert lint_main(argv + ["--ci", "--baseline", bad]) == 3
    capsys.readouterr()

    # a bad flag VALUE is a usage error -> 2 (argparse's code), never
    # 1 ("you introduced a finding") or 3 ("the checker is broken")
    with pytest.raises(SystemExit) as ei:
        lint_main(argv + ["--severity", "no_equals_sign"])
    assert ei.value.code == 2
    with pytest.raises(SystemExit) as ei:
        lint_main(argv + ["--severity", "moe:capacity=bogus"])
    assert ei.value.code == 2   # rejected BEFORE paying the model build
    with pytest.raises(SystemExit) as ei:
        lint_main(argv + ["--rules", "nope"])
    assert ei.value.code == 2
    capsys.readouterr()

    # --baseline keeps its promise without --ci too
    base0 = str(tmp_path / "base0.json")
    assert lint_main(argv + ["--write-baseline", base0]) == 0
    assert lint_main(argv + ["--baseline", base0]) == 0
    capsys.readouterr()

    # --ci still names the new fingerprints under machine formats
    assert lint_main(argv + ["--ci", "--format", "sarif"]) == 1
    cap = capsys.readouterr()
    assert json.loads(cap.out)["version"] == "2.1.0"
    assert "moe:capacity|blocks/moe_0" in cap.err

    # --ci with the findings baselined -> 0; severity demotion -> 0 too
    base = str(tmp_path / "base.json")
    assert lint_main(argv + ["--write-baseline", base]) == 0
    capsys.readouterr()
    assert lint_main(argv + ["--ci", "--baseline", base]) == 0
    assert lint_main(argv + ["--severity", "moe:capacity=info"]) == 0
    capsys.readouterr()


def test_cli_subject_matches_lint_gate_baseline(capsys):
    """The CLI's baseline subject must name configs the way
    tools/lint_gate.py does ("gpt.amp", "moe_transformer.tight"), or the
    committed baseline can never suppress a CLI run: the module
    docstring's own example must exit 0 against the committed file."""
    import os

    from paddle_tpu.analysis.__main__ import main as lint_main

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    baseline = os.path.join(root, "tools", "analysis_baseline.json")
    assert lint_main(["--model", "gpt", "--amp", "bfloat16", "--ci",
                      "--baseline", baseline]) == 0
    capsys.readouterr()
    # --subject overrides the default naming entirely: a made-up
    # subject no longer matches the suppressed keys -> the golden
    # finding reads as new again
    assert lint_main(["--model", "gpt", "--amp", "bfloat16", "--ci",
                      "--baseline", baseline,
                      "--subject", "somewhere_else"]) == 1
    assert "new finding" in capsys.readouterr().err
