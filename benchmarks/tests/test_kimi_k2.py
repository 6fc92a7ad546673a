"""The ``kimi_k2`` family and the readers that came with it, on the CPU: the
family end to end at a toy size through the ``serve_closed`` driver (its own
throw-away root: ``conftest.py``'s toys are GPT's), each new reader on a
small synthetic trace, and the family's counts against values worked out by
hand from the published config."""

import json
import os
import shutil
import time

import pytest

from benchmarks import harness
from benchmarks.families import kimi_k2 as family
from benchmarks.readers import decode_split, mla_flash_roofline
from benchmarks.trace_reduce import Trace

from conftest import BENCH, HERE, ROOT

CELL = "tiny-k25-serve-batch"
REAL_CELL = "k25-serve-batch"
MS = 1_000_000


@pytest.fixture
def k25_root(tmp_path):
    """A root with one cell: the toy configuration and traffic of
    ``tests/data`` under the real cell's metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    for kind, name in (("configs", "tiny-kimi-k2"),
                       ("traffic", "tiny-serve-closed-k25")):
        (bench / kind).mkdir()
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    bench / kind / (name + ".json"))
    doc = dict(
        real,
        configs=[{"name": "tiny-kimi-k2", "source": "none", "reduced": [],
                  "file": "bench/configs/tiny-kimi-k2.json", "why": "toy"}],
        workloads=[{"name": CELL, "config": "tiny-kimi-k2",
                    "traffic": "tiny-serve-closed-k25", "chips": 1,
                    "why": "toy"}])
    for group in ("end_to_end", "per_layer"):
        doc[group] = [
            dict(m, workloads=[CELL] if REAL_CELL in m["workloads"] else [])
            if "workloads" in m else m for m in real[group]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(tmp_path)


def real_cell():
    return harness.load_cell(REAL_CELL)


# -- the family through the driver ---------------------------------------------


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
def test_the_family_runs_through_serve_closed(k25_root, trace):
    line = harness.run_cell(CELL, seed=2**31 + 5, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), root=k25_root,
                            allow_cpu=True)
    assert line["correct"] is True, line["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    check = line["notes"]["check"]
    assert check["rows"] == family.SERVE_CHECK_ROWS
    assert check["worst_logit_gap"] <= family.LOGIT_MARGIN
    assert check["argmax_agree"] >= family.AGREE_FLOOR
    names = set(line["metrics"])
    if trace:
        # what needs no device plane (a CPU trace has none: idle reads 100%);
        # the rest have nothing to read and leave their metric out
        assert names == {"compiles_in_window.k25", "window_tokens_per_s.k25",
                         "server_block_ms.k25", "device_idle_share.k25"}
        assert line["metrics"]["compiles_in_window.k25"]["value"] == 0
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    json.dumps(line)


def test_the_real_cell_resolves_to_the_family_and_its_readers():
    cell = real_cell()
    assert cell.family is family and cell.chips == 1
    assert cell.driver.__name__ == "benchmarks.drivers.serve_closed"
    assert cell.end_to_end == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "compiles_in_window.k25", "device_idle_share.k25", "peak_hbm_gb.k25",
        "window_tokens_per_s.k25", "server_block_ms.k25", "decode_step_ms.k25",
        "prefill_ms.k25", "decode_step_hbm_share.k25", "prefill_mfu.k25",
        "mla_flash_roofline.k25"}
    t, c = cell.traffic, cell.config
    assert (t["rows"], t["prompt"], t["new_tokens"], t["workers"]) == (8, 1984, 64, 1)
    assert c["vocab_size"] == 20480 and c["n_routed_experts"] == 12
    assert c["published"] == {"num_hidden_layers": 61, "n_routed_experts": 384,
                              "vocab_size": 163840}


def test_the_selection_bias_is_seeded_small_and_the_same_for_export_and_check():
    """ISSUE 31's bias: N(0, 0.01^2) from the seed, float32, not fitted;
    the export's host copy and the check's device copy are the same
    numbers, and another seed gives others."""
    import numpy as np

    cfg = json.load(open(os.path.join(HERE, "data", "tiny-kimi-k2.json")))
    w = family.decoder_params(cfg, 5, 40, 8)
    name = "moe/experts/router/select_bias"
    host = w.host_params()[name]
    assert host.dtype == np.float32 and host.shape == (2, 16)
    assert 0.3 * family.SELECT_BIAS_STD < host.std() < 2 * family.SELECT_BIAS_STD
    again = family.decoder_params(cfg, 5, 40, 8)
    assert np.array_equal(host[1], np.asarray(again.slab(name, 1)))
    assert np.array_equal(
        host[1], np.asarray(again.reference_ffn(2)["select_bias"]))
    other = family.decoder_params(cfg, 6, 40, 8).host_params()[name]
    assert not np.array_equal(host, other)


def test_the_sensitivity_run_s_turned_keys_stand_one_position_on():
    """``k25_sensitivity``'s second fault, without a field in the
    reference: kv_a's rotary columns turned by one position's angles give
    the scores of keys rotated at ``pos + 1``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import kimi_k2 as reference
    from benchmarks.tools import k25_sensitivity

    cfg = json.load(open(os.path.join(HERE, "data", "tiny-kimi-k2.json")))
    sh = reference.shape_of(cfg)
    w = family.decoder_params(cfg, 5, 12, 4)
    lp = w.reference_attention(1)
    _, turned = k25_sensitivity.edits()["keys_one_position_on"](
        sh, "attention", 1, lp)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 12, cfg["hidden_size"]),
                    jnp.float32)
    k_pe = lambda p: (x @ p["kv_a"])[..., sh.kv_lora:]
    inv = reference.yarn_inv_freq(sh.rope, sh)
    with jax.default_matmul_precision("highest"):
        want = reference.rotate(k_pe(lp), jnp.arange(12) + 1, inv, 1.0)
        got = reference.rotate(k_pe(turned), jnp.arange(12), inv, 1.0)
        assert np.abs(np.asarray(got - want)).max() < 1e-5
        assert np.abs(np.asarray(reference.attention(x, turned, sh)
                                 - reference.attention(x, lp, sh))).max() > 1e-3
    assert np.array_equal(turned["kv_a"][:, :sh.kv_lora], lp["kv_a"][:, :sh.kv_lora])


# -- the new readers on a synthetic trace -----------------------------------------


def synthetic():
    """Two whole executions of ``jit_main`` and one cut by the window's
    end. In each: a prefill of 500 ms holding a scan ``while.1`` (400 ms)
    with two ``flash_fwd`` kernels and a grouped product, then the decode
    loop ``while.2`` (630 ms) with its conditional; a longer ``while.9``
    without a conditional shows that the rule is the conditional, not the
    length."""
    ops, modules = [], []
    for k, t0 in enumerate((0, 1200 * MS, 2400 * MS)):
        modules.append(("jit_main(1)", t0, 1130 * MS))
        ops += [("while.1 [while]", t0 + 50 * MS, 400 * MS),
                ("flash_fwd.1 [custom-call]", t0 + 10 * MS, 5 * MS),
                ("flash_fwd.2 [custom-call]", t0 + 60 * MS, 5 * MS),
                ("ragged-dot-none.3 [custom-call]", t0 + 70 * MS, 9 * MS),
                ("while.2 [while]", t0 + 500 * MS, 630 * MS),
                ("conditional.4 [conditional]", t0 + 501 * MS, 9 * MS),
                ("fusion.5 [fusion]", t0 + 502 * MS, 8 * MS)]
    ops.append(("while.9 [while]", 1150 * MS, 40 * MS))
    return Trace(ops={0: ops}, modules={0: modules},
                 kernels=["flash_fwd.1 [custom-call]", "flash_fwd.2 [custom-call]",
                          "ragged-dot-none.3 [custom-call]"],
                 host=[], window=(0, 3000 * MS))


def reading(reader, part=None, trace=synthetic, peaks=True):
    cell = real_cell()
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None)
    if peaks:
        run.peaks = harness.peaks_for("TPU v5 lite")
    obs = harness.Observed(True, 2, 0, {"rows": 8, "prompt": 1984,
                                        "new_tokens": 64},
                           trace=trace() if trace else None)
    return reader.read(run, obs, {"args": {"part": part}})


def test_decode_split_finds_the_loop_with_the_conditional():
    assert decode_split.executions(synthetic()) == [(1.13, 0.63), (1.13, 0.63)]
    assert reading(decode_split, "decode_step_ms") == pytest.approx(10.0)
    assert reading(decode_split, "prefill_ms") == pytest.approx(500.0)


def test_decode_split_shares_are_need_over_time_over_peak():
    cfg = real_cell().config
    need = sum(family.decode_step_bytes(cfg, 8, 1984 + j) for j in range(63)) / 63
    assert reading(decode_split, "decode_step_hbm_share") == pytest.approx(
        100 * need / 0.010 / 819e9)
    assert reading(decode_split, "prefill_mfu") == pytest.approx(
        100 * family.prefill_flops(cfg, 8, 1984) / 0.5 / 197e12)


def test_mla_flash_roofline_counts_whole_flash_calls_only():
    cfg = real_cell().config
    # 3 executions x 2 calls, all whole inside the window; the grouped
    # product is a kernel too and is left out
    want = 100 * 6 * family.mla_flash_flops(cfg, 8, 1984) / 0.030 / 197e12
    assert reading(mla_flash_roofline) == pytest.approx(want)


@pytest.mark.parametrize("reader,part", [
    (decode_split, "decode_step_ms"), (decode_split, "prefill_ms"),
    (decode_split, "decode_step_hbm_share"), (decode_split, "prefill_mfu"),
    (mla_flash_roofline, None)])
def test_a_new_reader_with_nothing_to_read_returns_nothing(reader, part):
    empty = lambda: Trace(ops={0: []}, modules={0: []}, kernels=[], host=[],
                          window=(0, MS))
    assert reading(reader, part, trace=None) is None      # an untraced run
    assert reading(reader, part, trace=empty) is None     # nothing whole
    if part not in ("decode_step_ms", "prefill_ms"):
        assert reading(reader, part, peaks=False) is None  # no peak, no share


def test_new_readers_leave_another_family_s_cell_alone():
    """``conftest.py`` maps every metric of a ``batch`` cell onto GPT's toy:
    the readers that ask the family for a count it does not have return
    nothing there."""
    cell = harness.load_cell("gpt2m-serve-batch")
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None,
                      peaks=harness.peaks_for("TPU v5 lite"))
    obs = harness.Observed(True, 2, 0, {"rows": 8, "prompt": 1984,
                                        "new_tokens": 64}, trace=synthetic())
    assert decode_split.read(run, obs, {"args": {"part": "prefill_mfu"}}) is None
    assert decode_split.read(
        run, obs, {"args": {"part": "decode_step_hbm_share"}}) is None
    assert mla_flash_roofline.read(run, obs, {"args": {}}) is None


# -- the family's arithmetic, by hand ------------------------------------------------


def test_counts_by_hand():
    """ISSUE 31's table: 101.1M parameters in MLA, 44.04M an expert, 2.75M
    in the router; a prefill token costs 2.83 GFLOP and a request 44.9
    TFLOP; a step that touches 1.86 experts a layer reads 3.7 GB (3.6 in the
    issue's rounder count) and one that reads all 12 reads 8.2."""
    cfg = real_cell().config
    c = family._counts(cfg)
    assert c["mla"] == 101_122_048 and c["expert"] == 44_040_192
    assert c["router"] == 2_752_512 and c["dense_ffn"] == 396_361_728
    assert c["head"] == 146_800_640
    # per token: 6 MLA, the dense FFN, 5 x (shared + router + 8 x 12/384 expert)
    per_token = 2 * (6 * 101_122_048 + 396_361_728
                     + 5 * (44_040_192 + 2_752_512 + 44_040_192 * 0.25))
    assert per_token == pytest.approx(2.585e9, rel=1e-3)
    attn = 6 * 2 * 8 * 64 * 1984 * 1985 / 2 * 320
    flops = family.prefill_flops(cfg, 8, 1984)
    assert flops == pytest.approx(8 * 1984 * per_token + attn
                                  + 2 * 8 * 146_800_640)
    assert flops == pytest.approx(44.9e12, rel=5e-3)
    assert family.mla_flash_flops(cfg, 8, 1984) == pytest.approx(attn / 6)
    touched = 12 * (1 - (1 - 8 / 384) ** 8)
    assert family.experts_touched(cfg, 8) == pytest.approx(touched)
    weights = (2 * (6 * 101_122_048 + 396_361_728 + 146_800_640
                    + 5 * (44_040_192 + touched * 44_040_192))
               + 4 * 5 * 2_752_512)
    cache = 2 * 6 * 8 * 2016 * 576
    assert family.decode_step_bytes(cfg, 8, 2015) == pytest.approx(weights + cache)
    assert weights + cache == pytest.approx(3.73e9, rel=5e-3)
    every = family.decode_step_bytes(dict(cfg, num_experts_per_tok=384), 8, 2015)
    assert every == pytest.approx(8.19e9, rel=5e-3)     # all 12 experts read


def test_decode_min_bytes_is_the_prefill_once_and_every_step():
    cfg = real_cell().config
    steps = sum(family.decode_step_bytes(cfg, 8, 1984 + j) for j in range(63))
    held = (2 * (6 * 101_122_048 + 396_361_728 + 146_800_640
                 + 5 * 13 * 44_040_192) + 4 * 5 * 2_752_512)
    assert family.decode_min_bytes(cfg, 8, 1984, 64) == pytest.approx(held + steps)
    assert family.decode_min_bytes(cfg, 8, 1984, 1) == pytest.approx(held)
    # the weights held, but for the embedding (read by row): 8.37 GB less 0.29
    assert held == pytest.approx(8.374e9 - 2 * 146_800_640, rel=5e-3)
