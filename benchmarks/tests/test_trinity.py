"""The ``trinity`` family on the CPU: the family end to end at a toy size
through the ``serve_closed`` driver (its own throw-away root: ``conftest.py``'s
toys are GPT's), the real cell's files resolving to the family and to readers
that exist, and the family's counts against brute-force sums."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.families import trinity as family
from benchmarks.reference import trinity as reference

from conftest import BENCH, HERE, ROOT

CELL = "tiny-trinity-serve-long"
REAL_CELL = "trinity-serve-long"
METRICS = {
    "compiles_in_window.trinity", "device_idle_share.trinity",
    "peak_hbm_gb.trinity", "window_tokens_per_s.trinity",
    "server_block_ms.trinity", "prefill_ms.trinity", "decode_step_ms.trinity",
    "prefill_mfu.trinity", "decode_step_hbm_share.trinity",
    "unscoped_time_share.trinity", "inherited_time_share.trinity",
    "swa_time_share.trinity", "full_attn_time_share.trinity",
    "moe_time_share.trinity", "window_kv_bytes_share.trinity",
    "gqa_flash_roofline.trinity"}


def tiny():
    with open(os.path.join(HERE, "data", "tiny-trinity.json")) as f:
        return json.load(f)


@pytest.fixture
def trinity_root(tmp_path):
    """A root with one cell: the toy configuration and traffic of
    ``tests/data`` under the real cell's metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    for kind, name in (("configs", "tiny-trinity"),
                       ("traffic", "tiny-serve-closed-trinity")):
        (bench / kind).mkdir()
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    bench / kind / (name + ".json"))
    doc = dict(
        real,
        configs=[{"name": "tiny-trinity", "source": "none", "reduced": [],
                  "file": "bench/configs/tiny-trinity.json", "why": "toy"}],
        workloads=[{"name": CELL, "config": "tiny-trinity",
                    "traffic": "tiny-serve-closed-trinity", "chips": 1,
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
def test_the_family_runs_through_serve_closed(trinity_root, trace):
    line = harness.run_cell(CELL, seed=2**31 + 5, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), root=trinity_root,
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
        assert names == {"compiles_in_window.trinity",
                         "window_tokens_per_s.trinity",
                         "server_block_ms.trinity", "device_idle_share.trinity",
                         "window_kv_bytes_share.trinity"}
        assert line["metrics"]["compiles_in_window.trinity"]["value"] == 0
        # the toy's four rings of 8 keys against one cache of 48 positions
        assert line["metrics"]["window_kv_bytes_share.trinity"]["value"] == (
            pytest.approx(100 * 4 * 8 / (4 * 8 + 48)))
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    json.dumps(line)


def test_the_real_cell_resolves_to_the_family_and_its_readers():
    cell = real_cell()
    assert cell.family is family and cell.chips == 1
    assert cell.driver.__name__ == "benchmarks.drivers.serve_closed"
    assert cell.end_to_end == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == METRICS
    for spec in cell.per_layer:     # every file names a reader that exists
        assert hasattr(harness.load_module("readers", spec["reader"]), "read")
        assert spec["workloads"] == [REAL_CELL]
        assert spec["moves"] == "serve_tokens_per_s"
    t, c = cell.traffic, cell.config
    assert (t["rows"], t["prompt"], t["new_tokens"], t["workers"],
            t["callers"], t["buckets"]) == (8, 32768, 128, 1, 2, [8])
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["sliding_window"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["route_scale"]) == (3072, 48, 8, 128, 4096, 12288, 3072, 4, 2.448)
    assert c["vocab_size"] == 25024 and c["num_experts"] == 32
    assert c["layer_indices"] == [5, 6, 7, 8, 9]
    assert c["published"] == {"num_hidden_layers": 60, "num_dense_layers": 6,
                              "num_experts": 256, "vocab_size": 200192}
    assert [(k == reference.SLIDING, d) for _, k, d in reference.layers_of(c)] == [
        (True, True), (True, False), (False, False), (True, False),
        (True, False)]
    pc = family.program_config(c)
    assert (pc.first_layer, pc.first_expert, pc.experts_held, pc.num_experts,
            pc.prefill_chunk) == (5, 0, 32, 256, c["run"]["chunk"])


def test_the_weights_are_seeded_and_an_expert_is_made_alone():
    """The export's host copy and the check's device copies are the same
    numbers; one expert of a bank is made of the draws that hold it and
    equals its rows of the bank; the embedding has unit variance after the
    muP multiplier; another seed gives other numbers."""
    cfg = tiny()
    w = family.decoder_params(cfg, 5, 40, 8)
    host = w.host_params()
    bias = host["layer_3/experts/router/select_bias"]
    assert bias.dtype == np.float32 and bias.shape == (16,)
    assert 0.3 * family.SELECT_BIAS_STD < bias.std() < 2 * family.SELECT_BIAS_STD
    assert np.array_equal(bias, np.asarray(w.reference_ffn(3, False)["select_bias"]))
    bank = host["layer_3/experts/up/w"]
    assert bank.shape == (4, 64, 32)
    for j in range(4):
        gate, up, down = w.reference_expert(3, j)
        assert np.array_equal(np.asarray(up), bank[j])
        assert np.array_equal(np.asarray(down), host["layer_3/experts/down/w"][j])
    emb = host["tok/embedding_0/w"]
    assert (emb * 8.0).std() == pytest.approx(1.0, rel=0.05)   # sqrt(64) = 8
    other = family.decoder_params(cfg, 6, 40, 8).host_params()
    assert not np.array_equal(bias, other["layer_3/experts/router/select_bias"])


# -- the family's arithmetic against brute force ----------------------------------


def _brute_pairs(kind, prompt, window):
    return sum(1 for i in range(prompt) for j in range(i + 1)
               if kind == reference.FULL or i - j < window)


@pytest.mark.parametrize("prompt", [5, 8, 40])
def test_attention_pairs_against_a_double_loop(prompt):
    cfg = tiny()
    for kind in (reference.SLIDING, reference.FULL):
        assert family.attention_pairs(cfg, kind, prompt) == _brute_pairs(
            kind, prompt, cfg["sliding_window"])


def test_prefill_flops_are_every_matrix_and_every_pair():
    cfg = tiny()
    d, hd, H, K = 64, 128, 4, 2
    attention = d * H * hd * 3 + d * K * hd * 2
    dense_ffn, shared, expert, router = 3 * d * 96, 3 * d * 32, 3 * d * 32, d * 16
    # layers 1..5: 1 dense, 4 with experts (4 held of 16, 2 a token)
    per_token = 2 * (5 * attention + dense_ffn
                     + 4 * (shared + router + expert * 2 * 4 / 16))
    kinds = [k for _, k, _ in reference.layers_of(cfg)]
    pairs = sum(_brute_pairs(k, 40, 8) for k in kinds)
    assert kinds.count(reference.FULL) == 1
    want = (2 * 40 * per_token + 2 * 2 * H * pairs * 2 * hd
            + 2 * 2 * d * cfg["vocab_size"])
    assert family.prefill_flops(cfg, 2, 40) == pytest.approx(want)
    assert family.flash_flops(cfg, 2, 40) == pytest.approx(
        2 * 2 * H * pairs * 2 * hd)


def test_kernel_counts_walk_the_pieces():
    """A call a layer and piece; q and o of every position; the keys a
    piece's queries reach, each key head's once."""
    cfg = tiny()          # pieces of 16: 40 tokens are 16 + 16 + 8
    flops, moved, calls = family.kernel_counts(cfg, 2, 40, "flash_fwd")
    assert calls == 5 * 3 and flops == family.flash_flops(cfg, 2, 40)
    want = 0.0
    for _, kind, _ in reference.layers_of(cfg):
        for p0, s in ((0, 16), (16, 16), (32, 8)):
            before = p0 if kind == reference.FULL else min(p0, 7)
            want += 2 * 2 * (2 * s * 4 * 128 + 2 * (before + s) * 2 * 128)
    assert moved == pytest.approx(want)
    assert family.kernel_counts(cfg, 2, 40, "mamba_fwd") is None


def test_decode_step_bytes_are_weights_touched_rings_and_the_cache_so_far():
    cfg = tiny()
    d, hd, H, K = 64, 128, 4, 2
    attention = d * H * hd * 3 + d * K * hd * 2
    touched = 4 * (1 - (1 - 2 / 16) ** 2)
    assert family.experts_touched(cfg, 2) == pytest.approx(touched)
    weights = (2 * (5 * attention + 3 * d * 96 + d * cfg["vocab_size"]
                    + 4 * (3 * d * 32 + touched * 3 * d * 32))
               + 4 * 4 * d * 16)
    # four rings of 8 keys, one cache to position 41, k and v, bfloat16
    cache = 2 * 2 * 2 * (4 * 8 + 42) * K * hd
    assert family.decode_step_bytes(cfg, 2, 41) == pytest.approx(weights + cache)
    # before the window is full a ring holds what was written
    early = 2 * 2 * 2 * (4 * 4 + 4) * K * hd
    assert family.decode_step_bytes(cfg, 2, 3) == pytest.approx(weights + early)


def test_the_real_cell_s_counts_are_the_issue_s():
    """ISSUE 45's table: 62.9M parameters in attention, 28.3M an expert,
    113.2M in the dense FFN, 153.7M in embedding and head together; per
    token 1.20 GFLOP of matrix products, one full layer 0.40 and four
    sliding layers 0.38 of attention."""
    cfg = real_cell().config
    c = family._counts(cfg)
    assert c["attention"] == 62_914_560 and c["expert"] == 28_311_552
    assert c["dense_ffn"] == 113_246_208 and c["router"] == 786_432
    assert 2 * c["head"] == 153_747_456
    tokens = 8 * 32768
    per_token = (family.prefill_flops(cfg, 8, 32768)
                 - family.flash_flops(cfg, 8, 32768)
                 - 2 * 8 * c["head"]) / tokens
    assert per_token == pytest.approx(1.20e9, rel=0.01)
    full = 2 * 48 * 256 * family.attention_pairs(cfg, reference.FULL, 32768) / 32768
    sliding = 4 * 2 * 48 * 256 * family.attention_pairs(
        cfg, reference.SLIDING, 32768) / 32768
    assert full == pytest.approx(0.40e9, rel=0.01)
    assert sliding == pytest.approx(0.38e9, rel=0.01)
    flops, moved, calls = family.kernel_counts(cfg, 8, 32768, "flash_fwd")
    assert calls == 5 * 32768 // cfg["run"]["chunk"]
    # compute bound by far: the bytes' time is a few percent of the pairs'
    assert moved / 819e9 < 0.1 * flops / 197e12


def test_a_layer_is_checked_where_the_served_tokens_reach():
    """``needed_from``: everything behind the full layer, a window's reach a
    sliding layer above it; and the trimmed forward gives the logits of the
    whole one."""
    cfg = real_cell().config
    assert family.needed_from(cfg, 32767) == [0, 0, 32767 - 2 * 4095,
                                              32767 - 4095, 32767]
    toy = tiny()        # window 8, layers 1-5 (3 the full one)
    assert family.needed_from(toy, 30) == [0, 0, 16, 23, 30]
    w = family.decoder_params(toy, 4, 31, 4)
    ids = family.prompts(toy["vocab_size"], 2, 34, 4, 1)[0]
    trimmed = family.reference_logits(toy, w, ids, 30)
    same = lambda sh, part, layer, kind, lp: (sh, kind, lp)
    whole = family.reference_logits(toy, w, ids, 30, edit=same)
    assert trimmed.shape == whole.shape == (2, 4, toy["vocab_size"])
    np.testing.assert_allclose(trimmed, whole, atol=2e-5)
