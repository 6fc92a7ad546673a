"""The ``granite_hybrid`` family on the CPU: the family end to end at a toy
size through the ``serve_closed`` driver (its own throw-away root), the files
of the real cell, the seeded weights, and the counts' arithmetic against
ISSUE 49's reckoning."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.families import granite_hybrid as family

from conftest import BENCH, HERE, ROOT

CELL = "tiny-granite-serve-agent"
REAL_CELL = "granite-serve-agent"
# ISSUE 49 names sixteen; the driver's contract for BENCHMARK.json allows 1
# to 128 per-layer metrics and the file held 120: these eight, the generic
# ones first (CHANGES.md, PR 49, says which were left out and why)
METRICS = {name + ".granite" for name in (
    "peak_hbm_gb", "window_tokens_per_s", "server_block_ms", "prefill_ms",
    "decode_step_ms", "prefill_mfu", "decode_step_hbm_share",
    "ssd_fwd_roofline")}


def tiny_config():
    return json.load(open(os.path.join(HERE, "data", "tiny-granite-hybrid.json")))


@pytest.fixture
def granite_root(tmp_path):
    """A root with one cell: the toy configuration and traffic of
    ``tests/data`` under the real cell's metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    for kind, name in (("configs", "tiny-granite-hybrid"),
                       ("traffic", "tiny-serve-closed-granite")):
        (bench / kind).mkdir()
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    bench / kind / (name + ".json"))
    doc = dict(
        real,
        configs=[{"name": "tiny-granite-hybrid", "source": "none", "reduced": [],
                  "file": "bench/configs/tiny-granite-hybrid.json", "why": "toy"}],
        workloads=[{"name": CELL, "config": "tiny-granite-hybrid",
                    "traffic": "tiny-serve-closed-granite", "chips": 1,
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
def test_the_family_runs_through_serve_closed(granite_root, trace):
    line = harness.run_cell(CELL, seed=2**31 + 5, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), root=granite_root,
                            allow_cpu=True)
    assert line["correct"] is True, line["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    check = line["notes"]["check"]
    assert check["rows"] == family.SERVE_CHECK_ROWS
    assert check["worst_logit_gap"] <= family.LOGIT_MARGIN
    assert check["argmax_agree"] >= family.AGREE_FLOOR
    # the fourth limit: the live server asked again for the checked rows
    # (16 rows through a 2-row bucket), a state held to its definition
    carried = check["carried"]
    assert carried["ok"] and carried["carried_error"] < 1e-5
    assert carried["positions"] == 200 + 8 - 1 and carried["ids_as_served"] == 1.0
    names = set(line["metrics"])
    if trace:
        # a CPU trace has no device plane: every reader of one has nothing to
        # read and leaves its metric out; the memory counter reads 0 here; the
        # host's clock and the server's spans read as on the chip
        server = {"window_tokens_per_s.granite", "server_block_ms.granite"}
        assert server <= names <= server | {"peak_hbm_gb.granite"}
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    json.dumps(line)


def test_the_real_cell_resolves_to_the_family_and_its_readers():
    cell = real_cell()
    assert cell.family is family and cell.chips == 1
    assert cell.driver.__name__ == "benchmarks.drivers.serve_closed"
    assert cell.end_to_end == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == METRICS
    for m in cell.per_layer:
        assert hasattr(harness.load_module("readers", m["reader"]), "read"), m
        assert m["moves"] == "serve_tokens_per_s" and m["workloads"] == [REAL_CELL]
    t, c = cell.traffic, cell.config
    assert (t["rows"], t["prompt"], t["new_tokens"], t["workers"], t["callers"],
            t["buckets"], t["distinct_prompts"], t["max_wait_ms"],
            t["queue_size"], t["trace_seconds"]) == (
        32, 2048, 256, 1, 2, [32], 4, 5, 64, 18.0)
    assert set(c) >= {"published", "deployment", "equations", "assumed",
                      "departures", "run", "memory", "reduced", "layer_indices"}
    assert c["run"]["dtype"] == "bfloat16"
    assert c["run"]["state_dtype"] == c["run"]["router_dtype"] == "float32"
    assert c["run"]["chunk"] % c["mamba_chunk_size"] == 0
    assert c["deployment"]["chips_sharing_a_layer"] == 2
    assert c["deployment"]["stages"] == 4
    # arguments + temporaries of the rehearsal, under the 14.5 GB of ISSUE 49
    m = c["memory"]
    assert (m["generator_weights_bytes"]
            + m["generator_rows_32_temporaries_bytes"]) < 14.5e9


def test_every_new_entry_equals_its_file():
    """What ``BENCHMARK.json`` says of a ``.granite`` metric is what its file
    says, and the entries are the last of their lists."""
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in doc["per_layer"] if m["name"].endswith(".granite")]
    assert mine == doc["per_layer"][-len(METRICS):]
    assert len(doc["per_layer"]) <= 128
    for m in mine:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves", "workloads")} == {
            k: v for k, v in m.items() if k != "name"}, m["name"]
    assert doc["workloads"][-1]["name"] == REAL_CELL
    assert doc["configs"][-1]["name"] == "granite-4.0-h-small-ep2"
    (tokens,) = [m for m in doc["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"][-1] == REAL_CELL
    assert len(doc["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_config_file_keeps_every_catalogued_number():
    """Every key of the catalog's ``config`` under the same name and value but
    the three reduced ones, which hold what is held here."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    c = real_cell().config
    assert {k: c[k] for k in published} == published
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert c["layer_types"] == period * 4
    assert (c["num_hidden_layers"], c["num_local_experts"], c["vocab_size"]
            ) == (10, 36, 50176)
    assert c["published"]["num_hidden_layers"] == 40
    assert c["published"]["num_local_experts"] == 72
    assert c["published"]["vocab_size"] == 100352
    assert c["layer_indices"] == list(range(10))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [e for e in doc["configs"]
                if e["name"] == "granite-4.0-h-small-ep2"]
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert entry["source"] == c["source"]
    for key in ("head_dim", "in_proj_order", "convolution",
                "recurrence_parameters", "gated_norm", "routing",
                "expert_input_matrix", "intermediate_size", "weights"):
        assert c["assumed"][key]


def test_the_weights_are_seeded_and_the_same_for_export_and_check():
    cfg = tiny_config()
    w = family.decoder_params(cfg, 5, 200, 8)
    host = w.host_params()
    again = family.decoder_params(cfg, 5, 200, 8)
    mixer = again.reference_mixer(0, "mamba")
    assert np.array_equal(host["layer_0/mixer/in/w"], np.asarray(mixer["in_proj"]))
    assert np.array_equal(host["layer_0/mixer/a_log"], np.asarray(mixer["a_log"]))
    gate, up, down = again.reference_expert(1, 2)
    assert np.array_equal(host["layer_1/experts/up/w"][2], np.asarray(up))
    assert np.array_equal(host["layer_1/experts/down/w"][2], np.asarray(down))
    other = family.decoder_params(cfg, 6, 200, 8).host_params()
    assert not np.array_equal(host["layer_2/mixer/q/w"], other["layer_2/mixer/q/w"])
    # Mamba-2's published initialisation
    a = np.exp(host["layer_1/mixer/a_log"])
    assert a.shape == (4,) and family.A_MIN <= a.min() <= a.max() <= family.A_MAX
    dt = np.log1p(np.exp(host["layer_1/mixer/dt/b"]))
    assert family.DT_MIN * 0.99 < dt.min() <= dt.max() < family.DT_MAX * 1.01
    assert (host["layer_1/mixer/d"] == 1).all()
    taps = host["layer_1/mixer/conv/w"]
    assert taps.shape == (4, 128 + 32) and -0.5 <= taps.min() and taps.max() <= 0.5
    # the scales assumed.weights names: [z | x | B | C | dt] = 128 + 128 + 16 + 16 + 4
    w_in = host["layer_0/mixer/in/w"].astype(np.float64)
    assert w_in.shape == (64, 292)
    assert 0.8 < w_in[:, :256].std() * 8 < 1.2
    assert 0.8 < w_in[:, 256:288].std() * 8 / family.BC_GAIN < 1.2
    assert 0.6 < w_in[:, 288:].std() * 8 < 1.4
    assert 0.8 < host["layer_0/mixer/out/w"].std() * 128 ** 0.5 / family.MAMBA_OUT_GAIN < 1.2
    assert 0.8 < host["layer_2/mixer/o/w"].std() * 8 / family.ATTN_OUT_GAIN < 1.2
    assert 0.8 < host["layer_2/mixer/q/w"].std() * 8 / family.QK_GAIN < 1.2
    assert 0.8 < host["layer_2/mixer/v/w"].std() * 8 < 1.2
    assert 0.8 < host["layer_3/experts/down/w"].std() * 32 ** 0.5 / family.EXPERT_DOWN_GAIN < 1.2
    assert 0.8 < host["layer_3/shared/down/w"].std() * 48 ** 0.5 / family.SHARED_DOWN_GAIN < 1.2
    assert 0.8 < host[family.EMBEDDING].std() / family.EMBED_STD < 1.2
    assert (host[family.FINAL] == 1).all()


# -- the counts' arithmetic: ISSUE 49's table ---------------------------------------


def test_the_counts_are_issue_49s():
    c = real_cell().config
    counts = family._counts(c)
    table = family.decoder_params(c, 0, 2048, 256).shapes
    held = sum(int(np.prod(s.shape)) for s in table.values())
    assert abs(held - 4.757e9) < 0.003e9                     # 4.757B parameters
    bytes_held = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                     for s in table.values())
    assert abs(bytes_held - 9.52e9) < 0.02e9                 # 9.51 GB + float32 parts
    assert abs(counts["mamba"] - 102.2e6) < 0.2e6
    assert counts["attention"] == 41943040 and counts["expert"] == 9437184
    assert abs(family.experts_touched(c, 32) - 35.7) < 0.05  # 1 - (62/72)^32
    # a step at 32 rows: 12.2 GB by ISSUE 49's count
    step = family.decode_step_bytes(c, 32, 2047 + 128)
    assert 11.9e9 < step < 12.5e9
    assert 2 * 9 * family.state_bytes(c, 32) > 2.41e9        # state read and written
    # the prefill: 153 TFLOP outside the experts and 62 inside them
    inside = 2.0 * 10 * counts["expert"] * 10 * 36 / 72 * 32 * 2048
    whole = family.prefill_flops(c, 32, 2048)
    assert abs(inside - 62e12) < 1e12
    assert abs(whole - inside - 153e12) < 6e12
    # ssd_fwd: 36 calls at pieces of 256... the run's own chunk
    ops, moved, calls = family.kernel_counts(c, 32, 2048, "ssd_fwd")
    assert calls == 9 * (2048 // c["run"]["chunk"])
    assert ops == 9 * family.ssd_flops(c, 32, 2048)
    # a chunk of 256: C.B over 32,896 pairs, a head's pairs x 64 and two
    # 256 x 128 x 64 products, two operations a multiply-add
    per_chunk = 2.0 * (32896 * 128 + 128 * (32896 * 64 + 2 * 256 * 128 * 64))
    assert ops == 9 * 32 * 8 * per_chunk
    assert moved > 9 * 32 * 2048 * 2 * 2 * 8192              # u and y alone
    f_ops, f_moved, f_calls = family.kernel_counts(c, 32, 2048, "flash_fwd")
    assert f_calls == 2048 // c["run"]["chunk"]
    assert f_ops == 2.0 * 32 * 32 * (2048 * 2049 / 2) * 256
    assert family.kernel_counts(c, 32, 2048, "mamba_fwd") is None


def test_every_fault_of_the_sensitivity_run_reaches_the_check():
    """``tools/granite_sensitivity.py``'s nine faults at the toy size: each
    program fault gives the generator another carried state than the sound
    one has, each reference fault gives the check another reading of the
    sound ids (whether a limit then fails is read at the published widths, on
    the chip); the wrapped names are the sound ones again afterwards."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import granite_hybrid as reference
    from benchmarks.tools import granite_sensitivity as tool
    from paddle_tpu.layers import mamba2
    from paddle_tpu.parallel import moe

    cfg, new = tiny_config(), 6
    weights = family.decoder_params(cfg, 5, 200, new)
    params = jax.tree.map(jnp.asarray, weights.host_params())
    (prompt,) = family.prompts(cfg["vocab_size"], 2, 200, 5, 1)
    names = [(mamba2, "mamba2_prefill"), (mamba2, "mamba2_decode"),
             (reference, "mamba"), (reference, "route"), (reference, "attention"),
             (moe, "moe_held"), (moe, "GATHER_TOKENS")]
    sound_names = [getattr(m, n) for m, n in names]
    with jax.default_matmul_precision("highest"):
        run = lambda *how: {k: np.asarray(v) for k, v in tool.generate(
            family, cfg, new, *how)(params, prompt).items()}
        sound = run()
        check = lambda **kw: family.served_check(cfg, weights, prompt,
                                                 sound["ids"], audit=sound, **kw)
        good = check()
        assert good["carried"]["ok"]
        program = tool.program_faults()
        assert set(program) == {"state_in_bfloat16",
                                "state_zeroed_between_pieces"}
        for name, how in program.items():
            faulty = run(*how)
            assert np.abs(faulty["audit_state"] - sound["audit_state"]).max() > 1e-4
            carried = family.carried_check(
                faulty, family.audited_a_log(cfg, weights), cfg["mamba_d_head"])
            assert carried["carried_error"] > 100 * good["carried"]["carried_error"]
        # the other walks of the expert pairs are no faults: the same ids
        for name, how in tool.program_forms().items():
            other = run(*how)
            assert (other["ids"] == sound["ids"]).all(), name
            np.testing.assert_allclose(other["audit_state"], sound["audit_state"],
                                       rtol=1e-4, atol=1e-6)
        faults = tool.reference_faults()
        assert len(faults) == 7
        for name, (wrappers, edit) in faults.items():
            with tool.faulted(reference, wrappers):
                got = check(edit=edit)
            assert abs(got["logit_std"] - good["logit_std"]) > 1e-7 or (
                got["mean_logit_gap"] != good["mean_logit_gap"]), name
    assert [getattr(m, n) for m, n in names] == sound_names
