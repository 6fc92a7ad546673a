"""The ``phi4_flash`` family on the CPU: the family end to end at a toy size
through the ``serve_closed`` driver (its own throw-away root), the
sensitivity tool's faults at the toy size, the readers of the cell on a
small synthetic trace, and the files of the real cell. (The family's counts
are held to ISSUE 41's reckoning in ``tests/test_phi4_flash.py``.)"""

import json
import os
import shutil
import time

import pytest

from benchmarks import harness
from benchmarks.families import phi4_flash as family
from benchmarks.readers import decode_split, kernel_roofline, kernel_time_share
from benchmarks.trace_reduce import Trace

from conftest import BENCH, HERE, ROOT

CELL = "tiny-phi4flash-serve-reason"
REAL_CELL = "phi4flash-serve-reason"
MS = 1_000_000
METRICS = {name + ".phi4f" for name in (
    "compiles_in_window", "device_idle_share", "peak_hbm_gb",
    "window_tokens_per_s", "server_block_ms", "prefill_ms", "decode_step_ms",
    "prefill_mfu", "decode_step_hbm_share", "unscoped_time_share",
    "inherited_time_share", "mamba_fwd_roofline", "swa_flash_roofline",
    "mamba_time_share", "shared_kv_time_share", "gmu_time_share",
    "mixer_time_share", "state_bytes_share")}


def tiny_config():
    return json.load(open(os.path.join(HERE, "data", "tiny-phi4-flash.json")))


@pytest.fixture
def phi4_root(tmp_path):
    """A root with one cell: the toy configuration and traffic of
    ``tests/data`` under the real cell's metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    for kind, name in (("configs", "tiny-phi4-flash"),
                       ("traffic", "tiny-serve-closed-phi4-flash")):
        (bench / kind).mkdir()
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    bench / kind / (name + ".json"))
    doc = dict(
        real,
        configs=[{"name": "tiny-phi4-flash", "source": "none", "reduced": [],
                  "file": "bench/configs/tiny-phi4-flash.json", "why": "toy"}],
        workloads=[{"name": CELL, "config": "tiny-phi4-flash",
                    "traffic": "tiny-serve-closed-phi4-flash", "chips": 1,
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
def test_the_family_runs_through_serve_closed(phi4_root, trace):
    line = harness.run_cell(CELL, seed=2**31 + 5, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), root=phi4_root,
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
    assert carried["ok"] and carried["carried_error"] < family.CARRIED_ERROR_LIMIT
    assert carried["positions"] == 200 + 8 - 1 and carried["ids_as_served"] == 1.0
    names = set(line["metrics"])
    if trace:
        # what needs no device plane (a CPU trace has none: idle reads 100%);
        # the rest have nothing to read and leave their metric out
        assert names == {"compiles_in_window.phi4f", "window_tokens_per_s.phi4f",
                         "server_block_ms.phi4f", "device_idle_share.phi4f",
                         "state_bytes_share.phi4f"}
        assert line["metrics"]["compiles_in_window.phi4f"]["value"] == 0
        assert 0 < line["metrics"]["state_bytes_share.phi4f"]["value"] < 100
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
                      "departures", "run", "memory"}
    assert c["assumed"]["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2,
                                     "dt_rank": "auto"}
    assert c["run"] == {"dtype": "bfloat16", "state_dtype": "float32",
                        "chunk": 512}
    # arguments + temporaries of the rehearsal, under ISSUE 41's bound
    m = c["memory"]
    assert (m["generator_weights_bytes"]
            + m["generator_rows_32_temporaries_bytes"]) < 14.5e9


def test_every_new_entry_equals_its_file():
    """What ``BENCHMARK.json`` says of a ``.phi4f`` metric is what its file
    says, and the entries are the last of their lists."""
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in doc["per_layer"] if m["name"].endswith(".phi4f")]
    assert mine == doc["per_layer"][-len(METRICS):]
    for m in mine:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves", "workloads")} == {
            k: v for k, v in m.items() if k != "name"}, m["name"]
    assert doc["workloads"][-1]["name"] == REAL_CELL
    assert doc["configs"][-1]["name"] == "phi4-mini-flash"
    (tokens,) = [m for m in doc["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"][-1] == REAL_CELL


def test_the_config_file_keeps_every_catalogued_number():
    """Every key of the catalog's ``config`` under the same name and value;
    nothing is reduced."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    c = real_cell().config
    assert {k: c[k] for k in published} == published
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [e for e in doc["configs"] if e["name"] == "phi4-mini-flash"]
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    for key in ("mamba_source", "head_pairing", "biases", "lambda_init",
                "weights"):
        assert c["assumed"][key]


def test_the_weights_are_seeded_and_the_same_for_export_and_check():
    import numpy as np

    cfg = tiny_config()
    w = family.decoder_params(cfg, 5, 200, 8)
    host = w.host_params()
    again = family.decoder_params(cfg, 5, 200, 8)
    mixer = again.reference_mixer(0)
    assert np.array_equal(host["layer_0/mixer/in/w"], np.asarray(mixer["in_proj"]))
    assert np.array_equal(host["layer_0/mixer/a_log"].T, np.asarray(mixer["a_log"]))
    ffn = again.reference_ffn(3)
    assert np.array_equal(host["layer_3/ffn/up/w"], np.asarray(ffn["gate_up"])[:, 96:])
    other = family.decoder_params(cfg, 6, 200, 8).host_params()
    assert not np.array_equal(host["layer_1/mixer/qkv/w"],
                              other["layer_1/mixer/qkv/w"])
    # Mamba's published initialisation
    a_log = host["layer_2/mixer/a_log"]
    assert a_log.shape == (16, 128)
    assert np.allclose(np.exp(a_log[:, 7]), np.arange(1, 17))
    dt = np.log1p(np.exp(host["layer_2/mixer/dt/b"]))
    assert family.DT_MIN * 0.99 < dt.min() < dt.max() < family.DT_MAX * 1.01
    assert (host["layer_2/mixer/d"] == 1).all()
    taps = host["layer_2/mixer/conv/w"]
    assert -0.5 <= taps.min() < -0.4 and 0.4 < taps.max() <= 0.5
    # the scales assumed.weights names
    x = host["layer_0/mixer/x/w"]           # [128, 4 + 32]: dt_rank 4
    assert 0.7 < x[:, :4].std() * 128 ** 0.5 < 1.3
    assert 0.8 < x[:, 4:].std() * 128 ** 0.5 / family.X_GAIN < 1.2
    assert 0.8 < host["layer_0/mixer/out/w"].std() * 128 ** 0.5 / family.MAMBA_OUT_GAIN < 1.2
    from benchmarks.reference import phi4_flash as reference
    want = family.ATTN_OUT / (1 - reference.lambda_init(7))
    assert 0.8 < host["layer_7/mixer/o/w"].std() * 8 / want < 1.2
    assert 0.8 < host[family.EMBEDDING].std() / family.EMBED_STD < 1.2


def test_every_fault_of_the_sensitivity_run_reaches_the_logits():
    """``tools/phi4_flash_sensitivity.py``'s six faults at the toy size:
    each gives the generator other distributions than the sound one has,
    after a prompt of two pieces and a tail, or after a step (whether the
    check then fails is read at the published widths, on the chip); the
    layer's names are the sound ones again afterwards."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from benchmarks.tools import phi4_flash_sensitivity as tool
    from paddle_tpu.layers import sambay as layer
    from paddle_tpu.models import phi4_flash

    # (K*, V*) longer than the 512 the fault leaves: a toy with a long prompt
    cfg = dict(tiny_config(), run={"dtype": "float32", "chunk": 320})
    p_len = 600
    params = jax.tree.map(np.asarray,
                          family.decoder_params(cfg, 5, p_len, 4).host_params())
    prompt = np.random.RandomState(1).randint(3, 503, (2, p_len)).astype(np.int32)

    def two_distributions(fault):
        wrappers, edit = fault

        def fn(prompt_ids):
            state0, step_fn, _ = phi4_flash._decoder(
                family.program_config({**cfg, **edit}), prompt_ids, 4)
            tokens = prompt_ids[:, -1]
            _, state = step_fn(tokens, state0)
            return {"first": state0["logp0"], "next": step_fn(tokens, state)[0]}
        with tool.faulted(wrappers), jax.default_matmul_precision("highest"):
            out, _ = pt.build(fn).apply(params, {}, training=False,
                                        prompt_ids=prompt)
        return np.asarray(out["first"]), np.asarray(out["next"])

    names = ("mamba_prefill", "cache_attention", "gmu", "_lambda",
             "selective_scan", "mamba_step")
    sound_calls = [getattr(layer, n) for n in names]
    faults = tool.faults()
    # the toy's window is 24: half of it, as the tool halves the real one
    faults["window_of_256"] = ({}, {"sliding_window": 12})
    sound = two_distributions(faults.pop("as_served"))
    assert len(faults) == 6
    for name, fault in faults.items():
        first, nxt = two_distributions(fault)
        floor = 1e-6 if name == "state_in_bfloat16" else 1e-3   # a rounding
        assert np.abs(nxt - sound[1]).max() > floor, name
        assert np.abs(first - sound[0]).max() > floor, name
    assert [getattr(layer, n) for n in names] == sound_calls


# -- the readers on a synthetic trace ---------------------------------------------


def synthetic(mamba_calls=36):
    """Two whole executions of ``jit_main`` and one cut by the window's end.
    In each: a prefill ``while.1`` of 1,000 ms (no conditional) with a
    request's 36 ``mamba_fwd`` calls of 5 ms and 32 ``flash_fwd`` calls of
    2 ms, then the decode loop ``while.2`` of 5,100 ms with its
    conditional."""
    ops, modules, kernels = [], [], set()
    for t0 in (0, 7_000 * MS, 14_000 * MS):
        modules.append(("jit_main(1)", t0, 6_200 * MS))
        ops.append(("while.1 [while]", t0 + 10 * MS, 1_000 * MS))
        for n, name, dur, start in ((mamba_calls, "mamba_fwd", 5, t0 + 20 * MS),
                                    (32, "flash_fwd", 2, t0 + 300 * MS)):
            for i in range(n):
                label = f"{name}.{i} [custom-call]"
                ops.append((label, start + i * (dur + 1) * MS, dur * MS))
                kernels.add(label)
        ops += [("while.2 [while]", t0 + 1_050 * MS, 5_100 * MS),
                ("conditional.4 [conditional]", t0 + 1_051 * MS, 9 * MS)]
    return Trace(ops={0: ops}, modules={0: modules}, kernels=sorted(kernels),
                 host=[], window=(0, 18_000 * MS))


def reading(reader, args, trace=synthetic, peaks=True):
    run = harness.Run(cell=real_cell(), seed=0, seconds=1.0, trace=True,
                      devices=[], t_start=0.0, compiles=None)
    if peaks:
        run.peaks = harness.peaks_for("TPU v5 lite")
    obs = harness.Observed(True, 2, 0, {"rows": 32, "prompt": 2048,
                                        "new_tokens": 256},
                           trace=trace() if trace else None)
    return reader.read(run, obs, {"args": args})


def test_decode_split_reads_the_prefill_and_the_steps():
    cfg = real_cell().config
    assert reading(decode_split, {"part": "decode_step_ms"}) == pytest.approx(20.0)
    assert reading(decode_split, {"part": "prefill_ms"}) == pytest.approx(1100.0)
    need = sum(family.decode_step_bytes(cfg, 32, 2048 + j) for j in range(255)) / 255
    assert reading(decode_split, {"part": "decode_step_hbm_share"}
                   ) == pytest.approx(100 * need / 0.020 / 819e9)
    assert reading(decode_split, {"part": "prefill_mfu"}) == pytest.approx(
        100 * family.prefill_flops(cfg, 32, 2048) / 1.1 / 197e12)


def test_the_two_rooflines_read_their_kernels():
    cfg = real_cell().config
    flops, moved, calls = family.kernel_counts(cfg, 32, 2048, "mamba_fwd")
    assert calls == 36 and moved / 819e9 > flops / 197e12       # the bytes bound it
    assert reading(kernel_roofline, {"kernel": "mamba_fwd"}) == pytest.approx(
        100 * (moved / 819e9) / 0.180)
    flops, moved, calls = family.kernel_counts(cfg, 32, 2048, "flash_fwd")
    assert calls == 32
    assert reading(kernel_roofline, {"kernel": "flash_fwd"}) == pytest.approx(
        100 * max(flops / 197e12, moved / 819e9) / 0.064)
    assert family.kernel_counts(cfg, 32, 2048, "retention_fwd") is None
    short = lambda: synthetic(mamba_calls=35)
    assert reading(kernel_roofline, {"kernel": "mamba_fwd"}, trace=short) is None
    assert reading(kernel_time_share, {"kernels": ["mamba_fwd", "flash_fwd"]}
                   ) == pytest.approx(100 * 3 * (180 + 64) / (2 * 6200 + 4000))


@pytest.mark.parametrize("kernel", ["mamba_fwd", "flash_fwd"])
def test_a_reader_with_nothing_to_read_returns_nothing(kernel):
    empty = lambda: Trace(ops={0: []}, modules={0: []}, kernels=[], host=[],
                          window=(0, MS))
    args = {"kernel": kernel}
    assert reading(kernel_roofline, args, trace=None) is None   # an untraced run
    assert reading(kernel_roofline, args, trace=empty) is None  # nothing whole
    assert reading(kernel_roofline, args, peaks=False) is None  # no peak, no share
