"""The ``brumby`` family and the reader that came with it, on the CPU: the
family end to end at a toy size through the ``serve_closed`` driver (its own
throw-away root), the sensitivity tool's faults at the toy size, the readers
of the cell on a small synthetic trace, and the files of the real cell. (The
family's counts are held to ISSUE 39's table in ``tests/test_brumby.py``.)"""

import json
import os
import shutil
import time

import pytest

from benchmarks import harness
from benchmarks.families import brumby as family
from benchmarks.readers import (decode_split, kernel_roofline,
                                kernel_time_share, step_kernel_roofline)
from benchmarks.trace_reduce import Trace

from conftest import BENCH, HERE, ROOT

CELL = "tiny-brumby-serve-decode"
REAL_CELL = "brumby-serve-decode"
MS = 1_000_000
BRUMBY_METRICS = {
    "compiles_in_window.brumby", "device_idle_share.brumby",
    "peak_hbm_gb.brumby", "window_tokens_per_s.brumby",
    "server_block_ms.brumby", "prefill_ms.brumby", "decode_step_ms.brumby",
    "prefill_mfu.brumby", "decode_step_hbm_share.brumby",
    "retention_fwd_roofline.brumby", "retention_step_roofline.brumby",
    "retention_time_share.brumby", "mixer_time_share.brumby",
    "state_bytes_share.brumby", "unscoped_time_share.brumby",
    "inherited_time_share.brumby"}


def tiny_config():
    return json.load(open(os.path.join(HERE, "data", "tiny-brumby.json")))


@pytest.fixture
def brumby_root(tmp_path):
    """A root with one cell: the toy configuration and traffic of
    ``tests/data`` under the real cell's metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    for kind, name in (("configs", "tiny-brumby"),
                       ("traffic", "tiny-serve-closed-brumby")):
        (bench / kind).mkdir()
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    bench / kind / (name + ".json"))
    doc = dict(
        real,
        configs=[{"name": "tiny-brumby", "source": "none", "reduced": [],
                  "file": "bench/configs/tiny-brumby.json", "why": "toy"}],
        workloads=[{"name": CELL, "config": "tiny-brumby",
                    "traffic": "tiny-serve-closed-brumby", "chips": 1,
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
def test_the_family_runs_through_serve_closed(brumby_root, trace):
    line = harness.run_cell(CELL, seed=2**31 + 5, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), root=brumby_root,
                            allow_cpu=True)
    assert line["correct"] is True, line["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    check = line["notes"]["check"]
    assert check["rows"] == family.SERVE_CHECK_ROWS
    assert check["worst_logit_gap"] <= family.LOGIT_MARGIN
    assert check["argmax_agree"] >= family.AGREE_FLOOR
    # the fourth limit: the live server asked again for the checked rows
    # (16 rows through a 2-row bucket), its states held to the definition
    carried = check["carried"]
    assert carried["ok"] and carried["carried_error"] < family.CARRIED_ERROR_LIMIT
    assert carried["positions"] == 300 + 8 - 1 and carried["ids_as_served"] == 1.0
    names = set(line["metrics"])
    if trace:
        # what needs no device plane (a CPU trace has none: idle reads 100%);
        # the rest have nothing to read and leave their metric out
        assert names == {"compiles_in_window.brumby", "window_tokens_per_s.brumby",
                         "server_block_ms.brumby", "device_idle_share.brumby",
                         "state_bytes_share.brumby"}
        assert line["metrics"]["compiles_in_window.brumby"]["value"] == 0
        # nothing but states and their key sums is carried
        assert line["metrics"]["state_bytes_share.brumby"]["value"] == 100.0
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    json.dumps(line)


def test_the_real_cell_resolves_to_the_family_and_its_readers():
    cell = real_cell()
    assert cell.family is family and cell.chips == 1
    assert cell.driver.__name__ == "benchmarks.drivers.serve_closed"
    assert cell.end_to_end == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == BRUMBY_METRICS
    for m in cell.per_layer:
        assert hasattr(harness.load_module("readers", m["reader"]), "read"), m
        assert m["moves"] == "serve_tokens_per_s" and m["workloads"] == [REAL_CELL]
    t, c = cell.traffic, cell.config
    assert (t["rows"], t["prompt"], t["new_tokens"], t["workers"], t["callers"],
            t["buckets"], t["distinct_prompts"], t["max_wait_ms"],
            t["queue_size"]) == (16, 1024, 256, 1, 2, [16], 4, 5, 64)
    assert c["num_hidden_layers"] == 8 and c["layer_indices"] == list(range(16, 24))
    assert c["published"] == {"num_hidden_layers": 40}
    assert set(c) >= {"deployment", "equations", "assumed", "departures", "run",
                      "memory"}
    assert c["assumed"]["degree"] == 2 and c["run"]["state_dtype"] == "float32"
    # arguments + temporaries of the rehearsal, under ISSUE 39's bound
    m = c["memory"]
    assert (m["generator_weights_bytes"]
            + m["generator_rows_16_temporaries_bytes"]) < 14.5e9 and m["kv_bytes"] == 0


def test_the_config_file_keeps_every_catalogued_number():
    """Every key of the catalog's ``config`` under the same name and value,
    but ``num_hidden_layers`` (``reduced``)."""
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    c = real_cell().config
    assert {k: c[k] for k in published} == published
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [e for e in doc["configs"] if e["name"] == "brumby-14b-pp5"]
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == c["source"]


def test_the_weights_are_seeded_and_the_same_for_export_and_check():
    import numpy as np

    cfg = tiny_config()
    w = family.decoder_params(cfg, 5, 300, 8)
    host = w.host_params()
    name = "layer_17/mixer/qkv/w"       # q, k and v as one [out, in] matrix
    assert host[name].shape == (64 + 2 * 32, 64)
    again = family.decoder_params(cfg, 5, 300, 8)
    mixer = again.reference_mixer(1)    # held layer 1 is published layer 17
    assert np.array_equal(host[name][:64].T, np.asarray(mixer["q"]))
    assert np.array_equal(host[name][96:].T, np.asarray(mixer["v"]))
    assert mixer["k"].shape == (64, 32)                     # 2 key heads of 16
    assert np.array_equal(host["layer_16/ffn/up/w"],
                          np.asarray(again.reference_ffn(0)["ffn_up"]))
    other = family.decoder_params(cfg, 6, 300, 8).host_params()[name]
    assert not np.array_equal(host[name], other)
    assert 0.8 < host[name].std() * 8 < 1.2     # N(0, 1 / fan_in), fan_in 64
    # the gate as assumed.weights draws it: -log gamma within about 1/512 .. 1/32
    assert (host["layer_16/mixer/gate/b"] == family.GATE_BIAS).all()
    assert 0.8 < host["layer_16/mixer/gate/w"].std() * 8 / family.GATE_STD < 1.2
    assert 0.8 < host["layer_16/mixer/o/w"].std() * 8 / family.MIXER_OUT_GAIN < 1.2
    forgets = [np.log1p(np.exp(-(family.GATE_BIAS + s * family.GATE_STD)))
               for s in (2, -2)]
    assert 1 / 520 < forgets[0] < forgets[1] < 1 / 31     # two deviations either way


def test_every_fault_of_the_sensitivity_run_reaches_the_logits():
    """``tools/brumby_sensitivity.py``'s four faults of the program at the
    toy size: each gives the generator other distributions than the sound
    one has, after a prompt of a chunk and a tail and after a step (whether
    the check then fails is read at the published widths, on the chip); the
    layer's calls are the sound ones again afterwards."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from benchmarks.tools import brumby_sensitivity as tool
    from paddle_tpu.layers import retention as layer
    from paddle_tpu.models import brumby

    cfg = tiny_config()
    params = jax.tree.map(np.asarray,
                          family.decoder_params(cfg, 5, 300, 4).host_params())
    prompt = np.random.RandomState(1).randint(3, 503, (2, 300)).astype(np.int32)

    def two_distributions(fault):
        def fn(prompt_ids):
            state0, step_fn, _ = brumby._decoder(family.program_config(cfg),
                                                 prompt_ids, 4)
            tokens = prompt_ids[:, -1]
            _, state = step_fn(tokens, state0)
            return {"first": state0["logp0"], "next": step_fn(tokens, state)[0]}
        with tool.faulted(fault), jax.default_matmul_precision("highest"):
            out, _ = pt.build(fn).apply(params, {}, training=False,
                                        prompt_ids=prompt)
        return np.asarray(out["first"]), np.asarray(out["next"])

    sound_calls = (layer.retention, layer.retention_step)
    faults = tool.faults()
    sound = two_distributions(faults.pop("as_served"))
    assert len(faults) == 4
    for name, fault in faults.items():
        first, nxt = two_distributions(fault)
        assert np.abs(nxt - sound[1]).max() > 1e-3, name
        if name != "state_in_bfloat16":     # (a rounding of the state alone)
            assert np.abs(first - sound[0]).max() > 1e-3, name
    assert (layer.retention, layer.retention_step) == sound_calls


# -- the readers on a synthetic trace ---------------------------------------------


def synthetic(fwd_calls=32):
    """Two whole executions of ``jit_main`` and one cut by the window's end.
    In each: a prefill ``while.1`` of 1,000 ms (no conditional) with a
    request's 32 ``retention_fwd`` calls of 10 ms, then the decode loop
    ``while.2`` of 5,100 ms with its conditional and 64 ``retention_step``
    calls of 2 ms."""
    ops, modules, kernels = [], [], set()
    for t0 in (0, 7_000 * MS, 14_000 * MS):
        modules.append(("jit_main(1)", t0, 6_200 * MS))
        ops.append(("while.1 [while]", t0 + 10 * MS, 1_000 * MS))
        for n, name, dur, start in ((fwd_calls, "retention_fwd", 10, t0 + 20 * MS),
                                    (64, "retention_step", 2, t0 + 1_060 * MS)):
            for i in range(n):
                label = f"{name}.{i} [custom-call]"
                ops.append((label, start + i * (dur + 1) * MS, dur * MS))
                kernels.add(label)
        ops += [("while.2 [while]", t0 + 1_050 * MS, 5_100 * MS),
                ("conditional.4 [conditional]", t0 + 1_051 * MS, 9 * MS)]
    return Trace(ops={0: ops}, modules={0: modules}, kernels=sorted(kernels),
                 host=[], window=(0, 18_000 * MS))


def reading(reader, args, trace=synthetic, peaks=True, cell=None):
    cell = cell or real_cell()
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None)
    if peaks:
        run.peaks = harness.peaks_for("TPU v5 lite")
    obs = harness.Observed(True, 2, 0, {"rows": 16, "prompt": 1024,
                                        "new_tokens": 256},
                           trace=trace() if trace else None)
    return reader.read(run, obs, {"args": args})


def test_decode_split_reads_the_prefill_and_the_steps():
    cfg = real_cell().config
    assert reading(decode_split, {"part": "decode_step_ms"}) == pytest.approx(20.0)
    assert reading(decode_split, {"part": "prefill_ms"}) == pytest.approx(1100.0)
    assert reading(decode_split, {"part": "decode_step_hbm_share"}) == pytest.approx(
        100 * family.decode_step_bytes(cfg, 16, 1024) / 0.020 / 819e9)
    assert reading(decode_split, {"part": "prefill_mfu"}) == pytest.approx(
        100 * family.prefill_flops(cfg, 16, 1024) / 1.1 / 197e12)


def test_the_two_rooflines_read_their_kernels():
    cfg = real_cell().config
    flops, moved, calls = family.kernel_counts(cfg, 16, 1024, "retention_fwd")
    assert calls == 32
    assert reading(kernel_roofline, {"kernel": "retention_fwd"}) == pytest.approx(
        100 * (flops / 197e12) / 0.320)
    # the step's kernel is not the chunked one's to count, nor the other way
    assert reading(kernel_roofline, {"kernel": "retention_step"}) is None
    assert reading(step_kernel_roofline, {"kernel": "retention_fwd"}) is None
    flops, moved = family.step_kernel_counts(cfg, 16, "retention_step")
    assert reading(step_kernel_roofline, {"kernel": "retention_step"}
                   ) == pytest.approx(100 * (moved / 819e9) / 0.002)
    # a request that lacks a chunked call is not whole for the one reader;
    # the other reads every call of a whole execution by itself
    short = lambda: synthetic(fwd_calls=31)
    assert reading(kernel_roofline, {"kernel": "retention_fwd"}, trace=short) is None
    assert reading(step_kernel_roofline, {"kernel": "retention_step"},
                   trace=short) == pytest.approx(100 * (moved / 819e9) / 0.002)
    # calls of the execution that the window's end cut are left out
    def cut_short():
        tr = synthetic()
        tr.ops[0] += [(f"retention_step.{900 + i} [custom-call]",
                       (17_000 + 50 * i) * MS, 40 * MS) for i in range(4)]
        return tr
    assert reading(step_kernel_roofline, {"kernel": "retention_step"},
                   trace=cut_short) == pytest.approx(100 * (moved / 819e9) / 0.002)
    assert reading(kernel_time_share, {"kernels": [
        "retention_fwd", "retention_step"]}) == pytest.approx(
        100 * 3 * (320 + 128) / (2 * 6200 + 4000))


@pytest.mark.parametrize("reader,args", [
    (step_kernel_roofline, {"kernel": "retention_step"}),
    (kernel_roofline, {"kernel": "retention_fwd"})])
def test_a_reader_with_nothing_to_read_returns_nothing(reader, args):
    empty = lambda: Trace(ops={0: []}, modules={0: []}, kernels=[], host=[],
                          window=(0, MS))
    assert reading(reader, args, trace=None) is None      # an untraced run
    assert reading(reader, args, trace=empty) is None     # nothing whole
    assert reading(reader, args, peaks=False) is None     # no peak, no share
    # another family's cell: no such count
    assert reading(reader, args, cell=harness.load_cell("sala-serve-long")) is None
