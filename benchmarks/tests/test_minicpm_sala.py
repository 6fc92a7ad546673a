"""The ``minicpm_sala`` family and the readers that came with it, on the
CPU: the family end to end at a toy size through the ``serve_closed`` driver
(its own throw-away root), each new reader on a small synthetic trace, and
the family's counts against values worked out by hand from the published
config."""

import json
import os
import shutil
import time

import pytest

from benchmarks import harness
from benchmarks.families import minicpm_sala as family
from benchmarks.readers import (decode_split, kernel_roofline,
                                kernel_time_share, plan_share)
from benchmarks.trace_reduce import Trace

from conftest import BENCH, HERE, ROOT

CELL = "tiny-sala-serve-long"
REAL_CELL = "sala-serve-long"
MS = 1_000_000
SALA_METRICS = {
    "compiles_in_window.sala", "device_idle_share.sala", "peak_hbm_gb.sala",
    "window_tokens_per_s.sala", "server_block_ms.sala", "prefill_ms.sala",
    "decode_step_ms.sala", "prefill_mfu.sala", "decode_step_hbm_share.sala",
    "sparse_fwd_roofline.sala", "lightning_fwd_roofline.sala",
    "mixer_time_share.sala", "state_bytes_share.sala"}


@pytest.fixture
def sala_root(tmp_path):
    """A root with one cell: the toy configuration and traffic of
    ``tests/data`` under the real cell's metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    for kind, name in (("configs", "tiny-minicpm-sala"),
                       ("traffic", "tiny-serve-closed-sala")):
        (bench / kind).mkdir()
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    bench / kind / (name + ".json"))
    doc = dict(
        real,
        configs=[{"name": "tiny-minicpm-sala", "source": "none", "reduced": [],
                  "file": "bench/configs/tiny-minicpm-sala.json", "why": "toy"}],
        workloads=[{"name": CELL, "config": "tiny-minicpm-sala",
                    "traffic": "tiny-serve-closed-sala", "chips": 1,
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
def test_the_family_runs_through_serve_closed(sala_root, trace):
    line = harness.run_cell(CELL, seed=2**31 + 5, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), root=sala_root,
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
        assert names == {"compiles_in_window.sala", "window_tokens_per_s.sala",
                         "server_block_ms.sala", "device_idle_share.sala",
                         "state_bytes_share.sala"}
        assert line["metrics"]["compiles_in_window.sala"]["value"] == 0
        # 2 lightning states of [2, 4, 16, 16] float32 against 2 layers'
        # slabs of 448 keys: k, v and the 28 compressed keys, float32 here
        state, slabs = 2 * 2 * 4 * 16 * 16 * 4, 2 * 2 * (2 * 448 + 28) * 32 * 4
        assert line["metrics"]["state_bytes_share.sala"]["value"] == pytest.approx(
            100 * state / (state + slabs))
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}
    json.dumps(line)


def test_the_real_cell_resolves_to_the_family_and_its_readers():
    cell = real_cell()
    assert cell.family is family and cell.chips == 1
    assert cell.driver.__name__ == "benchmarks.drivers.serve_closed"
    assert cell.end_to_end == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == SALA_METRICS
    t, c = cell.traffic, cell.config
    assert (t["rows"], t["prompt"], t["new_tokens"], t["workers"], t["callers"],
            t["buckets"], t["distinct_prompts"]) == (2, 32768, 128, 1, 2, [2], 4)
    assert c["num_hidden_layers"] == 16 and c["layer_indices"] == list(range(7, 23))
    assert c["published"] == {"num_hidden_layers": 32}
    assert len(c["mixer_types"]) == 32      # the published list, whole
    kinds, _ = family.layers_of(c)
    assert "".join("S" if k == family.SPARSE else "L" for k in kinds) == \
        "LLSLLLLLLSSLLLLS"


def test_the_config_file_keeps_every_catalogued_number():
    """Every key of the catalog's ``config`` under the same name and value,
    but ``num_hidden_layers`` (``reduced``)."""
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
        "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_key_value_heads": 2, "qk_norm": True,
        "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
        "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
        "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    c = real_cell().config
    assert {k: c[k] for k in published} == published
    sparse_at = [i for i, k in enumerate(c["mixer_types"]) if k == family.SPARSE]
    assert sparse_at == [0, 9, 16, 17, 22, 29, 30, 31]


def test_the_weights_are_seeded_and_the_same_for_export_and_check():
    import numpy as np

    cfg = json.load(open(os.path.join(HERE, "data", "tiny-minicpm-sala.json")))
    w = family.decoder_params(cfg, 5, 384, 8)
    host = w.host_params()
    name = "layer_10/mixer/qkv/w"       # q, k and v as one [out, in] matrix
    assert host[name].shape == (3 * 64, 64)
    again = family.decoder_params(cfg, 5, 384, 8)
    # held layer 2 is published layer 10, a lightning layer
    mixer = again.reference_mixer(2)
    assert np.array_equal(host[name][:64].T, np.asarray(mixer["q"]))
    assert np.array_equal(host[name][128:].T, np.asarray(mixer["v"]))
    assert np.array_equal(host["layer_16/ffn/up/w"],
                          np.asarray(again.reference_ffn(3)["ffn_up"]))
    assert again.reference_mixer(1)["k"].shape == (64, 32)      # 2 key heads of 16
    assert set(mixer) - set(again.reference_mixer(1)) == {"o_norm"}
    other = family.decoder_params(cfg, 6, 384, 8).host_params()[name]
    assert not np.array_equal(host[name], other)
    assert 0.8 < host[name].std() * 8 < 1.2     # N(0, 1 / fan_in), fan_in 64


def test_every_fault_of_the_sensitivity_run_reaches_the_reference():
    """``tools/sala_sensitivity.py``'s six edits at the toy size: each gives
    the reference other logits than the sound one has (whether the check
    then fails is read at the published widths, on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.tools import sala_sensitivity

    cfg = json.load(open(os.path.join(HERE, "data", "tiny-minicpm-sala.json")))
    w = family.decoder_params(cfg, 5, 384, 4)
    # 900 tokens: a late query chooses 3 of 11 blocks, so the scorer decides
    ids = np.random.RandomState(1).randint(3, 503, 900).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        sound = family.reference_logits(cfg, w, ids, 896, 895, query_block=64)
        faults = sala_sensitivity.edits()
        assert faults.pop("as_served") is None and len(faults) == 6
        for name, edit in faults.items():
            off = family.reference_logits(cfg, w, ids, 896, 895, edit=edit,
                                          query_block=64)
            assert np.abs(off - sound).max() > 1e-3 * sound.std(), name


# -- the new readers on a synthetic trace -----------------------------------------


def synthetic(sparse_calls=32):
    """Two whole executions of ``jit_main`` and one cut by the window's
    end. In each: a prefill ``while.1`` of 7,000 ms (no conditional) with
    the kernels of a request (32 ``sparse_fwd`` of 10 ms, 96
    ``lightning_fwd`` of 1 ms, a ``flash_fwd`` that no count knows), then
    the decode loop ``while.2`` of 2,540 ms with its conditional."""
    ops, modules, kernels = [], [], set()
    for t0 in (0, 10_000 * MS, 20_000 * MS):
        modules.append(("jit_main(1)", t0, 9_600 * MS))
        ops.append(("while.1 [while]", t0 + 10 * MS, 7_000 * MS))
        at = t0 + 20 * MS
        for n, name, dur in ((sparse_calls, "sparse_fwd", 10), (96, "lightning_fwd", 1),
                             (1, "flash_fwd", 4)):
            for i in range(n):
                label = f"{name}.{i} [custom-call]"
                ops.append((label, at, dur * MS))
                kernels.add(label)
                at += (dur + 1) * MS
        ops += [("while.2 [while]", t0 + 7_050 * MS, 2_540 * MS),
                ("conditional.4 [conditional]", t0 + 7_051 * MS, 9 * MS)]
    return Trace(ops={0: ops}, modules={0: modules}, kernels=sorted(kernels),
                 host=[], window=(0, 25_000 * MS))


def reading(reader, args, trace=synthetic, peaks=True, cell=None):
    cell = cell or real_cell()
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None)
    if peaks:
        run.peaks = harness.peaks_for("TPU v5 lite")
    obs = harness.Observed(True, 2, 0, {"rows": 2, "prompt": 32768,
                                        "new_tokens": 128},
                           trace=trace() if trace else None)
    return reader.read(run, obs, {"args": args})


def test_decode_split_reads_the_chunked_prefill_and_the_steps():
    """The prefill's scan over chunks is a ``[while]`` without a
    conditional: the decode loop is the other one."""
    assert reading(decode_split, {"part": "decode_step_ms"}) == pytest.approx(20.0)
    assert reading(decode_split, {"part": "prefill_ms"}) == pytest.approx(7060.0)
    cfg = real_cell().config
    need = sum(family.decode_step_bytes(cfg, 2, 32768 + j) for j in range(127)) / 127
    assert reading(decode_split, {"part": "decode_step_hbm_share"}) == pytest.approx(
        100 * need / 0.020 / 819e9)
    assert reading(decode_split, {"part": "prefill_mfu"}) == pytest.approx(
        100 * family.prefill_flops(cfg, 2, 32768) / 7.06 / 197e12)


def test_kernel_roofline_is_the_larger_bound_over_whole_requests_calls():
    cfg = real_cell().config
    flops, moved, calls = family.kernel_counts(cfg, 2, 32768, "sparse_fwd")
    flops_s = flops
    assert calls == 32 and flops / 197e12 > moved / 819e9      # compute-bound
    assert reading(kernel_roofline, {"kernel": "sparse_fwd"}) == pytest.approx(
        100 * (flops / 197e12) / 0.320)
    flops, moved, calls = family.kernel_counts(cfg, 2, 32768, "lightning_fwd")
    least = max(flops / 197e12, moved / 819e9)
    assert calls == 96
    assert reading(kernel_roofline, {"kernel": "lightning_fwd"}) == pytest.approx(
        100 * least / 0.096)
    # a kernel the family does not count, and a trace that lacks a call
    assert reading(kernel_roofline, {"kernel": "flash_fwd"}) is None
    assert reading(kernel_roofline, {"kernel": "sparse_fwd"},
                   trace=lambda: synthetic(sparse_calls=31)) is None
    # an execution the trace's end cut short (a shorter module inside the
    # window, some of its calls) is left out, not counted as whole
    def cut_short():
        tr = synthetic()
        tr.modules[0].append(("jit_main(1)", 22_000 * MS, 900 * MS))
        tr.ops[0] += [(f"sparse_fwd.{i} [custom-call]", 22_010 * MS + 11 * i * MS,
                       10 * MS) for i in range(8)]
        return tr
    assert reading(kernel_roofline, {"kernel": "sparse_fwd"},
                   trace=cut_short) == pytest.approx(100 * (flops_s / 197e12) / 0.320)


def test_kernel_time_share_is_the_kernels_over_the_module():
    want = 100 * (3 * (320 + 96 + 4)) / (2 * 9600 + 5000)
    assert reading(kernel_time_share, {"kernels": [
        "sparse_fwd", "lightning_fwd", "flash_fwd"]}) == pytest.approx(want)
    assert reading(kernel_time_share, {"kernels": ["no_such_kernel"]}) is None


def test_plan_share_reads_the_newest_plan_and_nothing_without_one():
    from paddle_tpu.core import profiler

    args = {"span": "decode.plan", "part": "state_bytes",
            "of": ["kv_bytes", "index_bytes", "state_bytes"]}
    profiler.record_span("decode.plan", time.time_ns(), 0, cache_kind="kv",
                         cache_bytes=5)            # another family's: no such ids
    assert reading(plan_share, args) is None
    profiler.record_span("decode.plan", time.time_ns(), 0, kv_bytes=60,
                         index_bytes=15, state_bytes=25)
    assert reading(plan_share, args) == pytest.approx(25.0)
    assert reading(plan_share, dict(args, span="no.such.plan")) is None


@pytest.mark.parametrize("reader,args", [
    (kernel_roofline, {"kernel": "sparse_fwd"}),
    (kernel_roofline, {"kernel": "lightning_fwd"}),
    (kernel_time_share, {"kernels": ["sparse_fwd"]})])
def test_a_new_reader_with_nothing_to_read_returns_nothing(reader, args):
    empty = lambda: Trace(ops={0: []}, modules={0: []}, kernels=[], host=[],
                          window=(0, MS))
    assert reading(reader, args, trace=None) is None      # an untraced run
    assert reading(reader, args, trace=empty) is None     # nothing whole
    if reader is kernel_roofline:
        assert reading(reader, args, peaks=False) is None  # no peak, no share
        # another family's cell: no such count
        assert reading(reader, args,
                       cell=harness.load_cell("k25-serve-batch")) is None


# -- the family's arithmetic, by hand ------------------------------------------------


def test_counts_by_hand():
    """ISSUE 33's table: 253.8M parameters a sparse layer, 285.2M a
    lightning layer, 8.88 GFLOP of matrices a token; a request's prefill
    needs some 600 TFLOP, of which the mixers' own are a tenth; a step
    moves 9.6 GB (the embedding is read by row), nearly all of it weights."""
    cfg = real_cell().config
    c = family._counts(cfg)
    assert c[family.SPARSE] == 3 * 4096 * 4096 + 2 * 4096 * 256
    assert c[family.LIGHTNING] == 5 * 4096 * 4096 and c["ffn"] == 3 * 4096 * 16384
    per_token = 2 * (4 * (c[family.SPARSE] + c["ffn"])
                     + 12 * (c[family.LIGHTNING] + c["ffn"]))
    assert per_token == pytest.approx(8.875e9, rel=1e-3)
    # a query beyond the 64th block reads 63 blocks and its own up to itself
    keys = sum(i + 1 for i in range(4096)) + sum(
        63 * 64 + i % 64 + 1 for i in range(4096, 32768))
    sparse = 2 * 2 * 32 * keys * 256
    assert family.sparse_attention_flops(cfg, 2, 32768) == sparse
    assert sparse == pytest.approx(2 * 2.1e12, rel=0.05)   # 2.2 TFLOP a row, less the early queries
    kernels = sum(max((i + 1 - 32) // 16 + 1, 0) for i in range(32768))
    assert family.scorer_flops(cfg, 2, 32768) == 2 * 2 * 32 * kernels * 128
    light = 2 * 32768 * 32 * (2 * 128 * 257 + 4 * 128 * 128)
    assert family.lightning_flops(cfg, 2, 32768) == light
    flops = family.prefill_flops(cfg, 2, 32768)
    assert flops == pytest.approx(
        2 * 32768 * per_token + 4 * (sparse + family.scorer_flops(cfg, 2, 32768))
        + 12 * light + 2 * 2 * c["head"])
    assert flops == pytest.approx(605e12, rel=0.02)
    weights = per_token + 2 * c["head"]                 # 2 bytes a parameter
    step = family.decode_step_bytes(cfg, 2, 32800)
    sel = 2 * 512 * (2 * (63 * 64 + 32800 % 64 + 1) + (32801 - 32) // 16 + 1)
    state = 2 * 4 * 2 * 32 * 128 * 128
    assert step == pytest.approx(weights + 4 * sel + 12 * state)
    assert step == pytest.approx(9.62e9, rel=5e-3) and weights / step > 0.98
