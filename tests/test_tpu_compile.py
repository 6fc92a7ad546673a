"""The flash kernels, the serve cells' generator and the tensor-parallel
block, compiled for a described TPU v5e with no chip attached (the
`on-chip-measurement` guide, section 2, third rehearsal).

Interpret mode — what every other flash test runs on the CPU — checks
none of what the chip's compiler refuses: block shapes that break the
(8, 128) tiling rule, kernels that need more than the 16 MB of scoped
VMEM. These cases are the shapes the zoo trains, the shapes the
benchmark's cells run (one chip's share of them) and the long-context
shapes, forward and backward. The generator's case reads what only the
chip's compiler decides about the KV cache: the layout and bytes of the
slabs the decode loop carries. The block's case reads the schedule of the
four-chip train cell's exchanges: whether each still has a matmul beside
it. A compile that passes is not a chip run; ``chip_smoke.py`` is.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import paddle_tpu as pt
from paddle_tpu import debugger
from paddle_tpu.core import config
from paddle_tpu.framework import mesh_mode
from paddle_tpu.layers import stacked
from paddle_tpu.models import gpt, kimi_k2
from paddle_tpu.ops import flash_attention as fa


@pytest.fixture(scope="module")
def chips():
    """The devices of a described v5e:2x2, with the persistent compile
    cache off: an entry written by such a compile cannot be read back
    without a chip, and the next run would warn about it."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 here: {type(e).__name__}: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(chips):
    return SingleDeviceSharding(chips[0])


def _attention(causal, mask=None):
    def fn(q, k, v, *rest):
        kw = {mask: rest[0]} if mask else {}
        return fa.flash_attention(q, k, v, causal=causal, interpret=False, **kw)
    return fn


def _packed(heads):
    # [b, s, h*d] operands, as a projection leaves them
    return lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                              interpret=False, num_heads=heads)


def _ring_backward(q, k, v, out, lse, g, delta):
    # the call parallel/ring_attention.py makes on every ring step: the
    # shard-invariant delta computed once outside and passed in (here
    # the zigzag schedule's half-shard of queries against a whole shard)
    return fa._flash_bwd(q, k, v, None, None, None, False, out, lse, g,
                         None, None, interpret=False, delta=delta)


# name -> (fn, (b, h, s, d) or (b, s, h*d), per-row mask operand or None)
SHAPES = {
    "gpt2_small": (_attention(True), (8, 12, 1024, 64), None),
    "transformer_base_key_bias": (_attention(False, "key_bias"),
                                  (32, 8, 256, 64), jnp.float32),
    "gpt2_small_segment_ids": (_attention(True, "segment_ids"),
                               (8, 12, 1024, 64), jnp.int32),
    "long_4k_d128": (_attention(True), (2, 8, 4096, 128), None),
    "long_32k": (_attention(True), (1, 8, 32768, 64), None),
    # the benchmark's cells: gpt2m-train-s1024, a dp2 x tp2 shard of
    # gpt2l-train-dp2tp2, and the serve cells' prefill (forward only)
    "gpt2m_train": (_attention(True), (32, 16, 1024, 64), None),
    "gpt2l_train_shard": (_attention(True), (16, 10, 1024, 64), None),
    "gpt2m_prefill": (_attention(True), (16, 16, 896, 64), None),
    # the same three in the projections' own layout, pairs of 64-wide heads
    # a 128-lane block (what layers/stacked.py hands the kernels)
    "gpt2m_train_packed": (_packed(16), (32, 1024, 1024), None),
    "gpt2l_train_shard_packed": (_packed(10), (16, 1024, 640), None),
    "gpt2m_prefill_packed": (_packed(16), (16, 896, 1024), None),
    # the longest calls that get the one backward kernel (``RESIDENT``
    # rows: q, k, v, dO, dq, dk, dv and three float32 accumulators of a
    # step in VMEM at once), 128-wide heads in both layouts, and no mask
    "resident_2k_d128": (_attention(True), (2, 8, 2048, 128), None),
    "resident_2k_d128_packed": (_packed(8), (2, 2048, 1024), None),
    "resident_2k_no_mask": (_attention(False), (2, 4, 2048, 64), None),
    # and the first the plan leaves to the pair: 256-wide heads at 1,536
    # rows, whose one kernel the compiler refuses for VMEM
    "resident_d256_split": (_attention(True), (4, 1, 1536, 256), None),
    # k25-serve-batch's prefill: latent attention scores over 192 (128
    # nope + 64 rope) and sums values 128 wide, under YaRN's softmax scale
    "k25_prefill": (lambda q, k, v: fa.flash_attention(
        q, k, v[..., :128], causal=True, interpret=False, scale=0.1147),
        (8, 64, 1984, 192), None),
}
FORWARD_ONLY = ("gpt2m_prefill", "gpt2m_prefill_packed", "k25_prefill")
# Every shape's gradient (which compiles its forward kernel too), and
# the forward alone where that is what runs: the tier is close to its
# time limit.
CASES = [("gpt2_small", False)] + [(name, name not in FORWARD_ONLY)
                                   for name in SHAPES]


@pytest.mark.parametrize("name,grad", CASES,
                         ids=[f"{n}-{'grad' if g else 'fwd'}" for n, g in CASES])
def test_flash_compiles_for_v5e(chip, name, grad):
    fn, shape, mask_dtype = SHAPES[name]
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
    args = [qkv, qkv, qkv]
    if mask_dtype is not None:
        args.append(jax.ShapeDtypeStruct((shape[0], shape[2]), mask_dtype,
                                         sharding=chip))
    if grad:
        fwd = fn
        fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*args).compile().as_text()
    # forward alone is one kernel; its gradient adds one backward kernel
    # where the sequence is resident, the dq and dkv passes where it streams
    rows = shape[2] if len(shape) == 4 else shape[1]
    backward = 2 if rows > fa.RESIDENT or name.endswith("_split") else 1
    assert text.count("tpu_custom_call") == (1 + backward if grad else 1)
    if name.endswith("_packed"):
        _assert_kernel_operands_lane_dense(text)


def test_ring_backward_with_delta_compiles_for_v5e(chip):
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    q = sds((1, 8, 4096, 64))
    kv = sds((1, 8, 8192, 64))
    row = sds((1, 8, 4096), jnp.float32)
    text = jax.jit(_ring_backward).lower(q, kv, kv, q, row, q,
                                         row).compile().as_text()
    assert text.count("tpu_custom_call") == 2


def test_generator_cache_is_lane_dense_for_v5e(chip, monkeypatch):
    """gpt2-medium's generator at the serve cells' shape (16 rows, prompt
    896 + 128 new, bfloat16): the decode loop carries its 48 cache slabs
    as ``bf16[16,1024,1024]`` with the model width minor, which (8, 128)
    tiles hold without padding (33.5 MB each; ``[rows, h, T, hd]`` with
    hd = 64 was held at 67 MB), no step copies a slab, and the
    temporaries stay under 4.2 GB (5.31 GB with the padded cache)."""
    rows, prompt, new = 16, 896, 128
    cfg = gpt.base_config(vocab_size=50257, max_len=1024, d_model=1024,
                          d_inner=4096, num_heads=16, num_layers=24,
                          use_flash=True, fused_ce=True, dtype="bfloat16")
    prog = pt.build(gpt.make_generator(cfg, max_new_tokens=new))
    before = config.get_flag("default_compute_dtype")
    config.set_flag("default_compute_dtype", "bfloat16")
    # the flash kernel asks jax.devices() whether to interpret: a described
    # chip is not attached, so the test steers it (SKILL.md), not an option
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    try:
        one_row = np.zeros((1, prompt), np.int32)
        shapes = jax.eval_shape(
            lambda key: prog.init(key, prompt_ids=one_row)[0],
            jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            shapes)
        ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
        compiled = jax.jit(
            lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]["ids"]
        ).lower(params, ids).compile()
    finally:
        config.set_flag("default_compute_dtype", before)

    assert compiled.memory_analysis().temp_size_in_bytes < 4.2e9
    slab = re.escape("bf16[%d,%d,%d]" % (rows, prompt + new, cfg.d_model))
    step = [ln for ln in compiled.as_text().splitlines()
            if "decode_step/" in ln]
    # every in-place write of the step produces a dense slab: the minor
    # dimension is the last logical one (1024 = 8 x 128 lanes), tiled (8, 128)
    writes = [ln for ln in step if re.search(
        r"= %s\{2,1,0:T\(8,128\)\(2,1\)\} (fusion|dynamic-update-slice)\("
        % slab, ln)]
    assert len(writes) == 2 * cfg.num_layers, len(writes)
    moved = [ln for ln in step if re.search(
        r"= %s\S* (copy|transpose|copy-start)\(" % slab, ln)]
    assert not moved, moved[0][:300]


def _k25_generator(chip, monkeypatch, layers, served=False):
    """The ``k25-serve-batch`` generator (8 rows, prompt 1984 + 64 new,
    bfloat16, the benchmark's configuration file at ``layers`` of its
    depth) compiled for one described chip. ``served``: the parameters in
    the row-major layout a served array arrives in; left free, a described
    compile chooses each parameter's layout itself, and a copy the chip
    makes in the decode loop moves out of sight into that choice."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.families import kimi_k2 as family

    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi-k2.5-ep32.json")) as f:
        cell_config = dict(json.load(f), num_hidden_layers=layers)
    rows, prompt, new = 8, 1984, 64
    prog = pt.build(kimi_k2.make_generator(family.program_config(cell_config),
                                           max_new_tokens=new))
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    one_row = np.zeros((1, prompt), np.int32)
    shapes = jax.eval_shape(lambda key: prog.init(key, prompt_ids=one_row)[0],
                            jax.random.PRNGKey(0))

    def held(s):
        if not served:
            return chip
        from jax.experimental.layout import Format, Layout
        return Format(Layout(major_to_minor=tuple(range(len(s.shape)))), chip)

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=held(s)),
        shapes)
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]["ids"]
                       ).lower(params, ids).compile()
    return cell_config, compiled


def test_k25_latent_cache_is_lane_dense_for_v5e(chip, monkeypatch):
    """Two expert layers suffice for layouts: the decode loop carries each
    layer's latents as ``bf16[8,2048,512]`` and its rotary keys as
    ``bf16[8,64,2048]``, minor dimension last and a multiple of 128, tiled
    (8, 128) with nothing padded (one ``[8,2048,576]`` slab would be held at
    640 lanes); no step copies a slab; no bank of routed experts is sliced
    or copied (every layer reads its experts in place through the grouped
    product's group sizes); flash and the grouped products are kernels."""
    _, compiled = _k25_generator(chip, monkeypatch, layers=3)
    text = compiled.as_text()
    # (the loop's own lines and its carried tuple: since PR 43 the prefill
    # pads a prompt's latents to 2,048 rows, which is a slab's shape too)
    loop = "\n".join(ln for ln in text.splitlines()
                     if "decode_step" in ln or " while(" in ln)
    slabs = set(re.findall(r"bf16\[8,(?:2048,512|64,2048)\]\{[^}]*\}", loop))
    assert slabs and all(s.split("{")[1].startswith("2,1,0:T(8,128)(2,1)")
                         for s in slabs), slabs
    assert not re.search(r"bf16\[8,2048,(576|640)\]", text)
    step = [ln for ln in text.splitlines() if "decode_step" in ln]
    assert step
    copies = [ln for ln in step if re.search(
        r"= bf16\[8,(2048,512|64,2048)\]\S* (copy|transpose)\(", ln)]
    assert not copies, copies[:2]
    banks = [ln for ln in text.splitlines() if re.search(
        r"= bf16\[(12|24),(7168,2048|2048,7168)\]\S* "
        r"(copy|slice|dynamic-slice|copy-start)\(", ln)]
    assert not banks, banks[:2]
    assert "ragged-dot" in text and text.count("tpu_custom_call") >= 6


def _array_bytes(shape_text):
    """Bytes of the largest array in an instruction's result type
    (``bf16[1,1536,12288]{...}``, or a tuple of such)."""
    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    return max([width.get(t, 4) * int(np.prod([int(n) for n in dims.split(",")
                                              if n]))
                for t, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape_text)]
               or [0])


def _copies_in(text, scope, least):
    """The instructions under ``scope`` (a component of the ``op_name``)
    that write an array of ``least`` bytes or more: copies and transposes,
    and fusions that are more than a view (a fusion of bitcasts alone
    writes nothing; one of slices copies what it cuts, as the parent's
    ``fusion.738`` did with every layer's ``q_b``; a ``reshape`` the
    compiler left standing changes tiles, as the ``[H, 32, 2, q_lora]``
    view of the rotary half did). Plain slices of a stack's leading axis
    and bitcasts are views and are not listed."""
    bodies = {name: body for name, body in re.findall(
        r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S)}
    fused = set(re.findall(r" fusion\(.*?calls=%([^,\s)]+)", text))

    def view(called):
        ops = re.findall(r"= \S+ ([\w-]+)\(", bodies.get(called, ""))
        return bool(ops) and set(ops) <= {"parameter", "bitcast"}

    found = []
    for inside, body in bodies.items():
        # a fusion's own instructions write nothing; a computation is
        # under the scope if any of its instructions names it, and then so
        # are those of its instructions that carry no name at all
        if inside in fused or scope not in body:
            continue
        for ln in body.splitlines():
            m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", ln)
            if not m or _array_bytes(m.group(2)) < least or (
                    scope not in ln and "op_name=" in ln):
                continue
            name, result, op = m.groups()
            called = re.search(r"calls=%([^,\s)]+)", ln)
            if op in ("copy", "transpose", "reshape") or (
                    op == "fusion" and not view(called.group(1))):
                found.append((name, op, result[:60]))
    return found


def test_k25_step_reads_q_b_as_held_and_prefill_builds_no_192_for_v5e(
        chip, monkeypatch):
    """With the parameters as a served array arrives (the free compile
    would pass on the parent too): in the decode loop nothing of 12 MB or
    more is written but the latent slabs' in-place updates, so each of
    ``q_b``'s regrouped halves (25.2 and 12.6 MB a layer) is read as it is
    held and the rotary half's 64-wide head view brings no copy back. In
    the prefill nothing is 192 wide (or 256 from it), ``flash_fwd`` takes
    ``[b, s, H * 128]`` three times, ``[b, s, H * 64]`` and ``[b, s, 64]``
    and writes ``[b, s, H * 128]``, and ``k_rope`` is broadcast to no
    head."""
    _, compiled = _k25_generator(chip, monkeypatch, layers=3, served=True)
    text = compiled.as_text()
    written = [c for c in _copies_in(text, "decode_step", 12e6)
               if "bf16[8,2048,512]" not in c[2]]
    assert not written, written[:4]
    assert _copies_in(text, "regroup", 12e6)      # made once, outside
    # no activation (8 rows lead) is 192 wide; the one view of ``q_b``'s
    # published columns a head at a time is the regrouping's, once
    assert not re.search(r"\[8,[\d,]*,(192|256)\]", text)
    assert all("regroup" in ln or "op_name" not in ln
               for ln in text.splitlines() if ",64,192]" in ln)
    assert not re.search(r"bf16\[8,64,(1984|2048),64\]", text)
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "flash_fwd" in ln]
    assert len(calls) == 2      # the dense layer's and the scan body's
    for ln in calls:
        taken = ln.split("operand_layout_constraints={")[1].split("}}")[0]
        assert re.findall(r"\w+\[[\d,]*\]", taken) == [
            "bf16[8,2048,8192]"] * 3 + ["bf16[8,2048,4096]", "bf16[8,2048,64]"]
        assert re.search(r"= \(bf16\[8,2048,8192\]\{", ln)


def test_k25_generator_fits_one_v5e(chip, monkeypatch):
    """The whole cut (1 dense + 5 expert layers, 12 experts a layer, 20480
    rows of the vocabulary): 8.37 GB of arguments and 2.47 GB of
    temporaries, under 14.5 GB together; the configuration file's ``memory``
    group records what this compile said (2.51 GB of temporaries since
    PR 43: 0.33 GB of regrouped projections come, the prefill's 537 MB
    operands go; within the 0.15 GB the file's figure is held to)."""
    cell_config, compiled = _k25_generator(chip, monkeypatch, layers=6)
    m = compiled.memory_analysis()
    assert 8.3e9 < m.argument_size_in_bytes < 8.45e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9
    recorded = cell_config["memory"]
    assert abs(recorded["generator_weights_bytes"]
               - m.argument_size_in_bytes) < 1e6
    assert abs(recorded["generator_rows_8_temporaries_bytes"]
               - m.temp_size_in_bytes) < 0.15e9


def _sala_generator(chip, monkeypatch, layers):
    """The ``sala-serve-long`` generator (2 rows, prompt 32,768 + 128 new,
    bfloat16, the benchmark's configuration file at the first ``layers`` of
    its stage) compiled for one described chip."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.families import minicpm_sala as family
    from paddle_tpu.models import minicpm_sala
    from paddle_tpu.ops import block_select, lightning_attention
    from paddle_tpu.ops import sparse_attention

    with open(os.path.join(root, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        cell_config = json.load(f)
    cell_config = dict(cell_config, num_hidden_layers=layers,
                       layer_indices=cell_config["layer_indices"][:layers])
    rows, prompt, new = 2, 32768, 128
    prog = pt.build(minicpm_sala.make_generator(
        family.program_config(cell_config), max_new_tokens=new))
    for module in (fa, sparse_attention, lightning_attention, block_select):
        monkeypatch.setattr(module, "default_interpret", lambda: False)
    one_row = np.zeros((1, prompt), np.int32)
    shapes = jax.eval_shape(lambda key: prog.init(key, prompt_ids=one_row)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]["ids"]
                       ).lower(params, ids).compile()
    return cell_config, compiled


def _decode_step_instructions(text):
    """The instructions of the decode loop's body and of every computation
    it calls (a conditional's branch, a fusion, a nested loop is a
    computation of its own). The decode loop is the ``while`` whose body
    holds the head's conditional; the prefill's scan holds the kernels."""
    from paddle_tpu.profiling import fusion

    comps = fusion.parse_hlo_module(text)
    called = lambda ins: (fusion._referenced(ins, "absorb")
                          + fusion._referenced(ins, "control"))
    (decode,) = {name for c in comps.values() for ins in c.instructions
                 if ins.opcode == "while" for name in called(ins)
                 if name in comps and any(i.opcode == "conditional"
                                          for i in comps[name].instructions)}
    seen, todo = set(), [decode]
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            todo += [c for ins in comps[name].instructions for c in called(ins)]
    step = [ins for name in seen for ins in comps[name].instructions]
    assert any("decode_step" in ins.op_name for ins in step)
    return step


def _sala_step_that_moves_no_carry(text):
    """The instructions of the decode loop's body and of every computation
    it calls (a conditional's branch, a fusion, a nested loop is a
    computation of its own, and what the compiler adds there carries no
    ``op_name`` of the step's), held to three things: none copies or
    transposes a carried array; an asynchronous ``copy-start`` of one (its
    result is a tuple, which a pattern for a bare shape never matched)
    moves it between HBM and memory space 1, where the compiler stages a
    sparse layer's slabs and some states round their update in either form
    of the step, and is not a second copy in HBM; and no conditional among
    them is handed a carried array or hands one back. The decode loop is
    the ``while`` whose body holds the head's conditional; the prefill's
    scan holds the kernels."""
    step = _decode_step_instructions(text)
    carried = r"(?:bf16\[2,(?:32896|2056),256\]|f32\[2,32,128,128\])"
    moved = [ins for ins in step if re.match(carried, ins.shape)
             and ins.opcode in ("copy", "transpose")]
    assert not moved, (len(moved), moved[0])
    staged = [re.findall(carried + r"\{[^}]*\}", ins.shape) for ins in step
              if ins.opcode == "copy-start" and re.search(carried, ins.shape)]
    assert staged and all(len(ends) == 2 and sum("S(1)" in e for e in ends) == 1
                          for ends in staged), staged
    through = [ins for ins in step if ins.opcode == "conditional" and re.search(
        carried, " ".join([ins.shape, *ins.operand_shapes]))]
    assert not through, through[0]
    return step


def test_sala_carried_state_is_lane_dense_and_in_place_for_v5e(chip, monkeypatch):
    """Three layers (published 7-9: two lightning, one sparse) suffice for
    layouts. The loops carry the sparse layer's keys and values as
    ``bf16[2,32896,256]`` and its compressed keys as ``bf16[2,2056,256]``,
    minor dimension last and 256 = 2 x 128 lanes, tiled (8, 128) with
    nothing padded, and each lightning state as ``f32[2,32,128,128]``; no
    step copies or transposes a slab or a state (each is written by an
    update in place); ``sparse_fwd`` and ``lightning_fwd`` are kernels whose
    operands are whole 128-lane registers wide, but the block indices, which
    the kernel reads from SMEM."""
    _, compiled = _sala_generator(chip, monkeypatch, layers=3)
    text = compiled.as_text()
    slabs = set(re.findall(r"bf16\[2,(?:32896|2056),256\]\{[^}]*\}", text))
    # (a kernel call's operand constraints name the bare order, ``{2,1,0}``)
    assert slabs and all(s.split("{")[1] == "2,1,0}" or s.split("{")[1]
                         .startswith("2,1,0:T(8,128)(2,1)") for s in slabs), slabs
    states = set(re.findall(r"f32\[2,32,128,128\]\{[^}]*\}", text))
    assert states and all(s.split("{")[1].startswith(("3,2,1,0:T(8,128)",
                                                      "3,2,1,0}"))
                          for s in states), states
    # the step by computation (the decode loop's body and all it calls),
    # not by ``decode_step`` in a line: what the compiler adds round a
    # conditional has no ``op_name`` (the 16-layer case below)
    step = _sala_step_that_moves_no_carry(text)
    assert any("f32[2,32,128,128]" in ins.shape and ins.opcode in (
        "fusion", "dynamic-update-slice") for ins in step)
    # (the benchmark's readers pick a kernel's calls by the name's prefix:
    # the scorer's kernel must not read as a ``sparse_fwd``)
    for kernel, calls in (("sparse_fwd", 1), ("lightning_fwd", 2),
                          ("select_fwd", 1)):
        found = [ln for ln in text.splitlines() if re.search(
            r"%%\S*%s\S* = .*tpu_custom_call" % kernel, ln)]
        assert len(found) == calls, (kernel, len(found))
        for ln in found:
            operands = re.search(r"operand_layout_constraints=\{(.*?\})\}", ln)
            for shape in re.findall(r"\w+\[[\d,]*\]\{[\d,]*\}",
                                    operands.group(1)):
                wide = _minor_dim(shape)
                assert wide is None or wide % 128 == 0 or shape.startswith(
                    ("s32", "f32[32]")), (shape, ln[:200])


    # the scorer's kernel writes indices alone: no float32 array with the
    # compressed keys (2,056, or 2,560 reordered and padded) as its minor
    # axis holds more than a step's one query a row and head has (the
    # parent's prefill held f32[2,2,16,512,2056] in a loop body)
    scores = {m.group(0) for m in re.finditer(r"f32\[([\d,]*),(?:2056|2560)\]",
                                              text)
              if np.prod([int(x) for x in m.group(1).split(",")]) > 2 * 32}
    assert not scores, scores


@pytest.mark.parametrize("context", [32896, 131200], ids=["32k", "128k"])
def test_select_kernel_compiles_for_v5e(chip, context):
    """``select_fwd`` alone at the cell's shapes (a chunk of 2 x 4,096
    queries of 32 heads against 2,056 compressed keys, the 31 highest of 514
    blocks) and at four times the context: a tile's score and accumulator
    scratch and a key head's reordered keys within the VMEM a kernel may
    ask for, every slice on the (8, 128) tiling."""
    from paddle_tpu.ops import block_select

    rows, s, heads, d, kv = 2, 4096, 32, 128, 2
    q = jax.ShapeDtypeStruct((rows, s, heads * d), jnp.bfloat16, sharding=chip)
    ck = jax.ShapeDtypeStruct((rows, context // 16, kv * d), jnp.bfloat16,
                              sharding=chip)
    p0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda q, ck, p0: block_select.block_select(
        q, ck, p0, group=heads // kv, head_dim=d, kernel_size=32, stride=16,
        block=64, init_blocks=1, window_blocks=32, n_sel=31, scale=d ** -0.5,
        interpret=False)[0]).lower(q, ck, p0).compile()
    text = compiled.as_text()
    assert len(re.findall(r"select_fwd\S* = .*tpu_custom_call", text)) == 1
    assert f"s32[{rows},{kv},32,{s}]" in text     # indices, queries on lanes
    assert not re.search(r"f32\[[\d,]*,%d\]" % (context // 16), text)


def test_sala_generator_fits_one_v5e(chip, monkeypatch):
    """The whole stage (4 sparse + 12 lightning layers, the whole
    vocabulary): 10.08 GB of arguments and 1.64 GB of temporaries (the
    carried state, a chunk's activations through a 16,384-wide FFN, the
    scorer's float32 scores), under 14.5 GB together, so ISSUE 33's 16-layer
    cut stands; the configuration file's ``memory`` group records what this
    compile said.

    At this depth, and not at three layers, the step moved all it carries
    while its layers stood inside the first step's conditional
    (``decoding.step_in_conditional``): PR 47's tree fails the assertion
    below with 36 copies in the conditional's branch (16 of a
    ``bf16[2,32896,256]`` slab, 8 of the compressed keys, 12 of a state),
    none with an ``op_name``. With the layers outside it
    (``step_with_write_switch``, PR 48) the decode loop's body and every
    computation it calls copy and transpose none of them, and no
    conditional in the loop is handed a carried array or hands one back."""
    cell_config, compiled = _sala_generator(chip, monkeypatch, layers=16)
    _sala_step_that_moves_no_carry(compiled.as_text())
    m = compiled.memory_analysis()
    assert 10.0e9 < m.argument_size_in_bytes < 10.15e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9
    recorded = cell_config["memory"]
    assert abs(recorded["generator_weights_bytes"]
               - m.argument_size_in_bytes) < 1e6
    assert abs(recorded["generator_rows_2_temporaries_bytes"]
               - m.temp_size_in_bytes) < 0.2e9


def test_brumby_generator_fits_one_v5e_and_carries_its_states_in_place(
        chip, monkeypatch):
    """The ``brumby-serve-decode`` generator (16 rows, prompt 1,024 + 256
    new, bfloat16, the benchmark's configuration file: 8 layers, the whole
    vocabulary) compiled for one described chip: 8.40 GB of arguments and
    5.32 GB of temporaries, 4.85 GB of them the eight states
    ``f32[16,8,136,8704]`` (8,704 products held a head, the key sum on the
    sublane behind a value's 128), under 14.5 GB together, so ISSUE 39's 16
    rows stand; the configuration file's ``memory`` group records what this
    compile said. Every state is tiled (8, 128) with nothing padded, both
    kernels are there once a layer, and neither loop, nor the audit that
    reads a slice of one state behind them, copies or transposes a state: a
    second 4.85 GB would not fit. A one-row step walks every FFN matrix in
    whole rows (no float32 ``[16, 17408]`` product, whose walk in strips of
    512 columns ran at a speed fixed by the process: PERF.md section 6,
    PR 39)."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.families import brumby as family
    from paddle_tpu.models import brumby
    from paddle_tpu.ops import power_retention

    with open(os.path.join(root, "benchmarks", "configs",
                           "brumby-14b-pp5.json")) as f:
        cell_config = json.load(f)
    rows, prompt, new = 16, 1024, 256
    prog = pt.build(brumby.make_generator(
        family.program_config(cell_config), max_new_tokens=new))
    monkeypatch.setattr(power_retention, "default_interpret", lambda: False)
    one_row = np.zeros((1, prompt), np.int32)
    shapes = jax.eval_shape(lambda key: prog.init(key, prompt_ids=one_row)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]
                       ).lower(params, ids).compile()
    m = compiled.memory_analysis()
    # ids and the audit of one key head: 16 x 1,279 positions of k and v
    assert 11e6 < m.output_size_in_bytes < 12e6
    assert 8.39e9 < m.argument_size_in_bytes < 8.41e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9
    recorded = cell_config["memory"]
    assert abs(recorded["generator_weights_bytes"]
               - m.argument_size_in_bytes) < 1e6
    assert abs(recorded["generator_rows_16_temporaries_bytes"]
               - m.temp_size_in_bytes) < 0.2e9
    held = 8 * rows * 8 * 136 * 8704 * 4
    assert recorded["state_bytes_as_held"] == held < m.temp_size_in_bytes
    text = compiled.as_text()
    carried = r"f32\[16,8,136,8704\]"
    # arrays are what computations outside fusions hold (inside one, a
    # "copy" under the audit's slice is an index map)
    fused = set(re.findall(r"fusion\(.*?calls=%?([\w.\-]+)", text))
    outside = [ln for name, body in _computations(text).items()
               if name not in fused for ln in body]
    states = set(re.findall(carried + r"\{[^}]*\}", "\n".join(outside)))
    assert states and all(s.split("{")[1].startswith(("3,2,1,0:T(8,128)",
                                                      "3,2,1,0}"))
                          for s in states), states
    moved = [ln for ln in outside if re.search(
        r"= %s\S* (copy|transpose|copy-start)\(" % carried, ln)]
    assert not moved, moved[0][:300]
    calls = {kernel: re.findall(r"%%\S*%s\S* = .*tpu_custom_call" % kernel, text)
             for kernel in ("retention_fwd", "retention_read", "retention_step")}
    assert {k: len(v) for k, v in calls.items()} == {
        "retention_fwd": 8, "retention_read": 8, "retention_step": 0}
    # no conditional holds a kernel: one round a kernel that writes in place
    # has the compiler copy every state on both of its sides
    comps = _computations(text)
    for ln in text.splitlines():
        if " conditional(" in ln:
            for branch in re.findall(r"(?:true|false|branch)_computations?=\{?%?([\w.\-, %]+)", ln):
                for name in re.split(r"[,\s%]+", branch):
                    assert "tpu_custom_call" not in "\n".join(comps.get(name, []))
    # the step's kernel reads every state block and writes 1 / WINDOW of
    # them (one a run of WINDOW, a last short run's too), whatever the
    # position: from its call's own grid and block maps
    window, kv, hd = power_retention.WINDOW, 8, 128
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype)
    grid, blocks = _pallas_blocks(
        lambda q, k, v, gate, state, held, at: power_retention.retention_step(
            q, k, v, gate, state, 40, kv, held, at),
        shape(rows, 40 * hd), shape(rows, kv * hd), shape(rows, kv * hd),
        shape(rows, kv, dtype=jnp.float32),
        shape(rows, kv, 136, 8704, dtype=jnp.float32),
        (shape(rows, kv, window, hd), shape(rows, kv, window, hd),
         shape(rows, kv, window, dtype=jnp.float32)),
        shape(dtype=jnp.int32))
    assert grid == (rows, kv)
    read, written = [at for block, at in blocks if block == (1, 1, 136, 8704)]
    steps = [(r, c) for r in range(rows) for c in range(kv)]
    for turn in range(window):
        prefetch = (np.array([turn, 1], np.int32), np.zeros(rows, np.float32))
        assert len({read(*rc, *prefetch) for rc in steps}) == rows * kv
        assert len({written(*rc, *prefetch) for rc in steps}) == -(-rows * kv // window)
    products = re.findall(r"= (\w+)\[16,17408\]\S* fusion\(.*decode_step/ffn/", text)
    assert len(products) >= 16 and set(products) == {"bf16"}, products


def test_the_window_and_the_scan_compile_for_v5e_at_the_cells_shapes(chip):
    """``phi4flash-serve-reason``'s two kernels alone at a prefill piece's
    shapes (32 rows x 512 tokens): the windowed ``flash_fwd`` over the 512
    keys held and the piece's own, 40 heads scoring over 64 and summing 128
    wide under a key bias, and ``mamba_fwd`` over 5,120 channels x 16 states;
    one ``tpu_custom_call`` each, by its name."""
    from paddle_tpu.ops import selective_scan as ss

    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=chip)
    f32 = jnp.float32
    text = jax.jit(lambda q, k, v, bias: fa.flash_attention(
        q, k, v, causal=True, key_bias=bias, window=512, interpret=False)
    ).lower(shape(32, 40, 512, 64), shape(32, 40, 1024, 64),
            shape(32, 40, 1024, 128), shape(32, 1024, dtype=f32)
            ).compile().as_text()
    assert len(re.findall(r"%\S*flash_fwd\S* = .*tpu_custom_call", text)) == 1
    text = jax.jit(lambda *a: ss.selective_scan(*a, interpret=False)).lower(
        shape(32, 512, 5120, dtype=f32), shape(32, 512, 5120, dtype=f32),
        shape(32, 512, 16, dtype=f32), shape(32, 512, 16, dtype=f32),
        shape(16, 5120, dtype=f32), shape(32, 16, 5120, dtype=f32)
    ).compile().as_text()
    assert len(re.findall(r"%\S*mamba_fwd\S* = .*tpu_custom_call", text)) == 1


def test_phi4_flash_generator_fits_one_v5e_and_its_steps_copy_no_cache(
        chip, monkeypatch):
    """The ``phi4flash-serve-reason`` generator (32 rows, prompt 2,048 + 256
    new, bfloat16, the benchmark's configuration file: all 32 layers, the
    whole vocabulary) compiled for one described chip: 7.71 GB of arguments
    and 3.89 GB of temporaries, under 14.5 GB together, so ISSUE 41's 32
    rows stand; the configuration file's ``memory`` group records what this
    compile said. Both kernels are there once a layer that has them (the
    prefill's scan holds them; a step runs neither). The decode loop copies
    or transposes none of what it carries (states ``f32[32,16,5120]``, rings
    ``bf16[32,512,1280]``, the shared ``bf16[32,2304,1280]``): each is tiled
    (8, 128) with nothing padded and written in place. No one-row product of
    a step is walked in narrow strided strips (a float32 result's
    ``kernel_window_bounds`` of the contraction's eighth by a handful of
    lane groups: PERF.md section 6, PR 39; section 7, "After PR 39" (2))."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.families import phi4_flash as family
    from paddle_tpu.models import phi4_flash
    from paddle_tpu.ops import selective_scan as ss

    with open(os.path.join(root, "benchmarks", "configs",
                           "phi4-mini-flash.json")) as f:
        cell_config = json.load(f)
    rows, prompt, new = 32, 2048, 256
    prog = pt.build(phi4_flash.make_generator(
        family.program_config(cell_config), max_new_tokens=new))
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    monkeypatch.setattr(ss, "default_interpret", lambda: False)
    params = {name: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
              for name, s in family.parameter_table(cell_config).items()}
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]
                       ).lower(params, ids).compile()
    m = compiled.memory_analysis()
    assert 7.70e9 < m.argument_size_in_bytes < 7.72e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9
    recorded = cell_config["memory"]
    assert abs(recorded["generator_weights_bytes"]
               - m.argument_size_in_bytes) < 1e6
    assert abs(recorded["generator_rows_32_temporaries_bytes"]
               - m.temp_size_in_bytes) < 0.2e9
    assert abs(recorded["generator_outputs_bytes"] - m.output_size_in_bytes) < 1e6
    carried = (recorded["state_bytes"] + recorded["window_kv_bytes"]
               + recorded["shared_kv_bytes"])
    assert 1.1e9 < carried < m.temp_size_in_bytes
    text = compiled.as_text()
    calls = {kernel: len(re.findall(r"%%\S*%s\S* = .*tpu_custom_call" % kernel,
                                    text))
             for kernel in ("mamba_fwd", "flash_fwd")}
    assert calls == {"mamba_fwd": 9, "flash_fwd": 8}
    comps = _computations(text)
    fused = set(re.findall(r"fusion\(.*?calls=%?([\w.\-]+)", text))
    loops = re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text)
    # the decode loop is the one that holds the step's conditional
    (decode,) = [name for name in loops
                 if any(" conditional(" in ln for ln in comps[name])]
    assert not any("tpu_custom_call" in ln for ln in comps[decode])
    held = r"(f32\[32,16,5120\]|bf16\[32,512,1280\]|bf16\[32,2304,1280\])"
    moved = [ln for ln in comps[decode] if re.search(
        r"= %s\S* (copy|transpose|copy-start)\(" % held, ln)]
    assert not moved, moved[0][:300]
    layouts = set(re.findall(held + r"(\{[^}]*\})", "\n".join(comps[decode])))
    assert layouts and all(
        layout.startswith("{2,1,0:T(8,128)") for _, layout in layouts), layouts
    # the step's one-row products: none walked in strips of under eight
    # lane groups of a whole contraction (2,560 / 5,120 / 10,240 rows are
    # 320 / 640 / 1,280 sublane groups)
    walked = 0
    for ln in text.splitlines():
        if "decode_step/" in ln and "dot_general" in ln and " fusion(" in ln:
            m = re.search(r'"kernel_window_bounds":\["(\d+)","(\d+)"\]', ln)
            walked += bool(m)
            if m and int(m.group(1)) in (320, 640, 1280):
                assert int(m.group(2)) >= 8, ln[:300]
    assert walked >= 100
    gates = re.findall(r"= (\w+)\[32,10240\]\S* fusion\(.*decode_step/ffn/", text)
    assert len(gates) >= 32 and set(gates) == {"bf16"}, gates


def test_trinity_generator_fits_one_v5e_and_repeats_no_key_head(
        chip, monkeypatch):
    """The ``trinity-serve-long`` generator (8 rows, prompt 32,768 + 128
    new, bfloat16, the benchmark's configuration file: layers 5-9, experts
    0-31, 25,024 rows of the vocabulary) compiled for one described chip:
    8.65 GB of arguments and the temporaries of a 2,048-token piece, under
    14.5 GB together, so ISSUE 45's 8 rows stand; the configuration file's
    ``memory`` group records what this compile said. The prefill's scan
    holds five grouped ``flash_fwd`` calls and no conditional, the decode
    loop the step's conditional and no kernel; no key/value slab is copied
    to 48 heads anywhere (nothing ``[.., 6144]`` wide is as long as a cache
    or a window's keys), and the decode loop copies or transposes neither a
    ring ``bf16[8,4096,1024]`` nor the full cache ``bf16[8,33792,1024]``."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.families import trinity as family
    from paddle_tpu.models import trinity

    with open(os.path.join(root, "benchmarks", "configs",
                           "trinity-large-ep8.json")) as f:
        cell_config = json.load(f)
    rows, prompt, new = 8, 32768, 128
    prog = pt.build(trinity.make_generator(
        family.program_config(cell_config), max_new_tokens=new))
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    table = family.decoder_params(cell_config, 0, prompt, new).shapes
    params = {name: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
              for name, s in table.items()}
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]
                       ).lower(params, ids).compile()
    m = compiled.memory_analysis()
    assert 8.64e9 < m.argument_size_in_bytes < 8.66e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9
    recorded = cell_config["memory"]
    assert abs(recorded["generator_weights_bytes"]
               - m.argument_size_in_bytes) < 1e6
    assert abs(recorded["generator_rows_8_temporaries_bytes"]
               - m.temp_size_in_bytes) < 0.2e9
    carried = recorded["window_kv_bytes"] + recorded["full_kv_bytes"]
    assert carried == 2 * 2 * 8 * 1024 * (4 * 4096 + 33792)
    assert carried < m.temp_size_in_bytes
    text = compiled.as_text()
    comps = _computations(text)
    loops = re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text)
    flash = lambda name: sum("tpu_custom_call" in ln and "flash_fwd" in ln
                             for ln in comps[name])
    decode = [name for name in loops
              if any(" conditional(" in ln for ln in comps[name])]
    scans = [name for name in loops if flash(name)]
    assert len(decode) == 1 and len(scans) == 1 and decode != scans
    assert flash(scans[0]) == 5
    assert not any("tpu_custom_call" in ln for ln in comps[decode[0]])
    # a key/value slab repeated to 48 heads would be [8, >= 4096, 6144]
    repeated = [ln for ln in text.splitlines() if re.search(
        r"= bf16\[8,(4096|6144|33792),6144\]", ln)]
    assert not repeated, repeated[0][:300]
    held = r"(bf16\[8,4096,1024\]|bf16\[8,33792,1024\])"
    moved = [ln for ln in comps[decode[0]] if re.search(
        r"= %s\S* (copy|transpose|copy-start)\(" % held, ln)]
    assert not moved, moved[0][:300]
    layouts = set(re.findall(held + r"(\{[^}]*\})", "\n".join(comps[decode[0]])))
    assert layouts and all(
        layout.startswith("{2,1,0:T(8,128)") for _, layout in layouts), layouts


def test_granite_generator_fits_one_v5e_and_its_steps_copy_no_carry(
        chip, monkeypatch):
    """The ``granite-serve-agent`` generator (32 rows, prompt 2,048 + 256
    new, bfloat16, the benchmark's configuration file: layers 0-9, experts
    0-35, 50,176 rows of the vocabulary) compiled for one described chip:
    9.52 GB of arguments and 4.80 GB of temporaries at pieces of 256 tokens,
    under 14.5 GB together, so ISSUE 49's 32 rows stand (pieces of 512 read
    15.4 GB: the configuration file's ``memory`` group records both). The
    prefill's scan holds ``ssd_fwd`` once a Mamba-2 layer and one grouped
    ``flash_fwd``; a step runs neither. The step, in every computation it
    calls, copies or transposes none of what it carries (nine states
    ``f32[32,64,128,128]``, nine tails ``bf16[3,32,8448]``, the cache
    ``bf16[32,3072,1024]`` twice), hands none through a conditional, and
    holds each state tiled (8, 128) with nothing padded."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.families import granite_hybrid as family
    from paddle_tpu.models import granite_hybrid
    from paddle_tpu.ops import ssd

    with open(os.path.join(root, "benchmarks", "configs",
                           "granite-4.0-h-small-ep2.json")) as f:
        cell_config = json.load(f)
    rows, prompt, new = 32, 2048, 256
    prog = pt.build(granite_hybrid.make_generator(
        family.program_config(cell_config), max_new_tokens=new))
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    monkeypatch.setattr(ssd, "default_interpret", lambda: False)
    table = family.decoder_params(cell_config, 0, prompt, new).shapes
    params = {name: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
              for name, s in table.items()}
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=chip)
    compiled = jax.jit(lambda p, i: prog.apply(p, {}, prompt_ids=i)[0]
                       ).lower(params, ids).compile()
    m = compiled.memory_analysis()
    assert 9.51e9 < m.argument_size_in_bytes < 9.53e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9
    recorded = cell_config["memory"]
    assert abs(recorded["generator_weights_bytes"]
               - m.argument_size_in_bytes) < 1e6
    assert abs(recorded["generator_rows_32_temporaries_bytes"]
               - m.temp_size_in_bytes) < 0.2e9
    assert abs(recorded["generator_outputs_bytes"] - m.output_size_in_bytes) < 1e6
    carried_bytes = (recorded["state_bytes"] + recorded["tail_bytes"]
                     + recorded["kv_bytes"])
    assert recorded["state_bytes"] == 9 * 32 * 128 * 64 * 128 * 4
    assert 1.6e9 < carried_bytes < m.temp_size_in_bytes
    text = compiled.as_text()
    calls = {kernel: len(re.findall(r"%%\S*%s\S* = .*tpu_custom_call" % kernel,
                                    text))
             for kernel in ("ssd_fwd", "flash_fwd")}
    assert calls == {"ssd_fwd": 9, "flash_fwd": 1}
    step = _decode_step_instructions(text)
    # (the grouped products of the held experts are the compiler's own
    # custom calls; neither Pallas kernel is in a step)
    assert not any(ins.opcode == "custom-call" and re.search(
        "ssd_fwd|flash_fwd", str(ins)) for ins in step)
    carried = (r"(?:f32\[32,64,128,128\]|bf16\[3,32,8448\]"
               r"|bf16\[32,3072,1024\])")
    moved = [ins for ins in step if re.match(carried, ins.shape)
             and ins.opcode in ("copy", "transpose", "copy-start")]
    assert not moved, (len(moved), moved[0])
    through = [ins for ins in step if ins.opcode == "conditional" and re.search(
        carried, " ".join([ins.shape, *ins.operand_shapes]))]
    assert not through, through[0]
    layouts = set(re.findall(r"f32\[32,64,128,128\](\{[^}]*\})",
                             " ".join(ins.shape for ins in step)))
    assert layouts and all(l.startswith("{3,2,1,0:T(8,128)") for l in layouts)


def test_loss_head_makes_three_products_a_chunk_for_v5e(chip):
    """gpt2-medium's loss head at the train cell's shape (32 x 1,024 rows,
    50,257 columns, bfloat16) under ``jax.value_and_grad``: the loop over
    its 8 chunks of 4,096 rows holds three products, each over 50,304
    columns (the vocabulary to the next 128) and each under scope ``ce``;
    the program makes no fourth (the backward pass scales what the forward
    made), holds no logits of all the rows at once, and its temporaries are
    1.34 GB (a chunk's float32 logits, 824 MB, and what the products keep
    beside them)."""
    from paddle_tpu.ops.fused_ce import softmax_cross_entropy_sum
    from paddle_tpu.profiling.fusion import scope_path

    rows, d, vocab, chunk = 32 * 1024, 1024, 50257, 4096

    def loss(h, w, labels):
        nonpad = (labels != 0).astype(jnp.float32)
        return softmax_cross_entropy_sum(
            h, w, None, labels, nonpad / jnp.maximum(nonpad.sum(), 1.0),
            0.0, chunk)

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=chip),
        jax.ShapeDtypeStruct((d, vocab), jnp.bfloat16, sharding=chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=chip)).compile()
    text = compiled.as_text()
    products = [ln for ln in text.splitlines() if " convolution(" in ln]
    shapes = sorted(re.search(r"= (f32\[\d+,\d+\])", ln).group(1)
                    for ln in products)
    assert shapes == ["f32[1024,50304]", "f32[4096,1024]",
                      "f32[4096,50304]"], shapes
    for ln in products:
        op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert "ce" in scope_path(op_name) and "/while/body/" in op_name, ln[:300]
    assert not re.search(r"f32\[%d,\d" % rows, text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _minor_dim(shape):
    """The minor dimension's size of ``bf16[32,1024,16,64]{3,1,2,0:T(8,128)}``
    (the first index in the braces names it), or None for a scalar."""
    m = re.match(r"\w+\[([\d,]*)\](?:\{(\d+))?", shape)
    dims = [int(x) for x in m.group(1).split(",") if x]
    if not dims:
        return None
    return dims[int(m.group(2)) if m.group(2) else len(dims) - 1]


def _assert_kernel_operands_lane_dense(text):
    """Every operand of a ``flash_*`` kernel call has a minor dimension of
    whole 128-lane registers: the chip holds none of them padded."""
    calls = [ln for ln in text.splitlines()
             if re.search(r"%\S*flash_(fwd|bwd|dq|dkv)\S* = .*tpu_custom_call",
                          ln)]
    assert calls
    for ln in calls:
        operands = re.search(r"operand_layout_constraints=\{(.*?\})\}", ln)
        for shape in re.findall(r"\w+\[[\d,]*\]\{[\d,]*\}", operands.group(1)):
            assert _minor_dim(shape) % 128 == 0, (shape, ln[:200])


def _assert_no_64_minor_copies_in_loops(text):
    """No while body holds a ``copy`` or ``transpose`` whose result or
    operand has a 64-wide minor dimension: under a 128-lane tile such an
    array is held, written and read at twice its size (the twelve head
    transposes a layer that stood round the flash kernels: PERF.md, PR 32)."""
    comps = _computations(text)
    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text))
    assert bodies
    for name in bodies:
        shapes = {}
        for ln in comps[name]:
            m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\S+) ", ln)
            if m:
                shapes[m.group(1)] = m.group(2)
        for ln in comps[name]:
            m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\S+) "
                         r"(?:copy|transpose|copy-start)\(%([\w.\-]+)", ln)
            if m and not m.group(1).startswith("("):
                for shape in (m.group(1), shapes.get(m.group(2), "")):
                    if shape and not shape.startswith("("):
                        assert _minor_dim(shape) != 64, ln[:300]


_COMPILED = {}   # what two tests read of one compile (a file runs on one worker)


def _one_chip_block(chip, monkeypatch):
    """(compiled text, temporaries' bytes) of ``jax.grad`` over two
    scan-stacked layers at gpt2-medium's widths (32 x 1024 tokens, d 1024,
    16 heads of 64, remat, bfloat16) on one described chip."""
    if "one_chip" in _COMPILED:
        return _COMPILED["one_chip"]
    d, inner, heads, batch, seq, layers = 1024, 4096, 16, 32, 1024, 2

    def net(x):
        stack = stacked.encoder_stack_params(layers, d, inner)
        y = stacked.apply_stacked(x, stack, stacked.make_encoder_block,
                                  num_heads=heads, use_flash=True,
                                  causal=True, remat=True)
        return {"loss": jnp.mean(jnp.square(y.astype(jnp.float32)))}

    prog = pt.build(net)
    before = config.get_flag("default_compute_dtype")
    config.set_flag("default_compute_dtype", "bfloat16")
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    try:
        small = np.zeros((2, 8, d), jnp.bfloat16)
        shapes = jax.eval_shape(lambda key: prog.init(key, x=small)[0],
                                jax.random.PRNGKey(0))
        params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)
                  for k, v in shapes.items()}
        x = jax.ShapeDtypeStruct((batch, seq, d), jnp.bfloat16, sharding=chip)
        compiled = jax.jit(jax.grad(
            lambda p, x: prog.apply(p, {}, training=True, x=x)[0]["loss"])
        ).lower(params, x).compile()
    finally:
        config.set_flag("default_compute_dtype", before)
    _COMPILED["one_chip"] = (compiled.as_text(),
                             compiled.memory_analysis().temp_size_in_bytes)
    return _COMPILED["one_chip"]


def test_stacked_train_block_has_no_padded_head_transposes_for_v5e(
        chip, monkeypatch):
    """Two scan-stacked layers at gpt2-medium's widths (32 x 1024 tokens,
    d 1024, 16 heads of 64, remat, ``jax.grad``, bfloat16): the fused
    projection's output goes into the flash kernels as it lies and their
    output into the out-projection, so neither loop body copies or
    transposes an array with a 64-wide minor dimension (the parent's had
    twelve a layer, each held at twice its size), every operand of the
    three kernel calls a layer is lane-dense, and the temporaries are
    1.17 GB where the parent's were 1.55."""
    text, temporaries = _one_chip_block(chip, monkeypatch)
    # forward; remat's second forward and the one backward kernel in the
    # backward body
    assert text.count("tpu_custom_call") == 3
    _assert_kernel_operands_lane_dense(text)
    _assert_no_64_minor_copies_in_loops(text)
    assert temporaries < 1.3e9


@pytest.mark.parametrize("step", ["one_chip", "dp2tp2"])
def test_backward_body_runs_one_flash_bwd_for_v5e(step, chips, chip,
                                                  monkeypatch):
    """The train cells' sequence (1,024 rows) is resident, so the scan's
    backward body, on one chip and inside the dp2 x tp2 ``shard_map``,
    holds remat's ``flash_fwd`` and one ``flash_bwd``; neither ``flash_dq``
    nor ``flash_dkv`` is in the program, and the loops still copy no array
    with a 64-wide minor dimension."""
    text = (_one_chip_block(chip, monkeypatch)[0] if step == "one_chip"
            else _dp2tp2_block_text(chips, monkeypatch, batch=32))
    comps = _computations(text)
    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text))
    kernels = sorted(
        sorted(m.group(1) for ln in comps[name] for m in [re.search(
            r"%\S*(flash_[a-z]+)\S* = .*tpu_custom_call", ln)] if m)
        for name in bodies)
    assert kernels == [["flash_bwd", "flash_fwd"], ["flash_fwd"]], kernels
    assert "flash_dq" not in text and "flash_dkv" not in text
    _assert_no_64_minor_copies_in_loops(text)
    # dq, dk and dv go into the projection's backward products as three
    # operands: no body lays them side by side in a buffer first
    laid = [ln for name in bodies for ln in comps[name] if re.search(
        r"dynamic-update-slice\S* = bf16\[\d+,1024,(3072|1920)\]", ln)]
    assert not laid, laid[0][:200]


def _pallas_blocks(fn, *shapes):
    """``(grid, [(block shape, index map)])`` of the one ``pallas_call``
    that ``fn`` traces to, inputs then outputs; an index map takes the grid
    indices and the scalar-prefetch operands and returns the block's index."""
    from jax._src.state import discharge

    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for inner in e.params.values():     # a jitted function's body
                if hasattr(inner, "jaxpr"):
                    yield from calls(inner.jaxpr)

    (call,) = calls(jax.make_jaxpr(fn)(*shapes).jaxpr)
    mapping = call.params["grid_mapping"]

    def index_map(block):
        closed = block.index_map_jaxpr
        plain, consts = discharge.discharge_state(closed.jaxpr, closed.consts)
        n = len(block.block_shape)
        return lambda *at: tuple(int(i) for i in jax.core.eval_jaxpr(
            plain, consts, *at)[:n])

    return mapping.grid, [
        (tuple(getattr(d, "block_size", d) for d in b.block_shape), index_map(b))
        for b in mapping.block_mappings]


def _computations(text):
    """name -> lines of every computation of an HLO module's text."""
    out, name = {}, None
    for ln in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", ln)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(ln)
    return out


def _dp2tp2_block_text(chips, monkeypatch, batch):
    """Compiled text of ``jax.grad`` over two scan-stacked layers at
    gpt2-large's widths (d 1280, inner 5120, 20 heads, ``batch`` x 1024,
    remat, bfloat16) on a dp2 x tp2 mesh of the described chips, the
    parameters sharded by the rule table."""
    if ("dp2tp2", batch) in _COMPILED:
        return _COMPILED["dp2tp2", batch]
    d, inner, heads, seq, layers = 1280, 5120, 20, 1024, 2
    mesh = Mesh(np.array(chips).reshape(2, 2), ("dp", "tp"))

    def net(x):
        stack = stacked.encoder_stack_params(layers, d, inner)
        y = stacked.apply_stacked(x, stack, stacked.make_encoder_block,
                                  num_heads=heads, use_flash=True,
                                  causal=True, remat=True)
        return {"loss": jnp.mean(jnp.square(y.astype(jnp.float32)))}

    prog = pt.build(net)
    before = config.get_flag("default_compute_dtype")
    config.set_flag("default_compute_dtype", "bfloat16")
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    try:
        small = np.zeros((2, 8, d), jnp.bfloat16)
        shapes = jax.eval_shape(lambda key: prog.init(key, x=small)[0],
                                jax.random.PRNGKey(0))
        rules = pt.parallel.transformer_tp_rules().adapted_to(mesh)
        params = {k: jax.ShapeDtypeStruct(
            v.shape, v.dtype,
            sharding=NamedSharding(mesh, rules.spec_for(k, v.shape, mesh)))
            for k, v in shapes.items()}
        x = jax.ShapeDtypeStruct((batch, seq, d), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("dp")))

        def loss(p, x):
            with mesh_mode(mesh):
                return prog.apply(p, {}, training=True, x=x)[0]["loss"]

        _COMPILED["dp2tp2", batch] = jax.jit(jax.grad(loss), out_shardings={
            k: v.sharding for k, v in params.items()}
        ).lower(params, x).compile().as_text()
        return _COMPILED["dp2tp2", batch]
    finally:
        config.set_flag("default_compute_dtype", before)


def test_partitioned_tp_block_gathers_no_weight_for_v5e(chips, monkeypatch):
    """The same two layers with 3 rows a replica, which tp 2 does not
    divide: the stack keeps the form the partitioner splits
    (``_batch_sharded_why_not``), ``qkv/w`` sharded over its last axis.
    The fused projection stays the ``[b, s, 3, e]`` einsum there (a flat
    ``[d, 3e]`` weight would merge the sharded axis with the 3 and be
    gathered every layer: six all-gathers in this text), its three parts
    go to the kernels per shard, five head pairs each, and the text holds
    the parent's collectives: all-reduces and nothing else."""
    text = _dp2tp2_block_text(chips, monkeypatch, batch=6)
    kinds = set(re.findall(r" (all-gather|all-reduce|collective-permute|"
                           r"all-to-all|reduce-scatter)(?:-start)?\(", text))
    assert kinds == {"all-reduce"}, kinds
    assert text.count("tpu_custom_call") == 3
    _assert_kernel_operands_lane_dense(text)


def test_tp_block_exchanges_run_under_matmuls_for_v5e(chips, monkeypatch):
    """Two layers at gpt2-large's widths (d 1280, inner 5120, 20 heads,
    16 x 1024 a replica, scan + remat, ``jax.grad``) as dp2 x tp2: the
    loop bodies hold no all-reduce of an activation, the 11 exchanges of
    a layer are ``collective-permute-start`` / ``-done`` pairs of half a
    replica's rows in bf16, matmul fusions are scheduled between a start
    and its done, and the gradients of the float32 parameters are
    reduced over dp in float32. Times are the chip's to give
    (PERF.md, PR 30); this holds the schedule they came from."""
    d, batch, seq, layers = 1280, 32, 1024, 2
    text = _dp2tp2_block_text(chips, monkeypatch, batch)

    # the shard_map bodies' kernels read the projections' layout too (ten
    # local heads, five pairs): no padded head transpose in a loop body
    _assert_kernel_operands_lane_dense(text)
    _assert_no_64_minor_copies_in_loops(text)
    comps = _computations(text)
    half = re.escape("bf16[%d,%d,%d]" % (batch // 4, seq, d))
    whole = re.escape("bf16[%d,%d,%d]" % (batch // 2, seq, d))
    matmuls = {name for name, lines in comps.items()
               if any(" convolution(" in ln for ln in lines)}
    starts = dones = covered = 0
    for lines in comps.values():
        open_at = {}
        for i, ln in enumerate(lines):
            m = re.search(r"%(collective-permute-start[.\d]*) = \(" + half, ln)
            if m:
                starts += 1
                open_at[m.group(1)] = i
            m = re.search("= " + half
                          + r"\S* collective-permute-done\(%([\w.\-]+)\)", ln)
            if m:
                dones += 1
                between = lines[open_at.pop(m.group(1)):i]
                covered += any(
                    re.search(r" fusion\(.*calls=%([\w.\-]+)", b)
                    and re.search(r" fusion\(.*calls=%([\w.\-]+)",
                                  b).group(1) in matmuls for b in between)
        assert not open_at            # every start has its done in its body
    # 4 forward, 3 in remat's second forward, 4 backward
    assert (starts, dones) == (11, 11), (starts, dones)
    # 8 when the chip measured it (PERF.md, PR 30): the out-projection's
    # scatter, forward and in remat, and the forward ffn gather have none
    # (the compiler fuses an own-half matmul with the add of the arrival,
    # or runs it under the neighbouring exchange)
    assert covered >= 8, covered
    # debugger.collective_report reads the same text (the train cell's
    # ``correct`` goes through it): it sees each exchange once, one chunk
    hops = [c for c in debugger._parse_hlo_collectives(text, 4)
            if c[0] == "collective-permute"]
    assert len(hops) == 11 and {c[1] for c in hops} == {
        batch // 4 * seq * d * 2}, hops
    reduces = [ln for ln in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", ln)]
    assert reduces and not any(re.search(half + "|" + whole, ln)
                               for ln in reduces), reduces
    # the stacked float32 gradients, summed over the dp pairs {0,2},{1,3}
    dp = [ln for ln in reduces
          if "f32[%d,%d,3,%d]" % (layers, d, d // 2) in ln]
    assert len(dp) == 1 and "replica_groups={{0,2},{1,3}}" in dp[0], dp
    assert "bf16[" not in dp[0].split(" all-reduce(")[0]


def test_scope_table_places_the_tp_block_and_its_exchanges_for_v5e(
        chips, monkeypatch):
    """The same compiled text through ``profiling.fusion.scope_table`` with
    the mesh's axes: every instruction line of the chip's text is parsed
    (an asynchronous pair's result is a tuple nested three deep), the 11
    exchanges of a layer, halves of a pair and all, run over ``tp`` and lie
    under ``attn`` or ``ffn``, three of them in remat's second forward; the
    gradient all-reduce runs over ``dp``; and the three kernel calls lie
    under ``attn`` by their own names."""
    from paddle_tpu.profiling.fusion import parse_hlo_module, scope_table

    text = _dp2tp2_block_text(chips, monkeypatch, batch=32)
    lines = [ln for ln in text.splitlines()
             if re.match(r"^\s+(ROOT\s+)?%?[\w.\-]+ = ", ln)]
    parsed = parse_hlo_module(text)
    assert sum(len(c.instructions) for c in parsed.values()) == len(lines)

    table = scope_table(text, (("dp", 2), ("tp", 2)))
    hops = [r for r in table.values()
            if r.opcode == "collective-permute-done"]
    assert len(hops) == 11 and {r.axes for r in hops} == {"tp"}
    assert {r.path[-1] for r in hops} <= {"attn", "ffn"}
    assert sum(r.remat for r in hops) == 3
    assert sum(r.backward and not r.remat for r in hops) == 4
    starts = [r for r in table.values()
              if r.opcode == "collective-permute-start"]
    assert len(starts) == 11 and {r.axes for r in starts} == {"tp"}
    reduces = {r.axes for r in table.values() if r.opcode == "all-reduce"}
    assert "dp" in reduces and reduces <= {"dp", "dp,tp"}
    kernels = {name.split(".")[0]: r for name, r in table.items()
               if r.opcode == "custom-call" and "flash" in name}
    assert set(kernels) == {"flash_fwd", "flash_bwd"}
    assert all("attn" in r.path for r in kernels.values())
    assert kernels["flash_bwd"].backward and not kernels["flash_bwd"].remat
