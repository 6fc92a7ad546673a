"""Device time by the program's own scopes: the instruction -> scope table
(``profiling.fusion.scope_table``), the registry of programs and the one
join (``core.profiler.register_program`` / ``program_tables`` /
``device_scopes``), and the operator's table."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import layers, optimizer as opt
from paddle_tpu.core import profiler
from paddle_tpu.profiling.fusion import (ScopeRow, collective_axes,
                                         scope_path, scope_table)


@pytest.fixture(autouse=True)
def empty_registry():
    profiler._programs.clear()
    yield
    profiler._programs.clear()


# ---------------------------------------------------------------------------
# op_name -> path


@pytest.mark.parametrize("op_name, path", [
    ("jit(step)/transpose(jvp(gpt))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/dot_general", ("gpt", "attn")),
    ("jit(call)/call_exported/jit(f)/prefill/attn/dot_general",
     ("prefill", "attn")),
    ("jit(g)/while/body/closed_call/decode_step/cond/branch_1_fun/sin",
     ("decode_step",)),
    ("jit(f)/jit(main)/jvp(jit(log_softmax))/exp", ()),
    ("jit(f)/shard_map/pjit/ffn/mul;jit(f)/ln/add", ("ffn",)),
    ("jit(g)/while/cond/lt", ()),
    ("params['gpt/h/w']", ()),
    ("jit(train_step)/jvp(body)/jit(relu)/max", ("body",)),
])
def test_scope_path_keeps_the_named_scopes_and_nothing_else(op_name, path):
    assert scope_path(op_name) == path


def small_step():
    def layer(x, w):
        with jax.named_scope("attn"):
            x = jnp.tanh(x @ w)
        with jax.named_scope("ffn"):
            x = jnp.sin(x @ w)
        return x

    def loss(ws, x):
        with jax.named_scope("gpt"):
            y, _ = jax.lax.scan(
                lambda c, w: (jax.checkpoint(layer)(c, w), None), x, ws)
        with jax.named_scope("ce"):
            return jnp.sum(y ** 2)

    def step(ws, x):
        l, g = jax.value_and_grad(loss)(ws, x)
        with jax.named_scope("optimizer"):
            return l, ws - 0.1 * g

    return step, (jnp.ones((3, 64, 64)), jnp.ones((8, 64)))


def test_table_of_a_step_holds_paths_and_the_remat_and_backward_tags():
    step, args = small_step()
    table = scope_table(jax.jit(step).lower(*args).compile().as_text())
    rows = [r for r in table.values()
            if r.opcode not in ("parameter", "constant", "tuple",
                                "get-tuple-element", "bitcast")]
    paths = {r.path for r in rows}
    assert {("gpt", "attn"), ("gpt", "ffn"), ("optimizer",)} <= paths
    assert any(r.path == ("ce",) or r.path[:1] == ("ce",) for r in rows)
    remat = [r for r in rows if r.remat]
    assert remat and all(r.backward and r.path[:1] == ("gpt",) for r in remat)
    assert all("rematted_computation" in r.op_name for r in remat)
    forward = [r for r in rows if r.path == ("gpt", "attn") and not r.backward]
    backward = [r for r in rows if r.path == ("gpt", "attn") and r.backward
                and not r.remat]
    assert forward and backward
    assert not any(r.remat or r.backward for r in rows
                   if r.path == ("optimizer",))
    # the loops themselves lie under the scope that holds them
    loops = [r for r in rows if r.opcode == "while"]
    assert len(loops) == 2 and all(r.path == ("gpt",) for r in loops)
    assert all(r.axes is None for r in rows)


def test_a_fusion_without_metadata_takes_its_costliest_path():
    text = """HloModule m

%fused (p: f32[64,4096], q: f32[4096,64], r: f32[64,64]) -> f32[64,64] {
  %p = f32[64,4096]{1,0} parameter(0)
  %q = f32[4096,64]{1,0} parameter(1)
  %r = f32[64,64]{1,0} parameter(2)
  %d = f32[64,64]{1,0} dot(f32[64,4096]{1,0} %p, f32[4096,64]{1,0} %q), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/transpose(jvp(ffn))/dot_general"}
  ROOT %a = f32[64,64]{1,0} add(f32[64,64]{1,0} %d, f32[64,64]{1,0} %r), metadata={op_name="jit(f)/ln/add"}
}

ENTRY %main (x: f32[64,4096], y: f32[4096,64], z: f32[64,64]) -> f32[64,64] {
  %x = f32[64,4096]{1,0} parameter(0), metadata={op_name="x"}
  %y = f32[4096,64]{1,0} parameter(1), metadata={op_name="y"}
  %z = f32[64,64]{1,0} parameter(2), metadata={op_name="z"}
  ROOT %fusion.1 = f32[64,64]{1,0} fusion(f32[64,4096]{1,0} %x, f32[4096,64]{1,0} %y, f32[64,64]{1,0} %z), kind=kOutput, calls=%fused
}
"""
    table = scope_table(text)
    assert table["fusion.1"] == ScopeRow(
        ("ffn",), False, True, None, "fusion",
        "jit(f)/transpose(jvp(ffn))/dot_general")
    assert table["x"].path == () and "d" not in table


def test_what_the_compiler_made_lies_with_what_it_feeds():
    text = """HloModule m

ENTRY %main (x: f32[64,64], w: f32[64,64]) -> f32[64,64] {
  %x = f32[64,64]{1,0} parameter(0), metadata={op_name="x"}
  %w = f32[64,64]{1,0} parameter(1), metadata={op_name="w"}
  %copy.1 = f32[64,64]{0,1} copy(f32[64,64]{1,0} %w)
  %slice-start.2 = ((f32[64,64]{0,1:T(8,128)}), f32[64,16]{0,1:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%copy.1), slice={[0:64], [0:16]}
  %slice-done.2 = f32[64,16]{0,1:T(8,128)S(1)} slice-done(%slice-start.2)
  %ragged-dot-none.3 = f32[64,64]{1,0} custom-call(%x, %slice-done.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %add.4 = f32[64,64]{1,0} add(f32[64,64]{1,0} %ragged-dot-none.3, f32[64,64]{1,0} %x), metadata={op_name="jit(f)/decode_step/moe/add"}
  ROOT %copy.5 = f32[64,64]{0,1} copy(f32[64,64]{1,0} %add.4)
}
"""
    table = scope_table(text)
    moe = ("decode_step", "moe")
    # a chain of nameless instructions reaches the add it feeds; the last
    # copy feeds nothing and takes the place of what feeds it
    for name in ("copy.1", "slice-start.2", "slice-done.2",
                 "ragged-dot-none.3", "copy.5"):
        assert table[name].path == moe, name
        # the donor's op_name, and the row says it is not its own
        assert table[name].op_name == "jit(f)/decode_step/moe/add"
        assert table[name].inherited
    assert table["add.4"].op_name == "jit(f)/decode_step/moe/add"
    assert not table["add.4"].inherited and not table["x"].inherited
    assert table["slice-start.2"].opcode == "slice-start"   # a nested tuple
    assert table["x"].path == () and table["x"].op_name == "x"


# ---------------------------------------------------------------------------
# through export -> serialize -> deserialize -> Predictor


def test_a_served_generator_keeps_prefill_and_decode_step(tmp_path):
    from paddle_tpu.fleet import decode as fdecode
    from paddle_tpu.models import gpt

    cfg = gpt.base_config(vocab_size=16, max_len=32, d_model=32, d_inner=64,
                          num_heads=4, num_layers=2, use_flash=False,
                          fused_ce=False)
    prompts = np.random.RandomState(0).randint(3, 16, (2, 8)).astype(np.int32)
    fdecode.export_decoder(str(tmp_path / "m"), cfg, max_new_tokens=4,
                           example_prompt=prompts)
    profiler._programs.clear()
    pred = pio.load_inference_model(str(tmp_path / "m"))
    assert [p[0] for p in profiler._programs] == ["jit_call"]
    assert profiler._programs[0][4] is None         # nothing read yet
    pred.run({"prompt_ids": prompts})
    del pred
    gc.collect()
    (table,) = profiler.program_tables("jit_call(12345)")
    heads = {r.path[0] for r in table.values() if r.path}
    assert {"prefill", "decode_step"} <= heads
    assert any("attn" in r.path for r in table.values())
    assert profiler._programs[0][2] is None         # the thunk is let go


# ---------------------------------------------------------------------------
# a collective's mesh axes

MESH = (("dp", 2), ("tp", 2))


@pytest.mark.parametrize("attrs, axes", [
    ("replica_groups={{0,1},{2,3}}, use_global_device_ids=true", "tp"),
    ("replica_groups={{0,2},{1,3}}, use_global_device_ids=true", "dp"),
    ("replica_groups={{0,1,2,3}}", "dp,tp"),
    ("replica_groups={}", "dp,tp"),
    ("replica_groups=[2,2]<=[4], use_global_device_ids=true", "tp"),
    ("replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true", "dp"),
    ("replica_groups=[1,4]<=[4]", "dp,tp"),
    ("source_target_pairs={{0,1},{1,0},{2,3},{3,2}}", "tp"),
    ("source_target_pairs={{0,2},{2,0},{1,3},{3,1}}", "dp"),
    ("replica_groups={{0,7}}", None),               # not this mesh's numbers
    ("channel_id=3", None),
])
def test_collective_axes_in_both_group_forms(attrs, axes):
    assert collective_axes(attrs, MESH) == axes
    assert collective_axes(attrs, ()) == ""


def test_psums_over_dp_and_tp_get_their_axes_on_a_2x2_mesh():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))

    def f(x):
        with jax.named_scope("grads"):
            a = jax.lax.psum(x, "dp")
        with jax.named_scope("ffn"):
            return jax.lax.psum(a * 2.0, "tp")

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("dp", "tp"),
                              out_specs=P(None, None)))
    text = g.lower(jnp.ones((8, 8))).compile().as_text()
    table = scope_table(text, tuple(mesh.shape.items()))
    found = {r.axes: r.path for r in table.values() if r.axes is not None}
    assert found == {"dp": ("grads",), "tp": ("ffn",)}
    # without the mesh a collective is still told from other operations
    bare = scope_table(text)
    assert {r.axes for r in bare.values() if r.axes is not None} == {""}


def test_an_asynchronous_done_takes_its_starts_axes():
    text = """HloModule m

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %x), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(f)/transpose(jvp(gpt))/psum"}
  ROOT %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars), metadata={op_name="jit(f)/transpose(jvp(gpt))/psum"}
}
"""
    table = scope_table(text, MESH)
    assert table["ars"].axes == table["ard"].axes == "dp"
    assert table["ard"].path == ("gpt",) and table["ard"].backward


# ---------------------------------------------------------------------------
# the join


def row(path, remat=False, backward=False, axes=None, opcode="fusion",
        inherited=False):
    return ScopeRow(tuple(path), remat, backward, axes, opcode,
                    "/".join(path), inherited)


TABLE = {
    "while.1": row(["gpt"], opcode="while"),
    "fusion.1": row(["gpt", "attn"]),
    "fusion.2": row(["gpt", "attn"], remat=True, backward=True),
    "psum.3": row(["gpt"], backward=True, axes="dp", opcode="all-reduce"),
    "copy.4": row(["gpt", "attn"], opcode="copy", inherited=True),
    "fusion.9": row([]),
}


def synthetic_events():
    # a while [100, 1100) over two body operations, twice each, in both
    # name forms; then a layout copy placed with attn, a collective, an
    # unscoped fusion and a stranger
    return [
        ("while.1 [while]", 100, 1000),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 110, 300),
        ("fusion.2 [fusion]", 420, 200),
        ("fusion.1", 630, 300),
        ("fusion.2 [fusion]", 940, 100),
        ("copy.4 [copy]", 1150, 20),
        ("psum.3 [all-reduce]", 1200, 50),
        ("fusion.9 [fusion]", 1300, 70),
        ("copy.77 [copy]", 1400, 30),
    ]


def test_device_scopes_sums_self_time_exactly():
    got = profiler.device_scopes(synthetic_events(), TABLE)
    assert got["total_ns"] == 1000 + 20 + 50 + 70 + 30 and got["table"] == 0
    p = got["paths"]
    assert set(p) == {"gpt", "gpt/attn", profiler.UNSCOPED,
                      profiler.NOT_IN_TABLE}
    # the loop keeps only what its body does not take: 1000 - 900
    assert p["gpt"]["ns"] == 100 + 50 and p["gpt"]["calls"] == 2
    assert p["gpt"]["ops"] == {"while.1": (100, 1), "psum.3": (50, 1)}
    assert p["gpt"]["axes"] == {"dp": 50} and p["gpt"]["backward_ns"] == 50
    assert p["gpt/attn"] == dict(
        path=("gpt", "attn"), calls=5, ns=920, remat_ns=300, backward_ns=300,
        inherited_ns=20, axes={},
        ops={"fusion.1": (600, 2), "fusion.2": (300, 2), "copy.4": (20, 1)})
    assert p["gpt"]["inherited_ns"] == 0
    assert p[profiler.UNSCOPED]["ns"] == 70
    assert p[profiler.NOT_IN_TABLE]["ns"] == 30
    assert sum(at["ns"] for at in p.values()) == got["total_ns"]


@pytest.mark.parametrize("select, ns", [
    (dict(scopes=["attn"]), 920),
    (dict(scopes=["gpt"]), 1070),
    (dict(scopes=["attn", "nowhere"]), 920),
    (dict(remat=True), 300),
    (dict(scopes=["attn"], remat=True), 300),
    (dict(axes="dp"), 50),
    (dict(axes="tp"), 0),
    (dict(unscoped=True), 100),
    (dict(inherited=True), 20),
    (dict(scopes=["attn"], inherited=True), 20),
    (dict(unscoped=True, inherited=True), 0),
    (dict(), 1170),
])
def test_scope_ns_selects(select, ns):
    got = profiler.device_scopes(synthetic_events(), TABLE)
    assert profiler.scope_ns(got, **select) == ns


def test_two_programs_under_one_name_are_told_apart_by_coverage():
    small = {"fusion.1": row(["check"]), "fusion.2": row(["check"])}
    events = synthetic_events()
    assert profiler.device_scopes(events, [small, TABLE])["table"] == 1
    assert profiler.device_scopes(events, [TABLE, small])["table"] == 0
    # events the small program names whole are the small program's
    own = [e for e in events if e[0].split(" ")[0].lstrip("%")
           in ("fusion.1", "fusion.2")]
    got = profiler.device_scopes(own, [TABLE, small])
    assert got["table"] == 1 and set(got["paths"]) == {"check"}
    assert profiler.device_scopes(events, [])["table"] is None


def test_events_inside_keeps_whole_events_of_the_runs():
    events = [("a", 0, 10), ("b", 10, 10), ("c", 25, 10), ("d", 40, 5),
              ("e", 44, 10)]
    assert profiler.events_inside(events, [(40, 50), (10, 35)]) == [
        ("b", 10, 10), ("c", 25, 10), ("d", 40, 5)]


# ---------------------------------------------------------------------------
# the registry


def test_registration_calls_no_thunk_and_the_registry_keeps_eight():
    def boom():
        raise RuntimeError("asked")

    for i in range(profiler.PROGRAMS + 3):
        profiler.register_program(f"jit_m{i}", boom)
    assert len(profiler._programs) == profiler.PROGRAMS == 8
    assert profiler.program_tables("jit_m0") == []      # fell off the end
    assert profiler.program_tables("jit_other") == []
    with pytest.raises(RuntimeError, match="asked"):
        profiler.program_tables("jit_m10")
    # a second registration under one name and key takes the first's place
    profiler.register_program("jit_k", boom, key="a")
    profiler.register_program("jit_k", lambda: "HloModule m\n", key="a")
    assert profiler.program_tables("jit_k") == [{}]
    assert profiler.module_name("jit_train_step(1033786790)") == "jit_train_step"


def trainer_and_batch():
    def net(x, label):
        with pt.framework.name_scope("trunk"):
            h = layers.fc(x, 64, act="relu", name="h")
        p = layers.fc(h, 4, name="o")
        return {"loss": layers.mean(
            layers.softmax_with_cross_entropy(p, label))}

    rng = np.random.RandomState(0)

    def batch():
        return {"x": rng.randn(8, 16).astype("float32"),
                "label": rng.randint(0, 4, (8, 1)).astype("int64")}

    tr = pt.Trainer(pt.build(net), opt.Adam(1e-3), loss_name="loss")
    tr.startup(sample_feed=batch())
    return tr, batch


def test_a_trainer_registers_once_a_feed_shape_and_pins_no_array():
    from paddle_tpu.debugger import _lower_step

    tr, batch = trainer_and_batch()
    assert not profiler._programs                    # startup runs no step
    for _ in range(3):
        tr.step(batch())
    assert [p[0] for p in profiler._programs] == ["jit_train_step"]
    assert tr._trace_count == tr._traces_registered
    want = scope_table(_lower_step(tr, batch()).compile().as_text())
    k = {n: np.stack([batch()[n] for _ in range(2)]) for n in ("x", "label")}
    tr.run_steps(k)
    assert [p[0] for p in profiler._programs] == ["jit_train_step",
                                                  "jit_run_k_steps"]
    assert all(p[4] is None for p in profiler._programs)

    leaf = weakref.ref(next(iter(tr.scope.params.values())))
    alive = weakref.ref(tr)
    del tr
    gc.collect()
    assert alive() is None and leaf() is None
    # the Trainer is gone, its program's table is still to be had: the
    # table of the executable that ran, name for name
    (table,) = profiler.program_tables("jit_train_step")
    assert table == want
    assert any(r.path == ("optimizer",) for r in table.values())
    assert any(r.path == ("trunk",) for r in table.values())
    (fused,) = profiler.program_tables("jit_run_k_steps")
    assert any(r.opcode == "while" for r in fused.values())
    spans = [s for s in profiler.spans() if s[0] == "profiler.program_table"]
    assert spans[-1][4]["rows"] == len(fused) and spans[-1][4]["text_bytes"] > 0


def test_a_fitted_trainer_with_a_cache_and_a_guard_is_let_go():
    """``fit(device_cache=...)`` binds an HBM dataset that points back at
    its Trainer, a guard leaves a device mask and a feed pending: the
    registered stand-in holds neither, nor the scope's ``extra``."""
    from paddle_tpu import resilience

    tr, batch = trainer_and_batch()
    tr2 = pt.Trainer(tr.program, opt.Adam(1e-3), loss_name="loss",
                     guard=resilience.GuardPolicy())
    rows = [[(b["x"][i], b["label"][i]) for i in range(8)]
            for b in (batch() for _ in range(4))]
    tr2.startup(sample_feed=batch())
    tr2.scope.extra["big"] = extra = jnp.zeros((256, 256))
    profiler._programs.clear()
    pt.fit(tr2, lambda: iter(rows), num_epochs=2, feed_names=["x", "label"],
           dtypes=["float32", "int64"], steps_per_dispatch=2,
           device_cache=1 << 30)
    assert tr2.device_cache is not None and tr2._guard is not None
    tr2.step(batch())                       # leaves a readback pending
    assert tr2._guard_pending is not None
    assert {p[0] for p in profiler._programs} == {"jit_train_step",
                                                  "jit_run_k_steps"}
    cached = next(a for a in jax.tree.leaves(vars(tr2.device_cache))
                  if isinstance(a, jax.Array))
    held = [weakref.ref(o) for o in (
        tr2, tr2.device_cache, cached, extra, tr2._guard_pending[0],
        next(iter(tr2.scope.params.values())))]
    del tr2, cached, extra, tr
    gc.collect()
    assert [r() for r in held] == [None] * len(held)
    # and the stand-in still gives the guarded program's table
    (table,) = profiler.program_tables("jit_train_step")
    assert any(r.path == ("optimizer",) for r in table.values())


def test_a_sharded_trainer_registers_its_mesh_axes():
    tr, batch = trainer_and_batch()
    mesh = pt.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    tr2 = pt.Trainer(tr.program, opt.Adam(1e-3), loss_name="loss", mesh=mesh)
    tr2.startup(sample_feed=batch())
    profiler._programs.clear()
    tr2.step(batch())
    (p,) = profiler._programs
    assert p[0] == "jit_train_step" and p[3] == (("dp", 2), ("tp", 2))
    (table,) = profiler.program_tables("jit_train_step")
    axes = {r.axes for r in table.values() if r.axes is not None}
    assert axes and axes <= {"dp", "tp", "dp,tp"}


# ---------------------------------------------------------------------------
# the operator's table


def test_profiler_with_a_trace_dir_prints_the_host_table_on_cpu(tmp_path,
                                                                capsys):
    tr, batch = trainer_and_batch()
    tr.step(batch())
    with profiler.profiler(str(tmp_path / "trace")):
        for _ in range(3):
            out = tr.step(batch())
        jax.block_until_ready(out)
    printed = capsys.readouterr().out
    assert "trainer.step" in printed and "Event" in printed
    # a CPU trace has no device plane: no device rows, nothing else printed
    assert "Device scope" not in printed
    assert profiler.device_rows(str(tmp_path / "trace")) == []
    assert profiler.device_rows(str(tmp_path / "nothing")) == []
    # and no program's text was asked for
    assert all(p[4] is None for p in profiler._programs)


XSPACE = """planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 9000000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 12000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%flash_fwd.2 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %b.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "jit_train_step(77)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (s32[], bf16[8,128]{1,0}) while((s32[], bf16[8,128]{1,0}) %t.1), condition=%c, body=%b" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.9 = bf16[8,128]{1,0} copy(bf16[8,128]{1,0} %x)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_other(5)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 16000000 } }
  event_metadata { key: 1 value { id: 1 name: "trainer.step" } }
}
"""

STEP_HLO = """HloModule jit_train_step

%b (t: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %t = (s32[], bf16[8,128]{1,0}) parameter(0)
  %p.1 = bf16[8,128]{1,0} get-tuple-element((s32[], bf16[8,128]{1,0}) %t), index=1
  %fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop, calls=%f, metadata={op_name="jit(train_step)/jvp(gpt)/while/body/closed_call/attn/mul"}
  %flash_fwd.2 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(gpt))/while/body/closed_call/checkpoint/rematted_computation/attn/pallas_call"}
  %i = s32[] get-tuple-element((s32[], bf16[8,128]{1,0}) %t), index=0
  ROOT %r = (s32[], bf16[8,128]{1,0}) tuple(s32[] %i, bf16[8,128]{1,0} %flash_fwd.2)
}

%c (t: (s32[], bf16[8,128])) -> pred[] {
  %t = (s32[], bf16[8,128]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (x: bf16[8,128]) -> bf16[8,128] {
  %x = bf16[8,128]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t.1 = (s32[], bf16[8,128]{1,0}) tuple(s32[] %z, bf16[8,128]{1,0} %x)
  %while.1 = (s32[], bf16[8,128]{1,0}) while((s32[], bf16[8,128]{1,0}) %t.1), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp(gpt)/while"}
  ROOT %o = bf16[8,128]{1,0} get-tuple-element((s32[], bf16[8,128]{1,0}) %while.1), index=1
}
"""


def test_device_rows_of_a_trace_with_a_device_plane(tmp_path, capsys,
                                                    monkeypatch):
    from jax.profiler import ProfileData

    run = tmp_path / "trace" / "plugins" / "profile" / "2026_09_29"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    profiler.register_program("jit_train_step", lambda: STEP_HLO)
    rows = profiler.device_rows(str(tmp_path / "trace"))
    # only the registered module's executions; copy.9 is not in its table
    assert [(r["module"], r["name"], r["calls"]) for r in rows] == [
        ("jit_train_step", "gpt/attn", 2), ("jit_train_step", "gpt", 1),
        ("jit_train_step", profiler.NOT_IN_TABLE, 1)]
    attn, loop, stranger = rows
    assert attn["total"] == pytest.approx(7000 / 1e6)
    assert attn["remat"] == attn["backward"] == pytest.approx(2000 / 1e6)
    assert attn["inherited"] == 0.0
    assert loop["total"] == pytest.approx(1000 / 1e6)
    assert stranger["total"] == pytest.approx(500 / 1e6)
    assert sum(r["share"] for r in rows) == pytest.approx(100.0)

    # the operator's table: device rows under the host rows
    monkeypatch.setattr(profiler, "device_rows", lambda d: rows)
    with profiler.profiler(str(tmp_path / "other")):
        with profiler.record_event("trainer.step", step=0):
            pass
    printed = capsys.readouterr().out
    host, device = printed.index("trainer.step"), printed.index("Device scope")
    assert host < device < printed.index("gpt/attn (jit_train_step)")
    assert "Inher(ms)" in printed
